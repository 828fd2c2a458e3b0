"""Calibration-subsystem suite (`repro.core.calibrate`).

Pins the PR 5 policy contract: the "auto" backend dispatches every
primitive through per-primitive *measured* crossovers — default table when
nothing is cached (off-accelerator: always jnp), cache round-trip, measured
tables actually steering dispatch, and platform hygiene (a cache from
another platform is never misapplied).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import calibrate as cal
from repro.core.backend import (
    AutoBackend,
    JnpBackend,
    PallasBackend,
    get_backend,
)

pytestmark = pytest.mark.backend


@pytest.fixture(autouse=True)
def _isolate_active_table():
    """Tests install tables process-wide (calibrate / tune_blocks /
    set_active_table); reset to lazy read-through afterwards."""
    yield
    cal.set_active_table(None)


def _table(thresholds, platform=None, source="test"):
    return cal.CalibrationTable(
        platform or jax.default_backend(), dict(thresholds), source
    )


class _Recording(PallasBackend):
    """Pallas backend that counts which primitives were dispatched to it."""

    def __init__(self):
        super().__init__(interpret=True)
        self.calls = []

    def __getattribute__(self, name):
        attr = object.__getattribute__(self, name)
        if name in cal.PRIMITIVES:
            calls = object.__getattribute__(self, "calls")

            def wrapped(*args, **kwargs):
                calls.append(name)
                return attr(*args, **kwargs)

            return wrapped
        return attr


def _drive_all_primitives(be):
    """One small call per registered primitive through ``be``."""
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 2))
    y = jax.random.normal(jax.random.PRNGKey(1), (52, 2))
    mask = jnp.ones((48,), jnp.bool_)
    segs = jax.random.normal(jax.random.PRNGKey(2), (3, 16, 2))
    taper = jnp.hanning(16)
    diags = jax.random.normal(jax.random.PRNGKey(3), (48, 5))
    be.lagged_sums(x, 4)
    be.masked_lagged_sums(y, mask, 4)
    be.windowed_moments(x, 8)
    be.segment_fft_power(segs, taper)
    be.segment_csd(segs, taper)
    be.banded_matvec(diags, x[:, 0])
    be.fused_lagged_moments(y, mask, 4, 8)
    be.fused_plan_update(y, mask, 0, 4, (8,), (16,), (8,), (taper,))


def test_default_table_off_accelerator_never_picks_pallas():
    table = cal.default_table("cpu")
    assert set(table.thresholds) == set(cal.PRIMITIVES)
    assert all(math.isinf(v) for v in table.thresholds.values())
    # ...and a TPU default exists for every primitive (finite sane values)
    tpu = cal.default_table("tpu")
    assert set(tpu.thresholds) == set(cal.PRIMITIVES)
    assert all(np.isfinite(v) and v > 0 for v in tpu.thresholds.values())


def test_auto_dispatch_follows_injected_table():
    rec = _Recording()
    # threshold 0: everything crosses over → every primitive hits pallas
    auto = AutoBackend(
        pallas_backend=rec, table=_table({p: 0.0 for p in cal.PRIMITIVES})
    )
    _drive_all_primitives(auto)
    assert sorted(set(rec.calls)) == sorted(cal.PRIMITIVES)
    # threshold inf: nothing does
    rec2 = _Recording()
    auto2 = AutoBackend(
        pallas_backend=rec2,
        table=_table({p: math.inf for p in cal.PRIMITIVES}),
    )
    _drive_all_primitives(auto2)
    assert rec2.calls == []


def test_auto_per_primitive_thresholds_are_independent():
    rec = _Recording()
    thresholds = {p: math.inf for p in cal.PRIMITIVES}
    thresholds["lagged_sums"] = 10.0  # only this one crosses over
    auto = AutoBackend(pallas_backend=rec, table=_table(thresholds))
    _drive_all_primitives(auto)
    assert set(rec.calls) == {"lagged_sums"}
    # parity while doing so
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 2))
    np.testing.assert_allclose(
        auto.lagged_sums(x, 3), JnpBackend().lagged_sums(x, 3), atol=1e-4
    )


def test_cache_roundtrip_and_platform_hygiene(tmp_path, monkeypatch):
    path = tmp_path / "calib.json"
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(path))
    table = _table(
        {p: (512.0 if i % 2 else math.inf) for i, p in enumerate(cal.PRIMITIVES)},
        source="measured",
    )
    cal.save_table(table)
    loaded = cal.load_table()
    assert loaded is not None and loaded.source == "cache"
    assert loaded.thresholds == table.thresholds  # inf survives JSON (null)
    # resolve_table prefers the installed cache over the defaults
    resolved = cal.resolve_table()
    assert resolved.thresholds == table.thresholds
    # a cache written on another platform is ignored, never misapplied
    alien = _table({p: 1.0 for p in cal.PRIMITIVES}, platform="tpu")
    cal.save_table(alien)
    assert cal.load_table() is None
    assert cal.resolve_table().source == "default"


def test_resolve_table_never_measures(tmp_path, monkeypatch):
    """With no installed table, even on TPU, resolution returns the built-in
    table and measures nothing: the first "auto" dispatch happens while a
    served program is being traced, where a timing would time the tracer."""
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(tmp_path / "absent.json"))

    def refuse(*args, **kwargs):
        raise AssertionError("resolve_table ran a calibration")

    monkeypatch.setattr(cal, "calibrate", refuse)
    monkeypatch.setattr(cal, "tune_blocks", refuse)
    table = cal.resolve_table(platform="tpu")
    assert table.source == "default" and table.platform == "tpu"
    assert table.thresholds == cal.default_table("tpu").thresholds
    assert cal.active_table() is table


def test_calibrate_measures_all_primitives_and_persists(tmp_path, monkeypatch):
    path = tmp_path / "calib.json"
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(path))
    table = cal.calibrate(sizes=(32, 64), d=2, iters=1, warmup=0, save=True)
    assert table.source == "measured"
    assert set(table.thresholds) == set(cal.PRIMITIVES)
    for v in table.thresholds.values():
        assert math.isinf(v) or v in (32.0, 64.0)
    assert path.exists()
    # a fresh resolve (e.g. a new process's first "auto" dispatch) reads it
    assert cal.resolve_table().thresholds == table.thresholds


def test_registry_auto_has_no_hardcoded_row_constant():
    """The acceptance pin: the registered "auto" policy carries a
    calibration table (resolved lazily), not a min_rows constant."""
    auto = get_backend("auto")
    assert not hasattr(auto, "min_rows")
    assert set(auto.table.thresholds) == set(cal.PRIMITIVES)

# ------------------------------------------------- PR 7: blocks + stale cache


def test_stale_cache_missing_primitive_falls_back_to_builtin():
    """Satellite-6 pin: a cached table that predates ``fused_plan_update``
    (or any newly registered primitive) must degrade to the BUILT-IN
    default for the table's platform — never a KeyError, never a blanket
    "always pallas"."""
    old = {p: 0.0 for p in cal.PRIMITIVES if p != "fused_plan_update"}
    stale_cpu = _table(old, platform="cpu", source="cache")
    assert math.isinf(stale_cpu.crossover("fused_plan_update"))
    stale_tpu = _table(old, platform="tpu", source="cache")
    assert stale_tpu.crossover("fused_plan_update") == 4096.0
    # dispatch through the auto policy: the missing primitive quietly runs
    # on jnp (cpu built-in = inf), everything present still crosses over
    rec = _Recording()
    auto = AutoBackend(pallas_backend=rec, table=_table(old, platform="cpu"))
    _drive_all_primitives(auto)
    assert "fused_plan_update" not in rec.calls
    assert "lagged_sums" in rec.calls


def test_blocks_json_roundtrip_and_resolution(tmp_path, monkeypatch):
    """Tuned tile configs survive the cache round-trip and steer
    `repro.kernels.tiling.resolve_block` (override > table > default)."""
    from repro.kernels.tiling import DEFAULT_BLOCKS, resolve_block

    path = tmp_path / "calib.json"
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(path))
    table = _table({p: math.inf for p in cal.PRIMITIVES}, source="measured")
    table.blocks = {
        "lagged_sums": {"block_t": 256},
        "segment_fft_power": {"block_s": 2},
    }
    cal.save_table(table)
    loaded = cal.load_table()
    assert loaded.blocks == table.blocks
    assert loaded.block_config("lagged_sums") == {"block_t": 256}
    assert loaded.block_config("banded_matvec") == {}  # never tuned

    cal.set_active_table(loaded)
    assert cal.active_blocks("lagged_sums") == {"block_t": 256}
    assert resolve_block("lagged_sums", "block_t", None) == 256
    assert resolve_block("segment_fft_power", "block_s", None) == 2
    # explicit override beats the table; untuned primitive gets the default
    assert resolve_block("lagged_sums", "block_t", 64) == 64
    assert (
        resolve_block("banded_matvec", "block_rows", None)
        == DEFAULT_BLOCKS["banded_matvec"]["block_rows"]
    )
    # reset → lazy read-through finds the same persisted blocks
    cal.set_active_table(None)
    assert cal.active_blocks("lagged_sums") == {"block_t": 256}


def test_tune_blocks_records_all_tunable_primitives(tmp_path, monkeypatch):
    path = tmp_path / "calib.json"
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(path))
    monkeypatch.setattr(
        cal, "BLOCK_CANDIDATES",
        {"block_t": (32, 64), "block_s": (2, 4), "block_rows": (32,)},
    )
    table = cal.tune_blocks(n=48, iters=1, warmup=0, save=True)
    assert set(table.blocks) == set(cal.TUNABLE_BLOCKS)
    for prim, params in cal.TUNABLE_BLOCKS.items():
        for param in params:
            assert table.blocks[prim][param] in cal.BLOCK_CANDIDATES[param]
    # persisted AND installed as the active table
    assert cal.load_table().blocks == table.blocks
    assert cal.active_table() is table


def test_calibrate_tune_blocks_one_artifact(tmp_path, monkeypatch):
    """``calibrate(tune_blocks=True)`` yields ONE table carrying both the
    dispatch thresholds and the kernel geometry."""
    path = tmp_path / "calib.json"
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(path))
    monkeypatch.setattr(
        cal, "BLOCK_CANDIDATES",
        {"block_t": (32,), "block_s": (2,), "block_rows": (32,)},
    )
    table = cal.calibrate(
        sizes=(32,), d=2, iters=1, warmup=0, save=True, tune_blocks=True
    )
    assert set(table.thresholds) == set(cal.PRIMITIVES)
    assert set(table.blocks) == set(cal.TUNABLE_BLOCKS)
    reloaded = cal.load_table()
    assert reloaded.blocks == table.blocks


def test_cli_show_and_bless(tmp_path, monkeypatch, capsys):
    import json

    path = tmp_path / "calib.json"
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(path))
    assert cal.main(["--show"]) == 0
    out = capsys.readouterr().out
    assert "crossover thresholds" in out and "tuned tile configs" in out

    def _payload(platform):
        t = _table(
            {p: 128.0 for p in cal.PRIMITIVES},
            platform=platform,
            source="measured",
        )
        t.blocks = {"lagged_sums": {"block_t": 128}}
        return t.to_json()

    # bless: wrong platform refused, right platform installed as the cache
    alien = tmp_path / "alien.json"
    alien.write_text(json.dumps(_payload("definitely-not-this-platform")))
    assert cal.main(["--bless", str(alien)]) == 1
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_payload(jax.default_backend())))
    assert cal.main(["--bless", str(good)]) == 0
    assert path.exists()
    assert cal.load_table().blocks == {"lagged_sums": {"block_t": 128}}


# --------------------------------------------- PR 8: corrupt-cache hygiene


@pytest.mark.parametrize(
    "body",
    [
        "{not json",                        # truncated / invalid JSON
        '{"thresholds": 42}',               # valid JSON, wrong structure
        '["a", "list"]',                    # valid JSON, wrong top type
        '{"platform": null, "thresholds": {"lagged_sums": "NaNish"}}',
    ],
)
def test_corrupt_cache_degrades_to_defaults_with_warning(
    tmp_path, monkeypatch, body
):
    """A torn or hand-mangled cache file must never crash the "auto"
    policy's first dispatch: load_table warns and returns None, and
    resolve_table falls through to the built-in defaults."""
    path = tmp_path / "calib.json"
    path.write_text(body)
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(path))
    with pytest.warns(RuntimeWarning, match="corrupt calibration cache"):
        assert cal.load_table() is None
    with pytest.warns(RuntimeWarning):
        resolved = cal.resolve_table()
    assert resolved.source == "default"
    assert set(resolved.thresholds) == set(cal.PRIMITIVES)


def test_cli_bless_rejects_corrupt_table(tmp_path, monkeypatch, capsys):
    path = tmp_path / "calib.json"
    monkeypatch.setenv("REPRO_CALIB_CACHE", str(path))
    bad = tmp_path / "bad.json"
    bad.write_text('{"thresholds": 42}')
    assert cal.main(["--bless", str(bad)]) == 1
    assert "refusing to bless" in capsys.readouterr().out
    assert cal.main(["--bless", str(tmp_path / "missing.json")]) == 1
    assert "cannot read" in capsys.readouterr().out
    assert not path.exists()                 # nothing was installed
