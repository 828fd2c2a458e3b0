"""Measured backend crossovers: the "auto" policy learns from the hardware.

The compute-backend registry (`repro.core.backend`) dispatches each of the
six primitive contractions to jnp or the Pallas tile kernels.  Where the
crossover sits — the problem size above which the tile kernel beats XLA's
fusion — is a property of the *hardware* (HBM bandwidth, MXU shape, grid
launch overhead), not something a constant in the source can know.  PR 2
shipped a single hard-coded ``min_rows=4096`` guess; this module replaces
it with measurement:

  * :func:`calibrate` microbenchmarks every registered primitive on both
    backends across a grid of problem sizes and derives a per-primitive
    **crossover threshold** — the smallest grid size at which the Pallas
    kernel wins and keeps winning for every larger size (``inf`` when it
    never does, e.g. interpret mode off-TPU);
  * the resulting :class:`CalibrationTable` is persisted to a per-platform
    cache file (:func:`save_table` / :func:`load_table`; the path honours
    ``REPRO_CALIB_CACHE``), so one calibration pass serves every later
    process on the same machine;
  * `repro.core.backend.AutoBackend` resolves its thresholds lazily at the
    first dispatch through :func:`resolve_table`, which never measures: the
    table the user installed for this platform (``--tune`` / ``--bless``
    below) if one exists, else the built-in :func:`default_table`
    (off-accelerator the Pallas path is interpret mode, never profitable,
    so the default is "always jnp").  Measuring is always an explicit step:
    a first dispatch happens while a served program is being traced, where
    a timing would time the tracer.

The built-in defaults are a *fallback*, not policy: any measured table,
cached or injected (``AutoBackend(table=...)``), overrides them.

Since the megakernel PR the table carries a second product next to the
crossovers: **tuned tile configurations**.  :func:`tune_blocks` searches the
per-primitive block-size space (``block_t`` for the windowed contractions
and the fused-plan megakernel, ``block_s`` for the segment-DFT family,
``block_rows`` for the banded matvec) on the Pallas backend and records the
winner in ``CalibrationTable.blocks``; every ``kernels/*`` ops entry point
resolves its tile size through :func:`active_blocks` (via
`repro.kernels.tiling.resolve_block`) instead of a hard-coded literal.
``calibrate(tune_blocks=True)`` runs both passes and persists one table.

Run it from the shell::

    python -m repro.core.calibrate --show          # resolved table
    python -m repro.core.calibrate --tune          # measure crossovers + blocks
    python -m repro.core.calibrate --bless t.json  # install a table file
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "PRIMITIVES",
    "TUNABLE_BLOCKS",
    "CalibrationTable",
    "block_all",
    "default_table",
    "cache_path",
    "load_table",
    "save_table",
    "resolve_table",
    "active_table",
    "active_blocks",
    "set_active_table",
    "calibrate",
    "tune_blocks",
    "main",
]

# The registered primitive contractions (`repro.core.backend.Backend`).
PRIMITIVES: Tuple[str, ...] = (
    "lagged_sums",
    "masked_lagged_sums",
    "windowed_moments",
    "segment_fft_power",
    "segment_csd",
    "banded_matvec",
    "fused_lagged_moments",
    "fused_plan_update",
)

# Built-in fallback crossovers when no measured table exists.  On TPU these
# are the PR 2 reasoning (tiles fill around 4k rows; the matmul-DFT needs
# more samples to amortize its O(L²) constant); everywhere else Pallas runs
# in interpret mode — a validation vehicle, never a serving path — so the
# crossover is "never".
_TPU_DEFAULTS: Dict[str, float] = {
    "lagged_sums": 4096.0,
    "masked_lagged_sums": 4096.0,
    "windowed_moments": 4096.0,
    "fused_lagged_moments": 4096.0,
    "fused_plan_update": 4096.0,
    "banded_matvec": 4096.0,
    "segment_fft_power": 32768.0,
    "segment_csd": 32768.0,
}

# Which tile parameter each primitive exposes to the block tuner, and the
# candidate grids :func:`tune_blocks` searches.
TUNABLE_BLOCKS: Dict[str, Tuple[str, ...]] = {
    "lagged_sums": ("block_t",),
    "masked_lagged_sums": ("block_t",),
    "windowed_moments": ("block_t",),
    "fused_lagged_moments": ("block_t",),
    "fused_plan_update": ("block_t",),
    "segment_fft_power": ("block_s",),
    "segment_csd": ("block_s",),
    "banded_matvec": ("block_rows",),
}
BLOCK_CANDIDATES: Dict[str, Tuple[int, ...]] = {
    "block_t": (128, 256, 512, 1024),
    "block_s": (2, 4, 8, 16),
    "block_rows": (128, 256, 512),
}


def _builtin_thresholds(platform: str) -> Dict[str, float]:
    if platform == "tpu":
        return dict(_TPU_DEFAULTS)
    return {p: math.inf for p in PRIMITIVES}


@dataclasses.dataclass
class CalibrationTable:
    """Per-primitive crossover thresholds + tuned tile configs, one platform.

    ``thresholds[name]`` is the problem size (rows for the windowed
    contractions, banded dimension for the matvec, total staged samples
    S·L for the segment DFT) at which the ``"auto"`` policy starts routing
    that primitive to the Pallas backend; ``math.inf`` means never.
    ``blocks[name]`` is the tuned tile configuration for that primitive's
    kernel (``{"block_t": 256}``, …) — written by :func:`tune_blocks`, read
    by every ``kernels/*`` ops entry point through
    `repro.kernels.tiling.resolve_block`.
    ``source`` records provenance: "default", "measured", or "cache".
    """

    platform: str
    thresholds: Dict[str, float]
    source: str = "default"
    blocks: Dict[str, Dict[str, int]] = dataclasses.field(default_factory=dict)

    def crossover(self, primitive: str) -> float:
        """Dispatch threshold for ``primitive``.

        A primitive absent from the table — e.g. a cached measurement that
        predates the primitive's registration — falls back to the BUILT-IN
        default for this table's platform, never to a KeyError and never
        to a blanket "always pallas": a stale cache degrades to the
        reasoned default, exactly what an uncalibrated machine gets.
        """
        if primitive in self.thresholds:
            return float(self.thresholds[primitive])
        return float(_builtin_thresholds(self.platform).get(primitive, math.inf))

    def block_config(self, primitive: str) -> Dict[str, int]:
        """Tuned tile config for ``primitive`` ({} when never tuned)."""
        return dict(self.blocks.get(primitive, {}))

    def to_json(self) -> dict:
        return {
            "platform": self.platform,
            # inf is not valid JSON — encode as null.
            "thresholds": {
                k: (None if math.isinf(v) else v)
                for k, v in self.thresholds.items()
            },
            "blocks": {
                k: {p: int(v) for p, v in cfg.items()}
                for k, cfg in self.blocks.items()
            },
            "source": self.source,
        }

    @classmethod
    def from_json(cls, payload: dict) -> "CalibrationTable":
        thresholds = {
            k: (math.inf if v is None else float(v))
            for k, v in payload.get("thresholds", {}).items()
        }
        blocks = {
            k: {p: int(v) for p, v in cfg.items()}
            for k, cfg in payload.get("blocks", {}).items()
        }
        return cls(
            platform=payload.get("platform", "unknown"),
            thresholds=thresholds,
            source=payload.get("source", "cache"),
            blocks=blocks,
        )


def default_table(platform: Optional[str] = None) -> CalibrationTable:
    """The built-in fallback table for ``platform`` (default: current)."""
    platform = platform or jax.default_backend()
    return CalibrationTable(
        platform, _builtin_thresholds(platform), source="default"
    )


def cache_path(platform: Optional[str] = None) -> str:
    """Where the measured table persists: ``$REPRO_CALIB_CACHE`` when set
    (one file, platform recorded inside), else
    ``~/.cache/repro/calibration_<platform>.json``."""
    env = os.environ.get("REPRO_CALIB_CACHE")
    if env:
        return env
    platform = platform or jax.default_backend()
    base = os.environ.get(
        "XDG_CACHE_HOME", os.path.join(os.path.expanduser("~"), ".cache")
    )
    return os.path.join(base, "repro", f"calibration_{platform}.json")


def load_table(platform: Optional[str] = None) -> Optional[CalibrationTable]:
    """The cached measured table for ``platform``, or None.  A cache written
    on a different platform is ignored, never misapplied.  A corrupt cache
    — truncated write, hand-edit gone wrong, valid JSON of the wrong shape
    — degrades to the built-in defaults with a warning instead of taking
    down every ``"auto"``-backend caller at first dispatch."""
    platform = platform or jax.default_backend()
    path = cache_path(platform)
    try:
        with open(path) as f:
            payload = json.load(f)
        table = CalibrationTable.from_json(payload)
    except OSError:
        return None
    except (ValueError, TypeError, KeyError, AttributeError) as e:
        warnings.warn(
            f"ignoring corrupt calibration cache {path!r} "
            f"({type(e).__name__}: {e}); using built-in defaults — "
            f"delete the file or re-run calibration to silence this",
            RuntimeWarning,
        )
        return None
    if table.platform != platform:
        return None
    table.source = "cache"
    return table


def save_table(table: CalibrationTable, path: Optional[str] = None) -> str:
    path = path or cache_path(table.platform)
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        json.dump(table.to_json(), f, indent=2)
        f.write("\n")
    return path


def resolve_table(platform: Optional[str] = None) -> CalibrationTable:
    """The table the ``"auto"`` backend should dispatch with, resolved at
    first use without measuring: the installed (cached) table for this
    platform > the built-in default."""
    platform = platform or jax.default_backend()
    table = load_table(platform) or default_table(platform)
    set_active_table(table)
    return table


# The table tile-size resolution reads (`repro.kernels.tiling.resolve_block`
# → :func:`active_blocks`).  Split from the AutoBackend's lazy ``table``
# because block resolution must NEVER trigger a measurement pass: the
# measurement itself calls the kernels, which resolve their blocks — a
# recursive calibration would never terminate.  ``_ACTIVE`` is set by
# explicit installs (resolve_table / calibrate / tune_blocks /
# ``AutoBackend.set_table``); until one happens, reads fall through to the
# persisted cache (memoized on the file's mtime) or the defaults.
_ACTIVE: Optional[CalibrationTable] = None
_READ_CACHE: Optional[tuple] = None  # ((path, mtime), table)


def set_active_table(table: Optional[CalibrationTable]) -> None:
    """Install ``table`` as the process-wide tile/threshold source (None
    resets to lazy read-through — tests use this for isolation)."""
    global _ACTIVE
    _ACTIVE = table


def active_table() -> CalibrationTable:
    """The table block resolution dispatches with, WITHOUT ever measuring:
    the explicitly installed table > the persisted platform cache > the
    built-in defaults."""
    if _ACTIVE is not None:
        return _ACTIVE
    global _READ_CACHE
    path = cache_path()
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        mtime = None
    key = (path, mtime)
    if _READ_CACHE is not None and _READ_CACHE[0] == key:
        return _READ_CACHE[1]
    table = load_table() or default_table()
    _READ_CACHE = (key, table)
    return table


def active_blocks(primitive: str) -> Dict[str, int]:
    """Tuned tile config for ``primitive`` from the active table ({} when
    never tuned — `repro.kernels.tiling` then applies its defaults)."""
    return active_table().block_config(primitive)


# ---------------------------------------------------------------- measurement
def block_all(out) -> None:
    """Block on EVERY jax leaf of ``out``, explicitly.

    A measurement must not return while any async leaf is still in flight:
    with donated-carry programs the visible leaf can materialize while
    sibling buffers are still being rewritten in place — blocking only the
    first leaf under-reports exactly the donation wins being measured.
    Non-array leaves (Python scalars in result dicts) are skipped.  Shared
    with the benchmark harness (`benchmarks.common`).
    """
    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def _time(fn, iters: int, warmup: int) -> float:
    """Median wall seconds per call, blocking on every output leaf."""
    for _ in range(warmup):
        block_all(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        block_all(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _workloads(
    n: int, d: int, max_lag: int, window: int, nperseg: int, bandwidth: int
) -> Dict[str, callable]:
    """One closure per primitive at problem size ``n``: builds the inputs
    once (outside the timed region) and returns ``fn(backend) -> callable``.
    Sizes are clamped so tiny grid points stay valid."""
    key = jax.random.PRNGKey(n)
    ks = jax.random.split(key, 4)
    H = min(max_lag, max(n - 1, 0))
    w = min(window, n)
    x = jax.random.normal(ks[0], (n, d))
    y = jax.random.normal(ks[1], (n + max(H, w - 1, 1), d))
    mask = jnp.ones((n,), jnp.bool_)
    L = min(nperseg, n)
    S = max(n // max(L, 1), 1)
    segs = jax.random.normal(ks[2], (S, L, d))
    taper = 0.5 - 0.5 * jnp.cos(2 * jnp.pi * jnp.arange(L) / max(L, 1))
    b = min(bandwidth, max((n - 1) // 2, 0))
    diags = jax.random.normal(ks[3], (n, 2 * b + 1))
    v = x[:, 0]

    z0 = jnp.asarray(0, jnp.int32)
    return {
        "lagged_sums": lambda be: (lambda: be.lagged_sums(x, H)),
        "masked_lagged_sums": lambda be: (
            lambda: be.masked_lagged_sums(y, mask, H)
        ),
        "windowed_moments": lambda be: (lambda: be.windowed_moments(x, w)),
        "segment_fft_power": lambda be: (
            lambda: be.segment_fft_power(segs, taper)
        ),
        "segment_csd": lambda be: (lambda: be.segment_csd(segs, taper)),
        "banded_matvec": lambda be: (lambda: be.banded_matvec(diags, v)),
        "fused_lagged_moments": lambda be: (
            lambda: be.fused_lagged_moments(y, mask, H, w)
        ),
        # the megakernel: a 3-family plan chunk update (lag + moments + DFT)
        "fused_plan_update": lambda be: (
            lambda: be.fused_plan_update(
                y, mask, z0, H, (w,), (L,), (max(L // 2, 1),), (taper,)
            )
        ),
    }


def calibrate(
    sizes: Sequence[int] = (512, 2048, 8192, 32768),
    d: int = 8,
    max_lag: int = 8,
    window: int = 64,
    nperseg: int = 256,
    bandwidth: int = 8,
    iters: int = 3,
    warmup: int = 1,
    backends: Tuple[str, str] = ("jnp", "pallas"),
    save: bool = True,
    path: Optional[str] = None,
    verbose: bool = False,
    tune_blocks: bool = False,
) -> CalibrationTable:
    """Measure per-primitive backend crossovers on THIS machine.

    For every primitive and every grid size, times the ``backends`` pair
    (median of ``iters`` after ``warmup``, blocking on every output leaf)
    and derives the crossover: the smallest grid size where the alternate
    backend is at least as fast as the baseline *and stays so for every
    larger size* — a single fluky win at one size does not flip the policy.
    ``inf`` (never) when no such size exists.

    Returns the measured :class:`CalibrationTable`; with ``save=True``
    (default) it is also persisted to the platform cache file so later
    processes skip the measurement.  Inject into a live policy with
    ``get_backend("auto").set_table(table)`` (a fresh process picks the
    cache up automatically).

    ``tune_blocks=True`` additionally runs the tile-size search
    (:func:`tune_blocks`) and records the winning per-primitive block
    configs in the same table — one calibration artifact carrying both the
    dispatch policy and the kernel geometry.
    """
    from .backend import get_backend

    base_be, alt_be = (get_backend(b) for b in backends)
    platform = jax.default_backend()
    sizes = sorted(set(int(s) for s in sizes))
    if not sizes:
        raise ValueError("need at least one calibration grid size")

    wins: Dict[str, list] = {p: [] for p in PRIMITIVES}
    for n in sizes:
        loads = _workloads(n, d, max_lag, window, nperseg, bandwidth)
        for prim in PRIMITIVES:
            t_base = _time(loads[prim](base_be), iters, warmup)
            t_alt = _time(loads[prim](alt_be), iters, warmup)
            wins[prim].append(t_alt <= t_base)
            if verbose:
                print(
                    f"calibrate {prim:<22s} n={n:<8d} "
                    f"{backends[0]}={t_base * 1e6:10.1f}us "
                    f"{backends[1]}={t_alt * 1e6:10.1f}us "
                    f"{'<<' if t_alt <= t_base else ''}"
                )

    thresholds: Dict[str, float] = {}
    for prim in PRIMITIVES:
        thr = math.inf
        # smallest size from which the alternate backend never loses again
        for i in range(len(sizes) - 1, -1, -1):
            if not wins[prim][i]:
                break
            thr = float(sizes[i])
        thresholds[prim] = thr

    table = CalibrationTable(platform, thresholds, source="measured")
    if tune_blocks:
        _tune_blocks_into(
            table,
            n=sizes[-1],
            d=d,
            max_lag=max_lag,
            window=window,
            nperseg=nperseg,
            bandwidth=bandwidth,
            iters=iters,
            warmup=warmup,
            verbose=verbose,
        )
    set_active_table(table)
    if save:
        # The measured table is the product; the cache is an optimization,
        # so an unwritable cache location only warns.
        try:
            save_table(table, path)
        except OSError as e:
            import warnings

            warnings.warn(
                f"calibration succeeded but the cache could not be written "
                f"({e}); the measured table is used for this process only"
            )
    return table


def _tune_blocks_into(
    table: CalibrationTable,
    n: int,
    d: int = 8,
    max_lag: int = 8,
    window: int = 64,
    nperseg: int = 256,
    bandwidth: int = 8,
    iters: int = 3,
    warmup: int = 1,
    verbose: bool = False,
) -> None:
    """Search :data:`BLOCK_CANDIDATES` per tunable primitive on the Pallas
    backend and record each winner in ``table.blocks`` (in place).

    The search times the SAME workload closures the crossover pass uses, one
    fresh ``PallasBackend`` per candidate (interpreted exactly when the
    registered ``"pallas"`` is) so the tile size under test is the
    explicit override — the resolution chain (override > table > default)
    guarantees the measurement cannot read the very table it is writing.
    """
    from .backend import PallasBackend, get_backend

    interpret = get_backend("pallas").interpret

    loads = _workloads(n, d, max_lag, window, nperseg, bandwidth)
    for prim, params in TUNABLE_BLOCKS.items():
        cfg: Dict[str, int] = {}
        for param in params:
            best_c, best_t = None, math.inf
            for cand in BLOCK_CANDIDATES[param]:
                be = PallasBackend(interpret=interpret, **{param: cand})
                t = _time(loads[prim](be), iters, warmup)
                if verbose:
                    print(
                        f"tune {prim:<22s} {param}={cand:<6d} "
                        f"{t * 1e6:10.1f}us"
                    )
                if t < best_t:
                    best_c, best_t = cand, t
            if best_c is not None:
                cfg[param] = int(best_c)
        if cfg:
            table.blocks[prim] = cfg


def tune_blocks(
    n: int = 32768,
    iters: int = 3,
    warmup: int = 1,
    save: bool = True,
    path: Optional[str] = None,
    verbose: bool = False,
) -> CalibrationTable:
    """Tile-size autotuning on top of the currently active table.

    Starts from :func:`active_table` (never triggers a crossover
    measurement), searches :data:`BLOCK_CANDIDATES` for every primitive in
    :data:`TUNABLE_BLOCKS`, merges the winners into ``table.blocks``,
    installs the result as the active table and (with ``save=True``)
    persists it to the platform cache.  ``calibrate(tune_blocks=True)`` is
    the one-shot that measures crossovers AND tunes blocks together.
    """
    base = active_table()
    table = CalibrationTable(
        platform=base.platform,
        thresholds=dict(base.thresholds),
        source=base.source,
        blocks={k: dict(v) for k, v in base.blocks.items()},
    )
    _tune_blocks_into(
        table, n=n, iters=iters, warmup=warmup, verbose=verbose
    )
    set_active_table(table)
    if save:
        try:
            save_table(table, path)
        except OSError as e:
            import warnings

            warnings.warn(
                f"block tuning succeeded but the cache could not be written "
                f"({e}); the tuned table is used for this process only"
            )
    return table


# ------------------------------------------------------------------------ CLI
def _print_table(table: CalibrationTable) -> None:
    print(f"platform: {table.platform}   source: {table.source}")
    print("crossover thresholds (rows; inf = always jnp):")
    for prim in PRIMITIVES:
        thr = table.crossover(prim)
        star = "" if prim in table.thresholds else "  (built-in default)"
        print(f"  {prim:<22s} {thr!r:>10}{star}")
    print("tuned tile configs (empty = kernels use built-in defaults):")
    if not table.blocks:
        print("  (none)")
    for prim, cfg in sorted(table.blocks.items()):
        pretty = ", ".join(f"{k}={v}" for k, v in sorted(cfg.items()))
        print(f"  {prim:<22s} {pretty}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m repro.core.calibrate`` — inspect / measure / install the
    calibration table.

    ``--show``         print the resolved active table (default action)
    ``--tune``         measure crossovers AND tune tile sizes, persist
    ``--tune-blocks``  tile-size search only, on top of the active table
    ``--bless PATH``   install a table JSON file as this platform's cache
    """
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m repro.core.calibrate",
        description="Measure, inspect, or install the backend calibration "
        "table (crossover thresholds + tuned tile configs).",
    )
    parser.add_argument(
        "--show", action="store_true",
        help="print the resolved active table (default when no action given)",
    )
    parser.add_argument(
        "--tune", action="store_true",
        help="measure backend crossovers and tune tile sizes, then persist "
        "to the platform cache",
    )
    parser.add_argument(
        "--tune-blocks", action="store_true",
        help="run only the tile-size search on top of the active table",
    )
    parser.add_argument(
        "--bless", metavar="PATH", default=None,
        help="validate the table JSON at PATH and install it as this "
        "platform's cache file",
    )
    parser.add_argument(
        "--no-save", action="store_true",
        help="with --tune/--tune-blocks: measure but do not write the cache",
    )
    parser.add_argument("-v", "--verbose", action="store_true")
    args = parser.parse_args(argv)

    if args.bless:
        try:
            with open(args.bless) as f:
                table = CalibrationTable.from_json(json.load(f))
        except OSError as e:
            print(f"cannot read {args.bless}: {e}")
            return 1
        except (ValueError, TypeError, KeyError, AttributeError) as e:
            print(
                f"refusing to bless {args.bless}: not a valid calibration "
                f"table ({type(e).__name__}: {e})"
            )
            return 1
        platform = jax.default_backend()
        if table.platform != platform:
            print(
                f"refusing to bless: table platform {table.platform!r} != "
                f"current platform {platform!r}",
            )
            return 1
        dest = save_table(table)
        set_active_table(table)
        print(f"blessed {args.bless} -> {dest}")
        _print_table(table)
        return 0

    if args.tune:
        table = calibrate(
            save=not args.no_save, verbose=args.verbose, tune_blocks=True
        )
    elif args.tune_blocks:
        table = tune_blocks(save=not args.no_save, verbose=args.verbose)
    else:
        table = active_table()
    _print_table(table)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
