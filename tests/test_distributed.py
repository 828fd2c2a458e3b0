"""Distributed tests — run in a subprocess with 8 host devices so the main
pytest process keeps its single device (brief requirement)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # jit-heavy: deselected by default, use --runslow


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    # 8 virtual CPU devices, never the accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_store_and_halo_modes():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.timeseries.dataset import TimeSeriesStore
        from repro.core.mapreduce import serial_window_map_reduce
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        x = jax.random.normal(jax.random.PRNGKey(0), (8*128, 3))
        kern = lambda w: jnp.outer(w[0], w[-1])
        s0 = serial_window_map_reduce(kern, x, 2, 3)
        for mode in ("replicate", "exchange"):
            st = TimeSeriesStore.from_series(x, 128, 2, 3, mesh=mesh, halo_mode=mode)
            r = st.map_reduce(kern)
            err = float(jnp.max(jnp.abs(r - s0)))
            assert err < 1e-3, (mode, err)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_autocovariance_exact():
    out = _run("""
        import jax
        import jax.numpy as jnp
        from repro.core.estimators.stats import autocovariance, autocovariance_sharded
        from repro.timeseries.dataset import TimeSeriesStore
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        x = jax.random.normal(jax.random.PRNGKey(1), (8*256, 4))
        st = TimeSeriesStore.from_series(x, 256, 0, 6, mesh=mesh)
        g = autocovariance_sharded(st.blocks, st.spec, 6, mesh)
        ref = autocovariance(x, 6)
        assert float(jnp.max(jnp.abs(g - ref))) < 1e-4
        print("OK")
    """)
    assert "OK" in out


def test_halo_exchange_equals_replication():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.halo import halo_exchange
        from repro.core.overlap import OverlapSpec, make_overlapping_blocks
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        n, d = 8*64, 3
        x = jax.random.normal(jax.random.PRNGKey(2), (n, d))
        hl, hr = 4, 5
        # replication-mode blocks with block_size = local shard size
        spec = OverlapSpec(n=n, block_size=64, h_left=hl, h_right=hr)
        blocks_ref, _ = make_overlapping_blocks(x, spec)
        def f(x_local):
            return halo_exchange(x_local, hl, hr, "data")
        padded = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False))(x)
        padded = padded.reshape(8, hl + 64 + hr, d)
        assert float(jnp.max(jnp.abs(padded - blocks_ref))) == 0.0
        print("OK")
    """)
    assert "OK" in out


def test_train_step_on_mesh():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import ARCHS
        from repro.models import init_params
        from repro.parallel import sharding as shr
        from repro.training.optimizer import adamw_init
        from repro.training.train_step import make_train_step
        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        r = ARCHS["qwen3-0.6b"].reduced()
        with mesh, jax.sharding.set_mesh(mesh):
            params = init_params(jax.random.PRNGKey(0), r, dtype=jnp.float32)
            pspecs = shr.param_pspecs(params, mesh)
            params = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspecs)
            opt = adamw_init(params)
            toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, r.vocab)
            batch = {"tokens": jax.device_put(toks, NamedSharding(mesh, P("data", None))),
                     "labels": jax.device_put(toks, NamedSharding(mesh, P("data", None)))}
            step = jax.jit(make_train_step(r, lr_fn=1e-3))
            params, opt, m = step(params, opt, batch)
            assert jnp.isfinite(m["loss"])
            # loss equals single-device computation
        print("OK", float(m["loss"]))
    """)
    assert "OK" in out


def test_sharded_matches_single_device_loss():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import ARCHS
        from repro.models import init_params
        from repro.training.train_step import loss_fn
        r = ARCHS["qwen3-0.6b"].reduced()
        params = init_params(jax.random.PRNGKey(0), r, dtype=jnp.float32)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, r.vocab)
        batch = {"tokens": toks, "labels": toks}
        l_single, _ = jax.jit(lambda p, b: loss_fn(p, b, r))(params, batch)
        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
        with mesh, jax.sharding.set_mesh(mesh):
            pb = {k: jax.device_put(v, NamedSharding(mesh, P("data", None))) for k, v in batch.items()}
            l_mesh, _ = jax.jit(lambda p, b: loss_fn(p, b, r))(params, pb)
        diff = abs(float(l_single) - float(l_mesh))
        assert diff < 1e-3, diff
        print("OK", diff)
    """)
    assert "OK" in out


def test_build_cell_lowers_on_test_mesh():
    """Miniature dry-run inside the test suite: one cell per step kind."""
    out = _run("""
        import dataclasses, jax
        from repro.configs.registry import QWEN3_0_6B
        from repro.configs.base import ShapeConfig
        from repro.launch.steps import build_cell
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(4, 2)
        cfg = dataclasses.replace(QWEN3_0_6B, n_layers=2)
        for shape in (ShapeConfig("t", 256, 8, "train"),
                      ShapeConfig("p", 256, 8, "prefill"),
                      ShapeConfig("d", 256, 8, "decode"),
                      ShapeConfig("sp", 2048, 1, "decode")):
            cell = build_cell(cfg, shape, mesh)
            compiled = cell.lower().compile()
            assert compiled.cost_analysis() is not None
        print("OK")
    """)
    assert "OK" in out
