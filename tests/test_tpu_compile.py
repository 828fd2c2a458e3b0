"""Compile rehearsal: every Pallas entry point, compiled for a TPU v5e.

Interpret-mode parity (tests/test_backend.py, tests/test_megakernel.py)
cannot see what only the TPU compiler refuses: a block that is not a whole
number of (8, 128) tiles, a value-level dynamic slice, more VMEM than a
kernel may use.  Each test here compiles one entry point of
`repro.core.backend.PallasBackend` for one chip of a *described* v5e:2x2
topology — no chip attached, shapes only — at the width the chip smoke runs
(d = 100 metrics), and checks that the kernel is in the compiled program.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.backend import PallasBackend

D = 100          # metrics per host (TSBS devops width)
N = 65536        # rows per primitive call
H = 8            # autocovariance lags
W = 32           # moment window
SEG, STEP = 64, 32   # Welch nperseg / stride
NPERSEG = 256    # standalone segment-DFT length
B = 8            # banded-matvec bandwidth
TENANTS = 4000   # served path: one vmapped chunk update per tenant
CHUNK = 64       # rows per tenant chunk

BE = PallasBackend(interpret=False)


def _taper(n):
    return jnp.hanning(n).astype(jnp.float32)


def _spec(shape, dtype=jnp.float32):
    return (tuple(shape), dtype)


# name -> (call(*args), [arg (shape, dtype)])
CASES = {
    "lagged_sums": (
        lambda x: BE.lagged_sums(x, H),
        [_spec((N, D))],
    ),
    "masked_lagged_sums": (
        lambda y, m: BE.masked_lagged_sums(y, m, H),
        [_spec((N + H, D)), _spec((N,), jnp.bool_)],
    ),
    "windowed_moments": (
        lambda x: BE.windowed_moments(x, W),
        [_spec((N, D))],
    ),
    "fused_lagged_moments": (
        lambda y, m: BE.fused_lagged_moments(y, m, H, (W,)),
        [_spec((N + W - 1, D)), _spec((N,), jnp.bool_)],
    ),
    "segment_fft_power": (
        lambda s: BE.segment_fft_power(s, _taper(NPERSEG)),
        [_spec((N // NPERSEG, NPERSEG, D))],
    ),
    "segment_csd": (
        lambda s: BE.segment_csd(s, _taper(NPERSEG)),
        [_spec((N // NPERSEG, NPERSEG, D))],
    ),
    "banded_matvec": (
        lambda diags, x: BE.banded_matvec(diags, x),
        [_spec((N, 2 * B + 1)), _spec((D, N))],
    ),
    "fused_plan_update_lag_moments": (
        lambda y, m, z0: BE.fused_plan_update(y, m, z0, H, (W,)),
        [_spec((N + W - 1, D)), _spec((N,), jnp.bool_), _spec((), jnp.int32)],
    ),
    "fused_plan_update_lag_moments_welch": (
        lambda y, m, z0: BE.fused_plan_update(
            y, m, z0, H, (W,), (SEG,), (STEP,), (_taper(SEG),)
        ),
        [_spec((N + SEG - 1, D)), _spec((N,), jnp.bool_), _spec((), jnp.int32)],
    ),
    # the served ingest: the megakernel vmapped over every tenant's chunk
    "fused_plan_update_served_vmap": (
        jax.vmap(
            lambda y, m, z0: BE.fused_plan_update(
                y, m, z0, H, (W,), (SEG,), (STEP,), (_taper(SEG),)
            )
        ),
        [
            _spec((TENANTS, CHUNK + SEG - 1, D)),
            _spec((TENANTS, CHUNK), jnp.bool_),
            _spec((TENANTS,), jnp.int32),
        ],
    ),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    call, specs = CASES[name]
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in specs
    ]
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
