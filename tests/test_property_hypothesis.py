"""Property-based tests (hypothesis) for the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dependency: property tests need hypothesis"
)
from hypothesis import given, settings, strategies as st

from repro.core.differencing import difference, integrate
from repro.core.mapreduce import block_window_map_reduce, serial_window_map_reduce
from repro.core.overlap import OverlapSpec, make_overlapping_blocks, reconstruct
from repro.training.compression import compress_int8, decompress_int8

SETTINGS = dict(max_examples=25, deadline=None)


@given(
    n=st.integers(8, 300),
    bs=st.integers(1, 64),
    hl=st.integers(0, 8),
    hr=st.integers(0, 8),
    d=st.integers(1, 4),
)
@settings(**SETTINGS)
def test_overlap_roundtrip_any_geometry(n, bs, hl, hr, d):
    """make_overlapping_blocks ∘ reconstruct == id for every admissible spec."""
    x = jax.random.normal(jax.random.PRNGKey(n * 7 + bs), (n, d))
    spec = OverlapSpec(n=n, block_size=bs, h_left=hl, h_right=hr)
    blocks, _ = make_overlapping_blocks(x, spec)
    np.testing.assert_array_equal(np.asarray(reconstruct(blocks, spec)), np.asarray(x))


@given(
    n=st.integers(20, 200),
    bs=st.integers(4, 50),
    hl=st.integers(0, 5),
    hr=st.integers(0, 5),
)
@settings(**SETTINGS)
def test_blocked_reduction_equals_serial_any_geometry(n, bs, hl, hr):
    """The paper's central claim, as a property over all geometries."""
    if n - hl - hr <= 0:
        return
    x = jax.random.normal(jax.random.PRNGKey(n * 13 + bs), (n, 2))
    kern = lambda w: (jnp.sum(w * w), jnp.outer(w[0], w[-1]))
    s = serial_window_map_reduce(kern, x, hl, hr)
    b = block_window_map_reduce(
        kern, x, OverlapSpec(n=n, block_size=bs, h_left=hl, h_right=hr)
    )
    np.testing.assert_allclose(s[0], b[0], rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(s[1], b[1], rtol=1e-4, atol=1e-3)


@given(order=st.integers(1, 3), n=st.integers(10, 100))
@settings(**SETTINGS)
def test_difference_integrate_inverse(order, n):
    if n <= order:
        return
    x = jnp.cumsum(jax.random.normal(jax.random.PRNGKey(n), (n, 2)), axis=0)
    dx = difference(x, order)
    initial = jnp.stack([difference(x, k)[0] for k in range(order)])
    back = integrate(dx, initial, order)
    # repeated f32 cumsum amplifies roundoff with order; scale the tolerance
    scale = float(jnp.max(jnp.abs(x))) * n ** (order - 1) + 1.0
    np.testing.assert_allclose(back, x, rtol=1e-4, atol=1e-5 * scale)


@given(scale=st.floats(1e-3, 1e3), n=st.integers(10, 2000))
@settings(**SETTINGS)
def test_int8_quantization_error_bound(scale, n):
    x = jax.random.normal(jax.random.PRNGKey(n), (n,)) * scale
    codes, s = compress_int8(x)
    back = decompress_int8(codes, s, x.shape)
    blockmax = np.asarray(s).reshape(-1) * 127.0
    err = np.abs(np.asarray(back - x))
    per_block_bound = np.repeat(np.asarray(s).reshape(-1), 256)[:n] * 0.5 + 1e-9
    assert (err <= per_block_bound).all()


@given(
    dims=st.lists(st.sampled_from([2, 3, 4, 6, 8, 16, 30]), min_size=1, max_size=3)
)
@settings(**SETTINGS)
def test_logical_spec_divisibility_fallback(dims):
    """logical_to_spec never produces a spec whose mesh axes don't divide."""
    import math

    from repro.parallel.sharding import logical_to_spec, mesh_axis_size

    mesh = jax.sharding.AbstractMesh(
        (2, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    spec = logical_to_spec(["batch", "heads", "ff"][: len(dims)], dims, mesh)
    for dim, entry in zip(dims, spec):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        assert dim % mesh_axis_size(mesh, names) == 0


# -------------------------------------------------- data-plane integrity


def _integrity_plan(compensated=False):
    from repro.core.plan import (
        autocovariance_request,
        fused_engine,
        moments_request,
    )

    return fused_engine(
        [autocovariance_request(2), moments_request(4)],
        d=2,
        backend="jnp",
        compensated=compensated,
    )


def _finite_mask(states):
    """The poisoned-lane fingerprint: finiteness of every stat leaf."""
    return [
        np.isfinite(np.asarray(leaf, np.float64))
        for st_ in states
        for leaf in jax.tree.leaves(st_.stat)
    ]


@given(
    scales=st.lists(
        st.sampled_from([1.0, 1e30, 1e-30, -1e30, float("nan"), float("inf")]),
        min_size=3,
        max_size=6,
    ),
    seed=st.integers(0, 100),
)
@settings(**SETTINGS)
def test_merge_order_never_changes_which_lanes_are_poisoned(scales, seed):
    """⊕ is a monoid even at the edge of f32: whatever non-finiteness a
    chunk introduces (NaN/Inf data, or ±1e30 squaring to overflow inside
    the chunk kernel), the set of poisoned stat entries after folding is a
    property of the CHUNKS, not of the fold shape — left fold, right fold,
    and balanced merge trees all poison exactly the same entries.  This is
    what makes `audit()`'s verdict deterministic under re-sharding."""
    plan = _integrity_plan()
    rng = np.random.RandomState(seed)
    chunks = [
        jnp.asarray((rng.randn(16, 2) * s).astype(np.float32)) for s in scales
    ]
    parts = [plan.from_chunk(c) for c in chunks]

    def fold_left(ps):
        acc = ps[0]
        for p in ps[1:]:
            acc = plan.merge(acc, p)
        return acc

    def fold_right(ps):
        acc = ps[-1]
        for p in ps[-2::-1]:
            acc = plan.merge(p, acc)
        return acc

    def fold_tree(ps):
        while len(ps) > 1:
            nxt = [
                plan.merge(ps[i], ps[i + 1]) if i + 1 < len(ps) else ps[i]
                for i in range(0, len(ps), 2)
            ]
            ps = nxt
        return ps[0]

    masks = [_finite_mask(fold(list(parts)))
             for fold in (fold_left, fold_right, fold_tree)]
    for other in masks[1:]:
        for a, b in zip(masks[0], other):
            np.testing.assert_array_equal(a, b)


@given(
    n_chunks=st.integers(4, 64),
    offset=st.floats(100.0, 5000.0),
    seed=st.integers(0, 50),
)
@settings(**SETTINGS)
def test_compensated_tracks_f64_oracle(n_chunks, offset, seed):
    """Neumaier-compensated chunked ingest of hostile (large-offset) data
    stays within f32-roundoff-of-the-*answer* of the exact float64 serial
    lag sums, independent of how many chunk-boundary ⊕-folds the stream
    crossed — the drift a plain f32 fold accumulates per merge is exactly
    what the error companions recapture."""
    chunk = 64
    rng = np.random.RandomState(seed)
    x = (offset + rng.randn(n_chunks * chunk, 2)).astype(np.float32)
    plan = _integrity_plan(compensated=True)
    states = plan.init()
    for off in range(0, x.shape[0], chunk):
        states = plan.update_jit(states, jnp.asarray(x[off:off + chunk]))
    got = np.asarray(plan.finalize(states)["autocovariance"], np.float64)

    x64 = x.astype(np.float64)
    n = x64.shape[0]
    want = np.stack(
        [(x64[: n - h].T @ x64[h:]) / max(n - h - 1, 1) for h in range(3)]
    )
    np.testing.assert_allclose(got, want, rtol=1e-5)


@given(h=st.integers(0, 6), n=st.integers(30, 120))
@settings(**SETTINGS)
def test_autocov_transpose_symmetry(h, n):
    """γ̂(-h) = γ̂(h)ᵀ consistency: raw sums S(h) of x equal S(h)ᵀ of reversed x."""
    from repro.core.estimators.stats import raw_lag_sums

    if h >= n - 1:
        return
    x = jax.random.normal(jax.random.PRNGKey(h * 31 + n), (n, 3))
    s = raw_lag_sums(x, h)[-1]
    s_rev = raw_lag_sums(x[::-1], h)[-1]
    np.testing.assert_allclose(s, s_rev.T, rtol=1e-4, atol=1e-3)
