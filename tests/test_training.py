"""Training substrate: optimizer math, accumulation equivalence, loss descent,
gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.data.tokens import SyntheticTokenPipeline
from repro.models import init_params
from repro.training.compression import compress_int8, decompress_int8
from repro.training.optimizer import adamw_init, adamw_update, cosine_schedule, global_norm
from repro.training.train_step import make_train_step

pytestmark = pytest.mark.slow  # jit-heavy: deselected by default, use --runslow



def test_adamw_first_step_is_lr_signed():
    """With bias correction, |Δp| of step 1 ≈ lr·sign(g) (wd=0)."""
    p = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    g = {"w": jnp.asarray([0.1, -0.2, 0.3])}
    st = adamw_init(p)
    new_p, _ = adamw_update(g, st, p, lr=0.01, weight_decay=0.0, clip_norm=None)
    np.testing.assert_allclose(
        np.abs(np.asarray(p["w"] - new_p["w"])), 0.01, rtol=1e-3
    )


def test_grad_clipping():
    p = {"w": jnp.ones(4)}
    g = {"w": jnp.full(4, 100.0)}
    st = adamw_init(p)
    _, st2 = adamw_update(g, st, p, lr=0.0, clip_norm=1.0)
    assert float(global_norm(st2.m)) <= 0.11  # (1-b1)·clipped ≤ 0.1·1.0


def test_cosine_schedule():
    lr = cosine_schedule(1e-3, warmup=10, total=110)
    assert float(lr(jnp.asarray(0))) == 0.0
    assert float(lr(jnp.asarray(10))) == pytest.approx(1e-3, rel=1e-5)
    assert float(lr(jnp.asarray(110))) == pytest.approx(0.0, abs=1e-9)


def test_microbatch_accumulation_matches_full_batch():
    r = ARCHS["qwen3-0.6b"].reduced()
    params = init_params(jax.random.PRNGKey(0), r, dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, r.vocab)
    batch = {"tokens": toks, "labels": toks}
    opt = adamw_init(params)
    p1, _, m1 = jax.jit(make_train_step(r, lr_fn=1e-3, accum=1))(params, opt, batch)
    p2, _, m2 = jax.jit(make_train_step(r, lr_fn=1e-3, accum=2))(params, opt, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)
    assert max(jax.tree.leaves(diffs)) < 1e-4


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "xlstm-125m"])
def test_loss_decreases(arch):
    r = ARCHS[arch].reduced()
    pipe = SyntheticTokenPipeline(vocab=r.vocab, seq_len=32, global_batch=8, seed=1)
    params = init_params(jax.random.PRNGKey(2), r, dtype=jnp.float32)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(r, lr_fn=3e-3))
    losses = []
    for i in range(30):
        hb = pipe.host_batch(i)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2, losses


def test_pipeline_determinism():
    p1 = SyntheticTokenPipeline(vocab=100, seq_len=16, global_batch=4, seed=7)
    p2 = SyntheticTokenPipeline(vocab=100, seq_len=16, global_batch=4, seed=7)
    b1, b2 = p1.host_batch(42), p2.host_batch(42)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(p1.host_batch(43)["tokens"], b1["tokens"])


# ------------------------------------------------------- compression --


def test_int8_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(3), (1000,)) * 3.0
    codes, scale = compress_int8(x)
    back = decompress_int8(codes, scale, x.shape)
    # error per element bounded by half a quantization step of its block
    err = np.abs(np.asarray(back - x))
    step = np.repeat(np.asarray(scale).reshape(-1), 256)[: x.size]
    assert (err <= step * 0.5 + 1e-7).all()


def test_error_feedback_allreduce_unbiased_over_steps():
    """Mean compressed gradient + residual carry ≈ exact mean over time."""
    from repro.training.compression import error_feedback_allreduce

    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    # single-device psum: axis of size 1 via shard_map on a trivial mesh
    mesh = jax.make_mesh((1,), ("d",), axis_types=(jax.sharding.AxisType.Auto,))
    g = {"w": jax.random.normal(jax.random.PRNGKey(4), (512,))}
    r = {"w": jnp.zeros((512,))}

    def f(g, r):
        return error_feedback_allreduce(g, r, "d")

    from jax.sharding import PartitionSpec as P

    fm = jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )
    )
    acc_exact = jnp.zeros((512,))
    acc_comp = jnp.zeros((512,))
    for i in range(10):
        gi = {"w": jax.random.normal(jax.random.PRNGKey(10 + i), (512,))}
        red, r = fm(gi, r)
        acc_exact += gi["w"]
        acc_comp += red["w"]
    # accumulated compressed-with-feedback sum tracks the exact sum closely
    rel = float(jnp.linalg.norm(acc_comp + r["w"] - acc_exact) / jnp.linalg.norm(acc_exact))
    assert rel < 1e-2
