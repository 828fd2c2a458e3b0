"""Assigned-config exactness (brief numbers) + sharding rule unit tests."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES_BY_NAME, get_arch
from repro.parallel import sharding as shr


def test_brief_numbers_exact():
    c = ARCHS["llama4-maverick-400b-a17b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.vocab) == (48, 5120, 40, 8, 202048)
    assert (c.moe.num_experts, c.moe.top_k, c.moe.d_ff_expert) == (128, 1, 8192)

    c = ARCHS["deepseek-v2-236b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab) == (60, 5120, 128, 102400)
    assert (c.mla.kv_lora_rank, c.moe.num_experts, c.moe.top_k, c.moe.num_shared) == (512, 160, 6, 2)
    assert c.d_ff == 1536

    c = ARCHS["glm4-9b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab) == (40, 4096, 32, 2, 13696, 151552)

    c = ARCHS["qwen3-0.6b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab) == (28, 1024, 16, 8, 3072, 151936)
    assert c.qk_norm

    c = ARCHS["h2o-danube-1.8b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab) == (24, 2560, 32, 8, 6912, 32000)
    assert c.swa_window is not None

    c = ARCHS["phi3-medium-14b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab) == (40, 5120, 40, 10, 17920, 100352)

    c = ARCHS["whisper-base"]
    assert (c.n_layers, c.enc_layers, c.d_model, c.n_heads, c.d_ff, c.vocab) == (6, 6, 512, 8, 2048, 51865)

    c = ARCHS["llava-next-34b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab) == (60, 7168, 56, 8, 20480, 64000)

    c = ARCHS["xlstm-125m"]
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab) == (12, 768, 4, 50304)
    assert c.d_ff == 0

    c = ARCHS["zamba2-7b"]
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff, c.vocab) == (81, 3584, 32, 14336, 32000)
    assert c.ssm.state_dim == 64 and c.shared_attn_every == 6


def test_shape_suites_exact():
    s = SHAPES_BY_NAME
    assert (s["train_4k"].seq_len, s["train_4k"].global_batch) == (4096, 256)
    assert (s["prefill_32k"].seq_len, s["prefill_32k"].global_batch) == (32768, 32)
    assert (s["decode_32k"].seq_len, s["decode_32k"].global_batch) == (32768, 128)
    assert (s["long_500k"].seq_len, s["long_500k"].global_batch) == (524288, 1)


def test_aliases():
    assert get_arch("llama4").name == "llama4-maverick-400b-a17b"
    with pytest.raises(KeyError):
        get_arch("nope")


@pytest.fixture
def mesh22():
    # AbstractMesh: sharding-rule tests need only axis names/sizes, not devices
    return jax.sharding.AbstractMesh(
        (2, 2), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def test_logical_to_spec_basic(mesh22):
    spec = shr.logical_to_spec(("batch", "heads"), (8, 8), mesh22)
    assert spec == P("data", "model")
    # divisibility fallback: 7 not divisible by 2 → replicated dim
    spec = shr.logical_to_spec(("batch", "heads"), (7, 8), mesh22)
    assert spec == P(None, "model")


def test_sp_mode_switch(mesh22):
    shr.set_sp_mode(True)
    try:
        spec = shr.logical_to_spec(("batch", "seq"), (1, 64), mesh22)
        assert spec == P(None, "data")
    finally:
        shr.set_sp_mode(False)
    spec = shr.logical_to_spec(("batch", "seq"), (4, 64), mesh22)
    assert spec == P("data", None)


def test_param_pspecs_rules(mesh22):
    params = {
        "layers": {
            "attn": {"wq": jnp.zeros((4, 8, 8)), "wo": jnp.zeros((4, 8, 8))},
            "mlp": {"w_gate": jnp.zeros((4, 8, 16)), "w_down": jnp.zeros((4, 16, 8))},
        },
        "embed": jnp.zeros((100, 8)),
    }
    specs = shr.param_pspecs(params, mesh22)
    assert specs["layers"]["attn"]["wq"] == P(None, None, "model")
    assert specs["layers"]["attn"]["wo"] == P(None, "model", None)
    assert specs["layers"]["mlp"]["w_gate"] == P(None, None, "model")
    assert specs["layers"]["mlp"]["w_down"] == P(None, "model", None)
    assert specs["embed"] == P("model", None)


def test_zero1_adds_data_axis(mesh22):
    params = {"w_gate": jnp.zeros((8, 16))}
    z = shr.zero1_pspecs(params, mesh22)
    assert z["w_gate"] == P("data", "model")  # ff→model, zero1 puts data on dim0


def test_no_mesh_shard_is_noop():
    x = jnp.zeros((4, 4))
    assert shr.shard(x, ("batch", None)) is x
