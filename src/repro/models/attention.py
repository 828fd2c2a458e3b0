"""Attention: GQA / MLA / sliding-window, train + prefill + decode paths.

Memory discipline: logits are never materialized at (S × S) — queries are
processed in chunks (lax.map), bounding the live buffer at (chunk × S_k).
For sliding-window attention the key slice per chunk is (window + chunk) —
the paper's weak-memory window (halo) at the XLA level; the Pallas kernel
`repro.kernels.swa_attention` is the explicitly-tiled forward twin.

Decode uses a static-capacity cache written in place at position ``pos``
(dynamic_update_slice), masked by entry validity.  SWA decode uses a ring
cache of capacity min(window, seq) with explicit position tracking.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import DTYPE, apply_rope, dense_init, rms_norm
from ..parallel.sharding import shard

Params = Dict[str, Any]


def _model_axis_size() -> int:
    # the active-mesh lookup shared with the sharding-rule resolver
    from ..parallel.sharding import _active_mesh

    m = _active_mesh()
    if m is None:
        return 1
    return dict(m.shape).get("model", 1)


# ---------------------------------------------------------------- init --


def gqa_init(key, cfg, dtype=DTYPE) -> Params:
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(k4, cfg.n_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def mla_init(key, cfg, dtype=DTYPE) -> Params:
    m = cfg.mla
    ks = jax.random.split(key, 8)
    qdim = cfg.n_heads * (m.nope_head_dim + m.rope_head_dim)
    p = {
        "w_dkv": dense_init(ks[0], cfg.d_model, m.kv_lora_rank, dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "w_uk": dense_init(ks[1], m.kv_lora_rank, cfg.n_heads * m.nope_head_dim, dtype),
        "w_uv": dense_init(ks[2], m.kv_lora_rank, cfg.n_heads * m.v_head_dim, dtype),
        "w_kr": dense_init(ks[3], cfg.d_model, m.rope_head_dim, dtype),
        "wo": dense_init(ks[4], cfg.n_heads * m.v_head_dim, cfg.d_model, dtype),
    }
    if m.q_lora_rank:
        p["w_dq"] = dense_init(ks[5], cfg.d_model, m.q_lora_rank, dtype)
        p["q_norm"] = jnp.ones((m.q_lora_rank,), dtype)
        p["w_uq"] = dense_init(ks[6], m.q_lora_rank, qdim, dtype)
    else:
        p["wq"] = dense_init(ks[7], cfg.d_model, qdim, dtype)
    return p


def attention_init(key, cfg, dtype=DTYPE) -> Params:
    return mla_init(key, cfg, dtype) if cfg.attn == "mla" else gqa_init(key, cfg, dtype)


# ------------------------------------------------------- chunked core --


def _chunked_attention(
    q: jax.Array,  # (B, S, KVH, G, hk)
    k: jax.Array,  # (B, Sk, KVH, hk)
    v: jax.Array,  # (B, Sk, KVH, hv)
    scale: float,
    *,
    q_pos0: int = 0,
    window: Optional[int] = None,
    chunk: int = 512,
    causal: bool = True,
) -> jax.Array:
    """Causal (optionally banded) or bidirectional attention, query-chunked.

    Bounds live logits at (B, chunk, KVH, G, key_width).  ``window=None`` →
    full causal, key_width = Sk; else key slice of width window+chunk (the
    weak-memory halo).  Returns (B, S, KVH, G, hv).
    """
    b, s, kvh, g, hk = q.shape
    sk = k.shape[1]
    chunk = min(chunk, s)
    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))

    use_window = window is not None and sk > window + chunk

    def chunk_fn(i):
        qs = i * chunk
        qc = jax.lax.dynamic_slice_in_dim(q, qs, chunk, axis=1)
        q_pos = q_pos0 + qs + jnp.arange(chunk)
        if use_window:
            width = window + chunk
            start = jnp.clip(qs + q_pos0 - window, 0, sk - width)
            kc = jax.lax.dynamic_slice_in_dim(k, start, width, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, width, axis=1)
            k_pos = start + jnp.arange(width)
        else:
            kc, vc = k, v
            k_pos = jnp.arange(sk)
        logits = jnp.einsum("bqngk,bsnk->bngqs", qc, kc).astype(jnp.float32) * scale
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]
        else:
            mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
        if window is not None:
            mask &= k_pos[None, :] > (q_pos[:, None] - window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bngqs,bsnv->bqngv", p.astype(v.dtype), vc)
        return out

    outs = jax.lax.map(chunk_fn, jnp.arange(n_chunks))  # (nc, B, chunk, ...)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, n_chunks * chunk, kvh, g, v.shape[-1])
    return out[:, :s]


def _decode_attention(
    q: jax.Array,  # (B, 1, KVH, G, hk)
    k: jax.Array,  # (B, C, KVH, hk)
    v: jax.Array,  # (B, C, KVH, hv)
    scale: float,
    valid: jax.Array,  # (C,) or (B, C) bool
) -> jax.Array:
    logits = jnp.einsum("bqngk,bsnk->bngqs", q, k).astype(jnp.float32) * scale
    if valid.ndim == 1:
        valid = valid[None]
    logits = jnp.where(valid[:, None, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bngqs,bsnv->bqngv", p.astype(v.dtype), v)


# ------------------------------------------------------------- GQA ----


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def gqa_apply(
    p: Params,
    x: jax.Array,  # (B, S, d)
    cfg,
    positions: jax.Array,  # (S,) int32
    *,
    cache: Optional[Params] = None,
    pos: Optional[jax.Array] = None,  # decode write position (scalar)
    return_cache: bool = False,
) -> Tuple[jax.Array, Optional[Params]]:
    hd = cfg.resolved_head_dim
    kvh = cfg.n_kv_heads
    g = cfg.n_heads // kvh
    scale = 1.0 / math.sqrt(hd)
    b, s, _ = x.shape

    q = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wq"]), cfg.n_heads, hd)
    k = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wk"]), kvh, hd)
    v = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wv"]), kvh, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, ("batch", None, "heads", None))
    k = shard(k, ("batch", None, "heads", None))

    if cache is None:
        # train / prefill over the full (possibly seq-sharded) sequence.
        # §Perf B2: when KVH doesn't divide the model axis but H does, the
        # (KVH, G) head split is unshardable and GSPMD replicates attention
        # (+ all-to-all reshards around it).  Repeating K/V to H heads keeps
        # the whole attention head-sharded: per-device K/V bytes are
        # UNCHANGED (H/ms sharded vs KVH replicated) and the resharding
        # collectives disappear.
        ms = _model_axis_size()
        if ms > 1 and cfg.n_heads % ms == 0 and kvh % ms != 0:
            k_a = jnp.repeat(k, g, axis=2)
            v_a = jnp.repeat(v, g, axis=2)
            k_a = shard(k_a, ("batch", None, "heads", None))
            v_a = shard(v_a, ("batch", None, "heads", None))
            qg = q.reshape(b, s, cfg.n_heads, 1, hd)
        else:
            k_a, v_a = k, v
            qg = q.reshape(b, s, kvh, g, hd)
        out = _chunked_attention(qg, k_a, v_a, scale, window=cfg.swa_window)
        new_cache = None
        if return_cache:
            new_cache = _gqa_fresh_cache(cfg, k, v, positions)
    else:
        # decode: write this token's k/v into the cache, attend over it
        qg = q.reshape(b, s, kvh, g, hd)
        assert s == 1
        if cfg.swa_window is not None and cache["k"].shape[1] <= cfg.swa_window:
            slot = jnp.mod(pos, cache["k"].shape[1])
        else:
            slot = pos
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.full((1,), pos, cache["pos"].dtype), slot, axis=0
        )
        valid = (cpos <= pos) & (cpos >= 0)
        if cfg.swa_window is not None:
            valid &= cpos > pos - cfg.swa_window
        out = _decode_attention(qg, ck, cv, scale, valid)
        new_cache = {"k": ck, "v": cv, "pos": cpos}

    out = out.reshape(b, s, cfg.n_heads * hd)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return out, new_cache


def _gqa_fresh_cache(cfg, k, v, positions):
    """Cache built by prefill.

    SWA archs keep only the trailing window, stored RING-ALIGNED so decode's
    ``slot = pos % window`` convention continues it seamlessly; shorter-than-
    window prefills are padded to full window capacity with invalid slots.
    """
    pos = jnp.broadcast_to(positions, (k.shape[1],)).astype(jnp.int32)
    if cfg.swa_window is not None:
        w = cfg.swa_window
        s = k.shape[1]
        if s > w:
            k, v, pos = k[:, -w:], v[:, -w:], pos[-w:]
            p0 = s - w  # global position of the first kept entry
            shift = p0 % w
            k = jnp.roll(k, shift, axis=1)
            v = jnp.roll(v, shift, axis=1)
            pos = jnp.roll(pos, shift, axis=0)
        elif s < w:
            pad = w - s
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            pos = jnp.pad(pos, ((0, pad),), constant_values=-1)
    return {"k": k, "v": v, "pos": pos}


def gqa_cache_spec(cfg, batch: int, seq_len: int, dtype=DTYPE) -> Dict[str, Any]:
    """ShapeDtypeStructs for the decode cache (dry-run inputs)."""
    hd = cfg.resolved_head_dim
    c = min(cfg.swa_window, seq_len) if cfg.swa_window is not None else seq_len
    return {
        "k": jax.ShapeDtypeStruct((batch, c, cfg.n_kv_heads, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, c, cfg.n_kv_heads, hd), dtype),
        "pos": jax.ShapeDtypeStruct((c,), jnp.int32),
    }


# ------------------------------------------------------------- MLA ----


def mla_apply(
    p: Params,
    x: jax.Array,
    cfg,
    positions: jax.Array,
    *,
    cache: Optional[Params] = None,
    pos: Optional[jax.Array] = None,
    return_cache: bool = False,
) -> Tuple[jax.Array, Optional[Params]]:
    """Multi-head latent attention (DeepSeek-V2).

    Two computation forms with identical math (§Perf iteration A1):
      * full-sequence (train/prefill): NON-absorbed — materialize per-head
        k_nope = c_kv·W_uk and v = c_kv·W_uv, score dim 192/head.  The
        matrix-absorbed form costs (kvr+rope)+kvr = 1088 flops per
        (q,k,head) pair vs 192+128 = 320 — 3.4× more on the S² term, which
        dominates training.  Heads shard over "model".
      * decode: ABSORBED — q folded through W_uk so the cache stays the
        compact (c_kv, k_rope) latent and per-step compute is O(H·kvr).
    """
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)

    if m.q_lora_rank:
        cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dq"]), p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rh->bsh", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    q = q.reshape(b, s, h, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim :]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    c_kv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"]), p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(jnp.einsum("bsd,dr->bsr", x, p["w_kr"]), positions, cfg.rope_theta)
    kv_lat = jnp.concatenate([c_kv, k_rope], axis=-1)  # (B,S,kvr+rope)

    w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.nope_head_dim)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)

    if cache is None:
        # non-absorbed: per-head keys/values, heads sharded over "model"
        k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, w_uk)
        k_nope = shard(k_nope, ("batch", None, "heads", None))
        v = jnp.einsum("bsr,rhv->bshv", c_kv, w_uv)
        v = shard(v, ("batch", None, "heads", None))
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, m.rope_head_dim))],
            axis=-1,
        )
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)  # (B,S,H,192)
        q_full = shard(q_full, ("batch", None, "heads", None))
        qg = q_full.reshape(b, s, h, 1, -1)  # kvh = H, group = 1
        ctx = _chunked_attention(qg, k_full, v, scale, window=cfg.swa_window)
        out = ctx.reshape(b, s, h * m.v_head_dim)
        new_cache = None
        if return_cache:
            new_cache = {
                "lat": kv_lat,
                "pos": jnp.broadcast_to(positions, (s,)).astype(jnp.int32),
            }
    else:
        # absorbed decode against the latent cache
        assert s == 1
        q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)
        q_dec = jnp.concatenate([q_abs, q_rope], axis=-1)  # (B,1,H,kvr+rope)
        lat = jax.lax.dynamic_update_slice_in_dim(cache["lat"], kv_lat, pos, axis=1)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.full((1,), pos, cache["pos"].dtype), pos, axis=0
        )
        valid = (cpos <= pos) & (cpos >= 0)
        qg = q_dec.reshape(b, 1, 1, h, -1)
        ctx = _decode_attention(
            qg, lat[:, :, None, :], lat[:, :, None, : m.kv_lora_rank], scale, valid
        )
        ctx = ctx.reshape(b, 1, h, m.kv_lora_rank)
        out = jnp.einsum("bshr,rhv->bshv", ctx, w_uv).reshape(b, 1, h * m.v_head_dim)
        new_cache = {"lat": lat, "pos": cpos}

    out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return out, new_cache


def mla_cache_spec(cfg, batch: int, seq_len: int, dtype=DTYPE) -> Dict[str, Any]:
    m = cfg.mla
    return {
        "lat": jax.ShapeDtypeStruct(
            (batch, seq_len, m.kv_lora_rank + m.rope_head_dim), dtype
        ),
        "pos": jax.ShapeDtypeStruct((seq_len,), jnp.int32),
    }


# ------------------------------------------------------------ router --


def attention_apply(p, x, cfg, positions, **kw):
    if cfg.attn == "mla":
        return mla_apply(p, x, cfg, positions, **kw)
    return gqa_apply(p, x, cfg, positions, **kw)


def attention_cache_spec(cfg, batch: int, seq_len: int, dtype=DTYPE):
    if cfg.attn == "mla":
        return mla_cache_spec(cfg, batch, seq_len, dtype)
    return gqa_cache_spec(cfg, batch, seq_len, dtype)
