"""Time-series graphs (paper §9, §11): arterial-traffic DBN on a corridor.

Simulates the order-(1,1) traffic Bayesian network, partitions the graph
with 1-hop halos, and estimates per-link AR dynamics by graph map-reduce —
each partition touching only its own vertices plus replicated halo
neighbours (paper Fig. 5-8).

  PYTHONPATH=src python examples/traffic_graph.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

import jax
import jax.numpy as jnp

from repro.core.estimators.stats import autocovariance
from repro.core.estimators.yule_walker import levinson_durbin
from repro.core.graphs import (
    graph_window_map_reduce,
    line_graph,
    make_graph_partition,
    simulate_traffic_dbn,
)


def main():
    v, steps = 512, 2000
    g = line_graph(v)
    x0 = jnp.full((v,), 0.4)
    traj = simulate_traffic_dbn(g, x0, steps, jax.random.PRNGKey(0), inflow_scale=0.08)
    print(f"traffic DBN: {v} links, {steps} steps, "
          f"occupancy ∈ [{float(traj.min()):.3f}, {float(traj.max()):.3f}]")

    # per-link temporal dynamics: univariate AR(1) via Durbin-Levinson
    x_mid = traj[:, v // 2] - traj[:, v // 2].mean()
    gam = autocovariance(x_mid[:, None], 3, normalization="standard")[:, 0, 0]
    phi, var, pacf = levinson_durbin(gam, 2)
    print(f"link {v//2}: AR(2) fit φ = {[f'{float(p):.3f}' for p in phi]}, "
          f"PACF = {[f'{float(p):.3f}' for p in pacf]}")

    # graph map-reduce with 1-hop halos: Σ_v Σ_t x_v(t)·mean_nb x(t) — the
    # spatial weak-memory cross statistic, partition-parallel (Fig. 5)
    part = make_graph_partition(g, num_parts=8, k=1)

    def kern(xc, nb, mask):
        # xc: (T,) own series; nb: (max_deg, T) neighbour series
        nbm = jnp.sum(jnp.where(mask[:, None], nb, 0.0), axis=0) / jnp.maximum(
            jnp.sum(mask), 1
        )
        return jnp.sum(xc * nbm)

    stat = graph_window_map_reduce(kern, jnp.moveaxis(traj, 0, 1), g, part)
    # serial check
    serial = 0.0
    for vtx in range(v):
        nb_ids = [n for n in g.nbrs[vtx] if n >= 0]
        nbm = jnp.mean(traj[:, jnp.asarray(nb_ids)], axis=1)
        serial += float(jnp.sum(traj[:, vtx] * nbm))
    print(f"graph-parallel neighbour statistic: {float(stat):.3f} "
          f"(serial {serial:.3f}; {part.padded.shape[1] * 8 - v} replicated halo vertices)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
