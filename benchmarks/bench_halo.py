"""Beyond-paper: halo materialization — replication vs collective-permute.

Runs in a subprocess with 8 virtual CPU devices and parses the optimized HLO for
collective bytes: the paper's pre-replication pays (P−1)·H·d extra storage
and ZERO wire bytes per sweep; exchange mode pays ~2·H·d wire bytes per
sweep and zero storage.  (The crossover rule-of-thumb lands in
EXPERIMENTS.md §Perf.)
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from .common import row

_CODE = """
import jax, jax.numpy as jnp
from repro.timeseries.dataset import TimeSeriesStore
from repro.launch.roofline import parse_collectives
mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (8*4096, 8))
kern = lambda w: jnp.outer(w[0], w[-1])
for mode in ("replicate", "exchange"):
    st = TimeSeriesStore.from_series(x, 4096, 4, 4, mesh=mesh, halo_mode=mode)
    # lower the sweep and count wire bytes
    def sweep(blocks):
        st2 = TimeSeriesStore(blocks=blocks, spec=st.spec, mesh=mesh, axis="data", halo_mode=mode)
        return st2.map_reduce(kern)
    compiled = jax.jit(sweep).lower(st.blocks).compile()
    coll = parse_collectives(compiled.as_text())
    extra = st.blocks.size - x.size if mode == "replicate" else 0
    print(f"RESULT {mode} wire={coll.wire_bytes:.0f} counts={sum(coll.counts.values())} extra_elems={extra}")
"""


def run():
    env = dict(os.environ)
    # 8 virtual CPU devices; the child stays off any accelerator, which the
    # parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_CODE)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"halo-mode child failed (exit {r.returncode}):\n{r.stderr[-2000:]}"
        )
    for line in r.stdout.splitlines():
        if line.startswith("RESULT"):
            _, mode, wire, counts, extra = line.split()
            row(f"halo_{mode}", 0.0, f"{wire};{counts};{extra};P=8;H=4")


if __name__ == "__main__":
    run()
