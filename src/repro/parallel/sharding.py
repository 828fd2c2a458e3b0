"""Logical-axis sharding rules → PartitionSpecs (GSPMD/pjit integration).

Activations and parameters are annotated with *logical* axis names; this
module resolves them against whatever mesh is active
(``jax.sharding.set_mesh``), with automatic divisibility fallback: a logical
axis whose dimension does not divide over its mesh axes is replicated
instead of erroring — so the same model code lowers on the 16×16 single-pod
mesh, the 2×16×16 multi-pod mesh, an 8-device test mesh, and a single CPU
device.

Rules (DESIGN.md §6):
  batch   → ("pod", "data")   data parallelism (pod = outer pure-DP axis)
  heads   → "model"           tensor parallelism over (kv-grouped) heads
  ff      → "model"           tensor parallelism over MLP hidden
  experts → "model"           expert parallelism
  vocab   → "model"           embedding / logits sharding
  seq     → "data" in SP mode sequence/context parallelism (long_500k)

SP mode is a module-level switch flipped by the launchers for cells where
the batch axis is too small to fill "data" (global_batch=1 long-context):
batch then stays replicated and the sequence axis takes over "data".
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

LogicalAxis = Union[str, None, Tuple[str, ...]]

_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "heads": ("model",),
    "kv": ("model",),
    "ff": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "embed": (),
    "seq": (),  # overridden in SP mode
    "seq_sp": ("data",),
    "seq_tp": ("model",),  # Megatron-SP residual sharding (§Perf B5)
}

_SP_MODE = False


def set_sp_mode(enabled: bool) -> None:
    """Sequence-parallel mode: 'seq' → data axis, 'batch' → replicated."""
    global _SP_MODE
    _SP_MODE = enabled


def sp_mode_enabled() -> bool:
    return _SP_MODE


def _active_mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m


def mesh_axis_size(mesh, names: Sequence[str]) -> int:
    return math.prod(dict(mesh.shape).get(n, 1) for n in names)


def _resolve(logical: LogicalAxis, mesh) -> Tuple[str, ...]:
    if logical is None:
        return ()
    if isinstance(logical, tuple):
        names: Tuple[str, ...] = logical
    else:
        if logical == "batch" and _SP_MODE:
            return ()
        if logical == "seq" and _SP_MODE:
            names = _RULES["seq_sp"]
        else:
            names = _RULES.get(logical, (logical,))
    return tuple(n for n in names if n in mesh.axis_names)


def logical_to_spec(axes: Sequence[LogicalAxis], shape: Sequence[int], mesh) -> P:
    """Resolve logical names per-dimension with divisibility fallback."""
    entries = []
    used: set = set()
    for dim, logical in zip(shape, axes):
        names = tuple(n for n in _resolve(logical, mesh) if n not in used)
        if names and dim % mesh_axis_size(mesh, names) == 0:
            used.update(names)
            entries.append(names if len(names) > 1 else names[0])
        else:
            entries.append(None)
    return P(*entries)


def psum_tree(tree: Any, axis: str) -> Any:
    """Single-collective reduction of a pytree of per-device partials.

    This is the cluster-level merge of the weak-memory monoid
    (`repro.core.streaming`): per-shard partial statistics built from
    halo-complete blocks contain every window the shard owns, so the global
    ⊕ degenerates to one ``psum`` of the (tiny) sufficient statistics —
    never the data.  Used by every sharded estimator path
    (`core.mapreduce.sharded_window_map_reduce`,
    `core.estimators.stats.autocovariance_sharded`,
    `timeseries.TimeSeriesStore.map_reduce`).  The per-shard local
    contraction feeding this collective routes through the compute-backend
    registry (`repro.core.backend`) — shards hit the Pallas tile kernels or
    pure jnp per the caller's ``backend=``, while the collective itself is
    backend-agnostic.
    """
    return jax.tree.map(lambda l: jax.lax.psum(l, axis), tree)


def shard(x: jax.Array, axes: Sequence[LogicalAxis]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    spec = logical_to_spec(axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


# ------------------------------------------------ parameter pspecs ----

# Leaf-name → logical axes (per dimension).  Matched by the *last* path
# component; falls back to replicated.  Divisibility fallback applies per
# dim, so e.g. a 4-head test model simply replicates its head axis.
_PARAM_RULES: Dict[str, Tuple[LogicalAxis, ...]] = {
    # attention
    "wq": (None, "heads"),
    "wk": (None, "kv"),
    "wv": (None, "kv"),
    "wo": ("heads", None),
    # MLA
    "w_dq": (None, None),
    "w_uq": (None, "heads"),
    "w_dkv": (None, None),
    "w_uk": (None, "heads"),
    "w_uv": (None, "heads"),
    "w_kr": (None, None),
    # MLP
    "w_gate": (None, "ff"),
    "w_up": (None, "ff"),
    "w_down": ("ff", None),
    # MoE (leading expert axis)
    "router": (None, None),
    "e_gate": ("experts", None, None),
    "e_up": ("experts", None, None),
    "e_down": ("experts", None, None),
    # embeddings / head
    "embed": ("vocab", "embed"),
    "lm_head": (None, "vocab"),
    "patch_proj": (None, None),
    # mamba2
    "in_proj": (None, "ff"),
    "conv_w": (None, "ff"),
    "conv_b": ("ff",),
    "out_proj": ("ff", None),
    "A_log": ("ff",),
    "D": ("ff",),
    "dt_bias": ("ff",),
    # xlstm
    "w_qkv": (None, "ff"),
    "w_if": (None, "heads"),
    "w_o_gate": (None, "ff"),
    "up_proj": (None, "ff"),
    "down_proj": ("ff", None),
    "w_gates": (None, "heads"),
    "r_gates": (None, "heads"),
}


def _leaf_rule(path: Tuple[Any, ...], leaf) -> Tuple[LogicalAxis, ...]:
    name = None
    for entry in reversed(path):
        key = getattr(entry, "key", None)
        if isinstance(key, str):
            name = key
            break
    rule = _PARAM_RULES.get(name or "", None)
    if rule is None:
        return (None,) * leaf.ndim
    if len(rule) == leaf.ndim:
        return rule
    if len(rule) + 1 == leaf.ndim:
        # stacked-over-layers variant (leading L axis from scan init)
        return (None,) + rule
    return (None,) * leaf.ndim


def param_pspecs(params: Any, mesh) -> Any:
    """PartitionSpec pytree for a parameter pytree (works on ShapeDtypeStructs)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: logical_to_spec(_leaf_rule(path, leaf), leaf.shape, mesh),
        params,
    )


def zero1_pspecs(params: Any, mesh) -> Any:
    """ZeRO-1 optimizer-state specs: the param spec PLUS the data(+pod) axes
    on the first still-unsharded divisible dimension.

    Optimizer moments are only touched at the (per-step) update, so paying a
    reduce-scatter/all-gather there buys an N_data× memory reduction — the
    standard ZeRO-1 trade.  Falls back to the plain param spec when no
    dimension divides.
    """
    dp_axes = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    dp = mesh_axis_size(mesh, dp_axes)

    def one(path, leaf):
        spec = logical_to_spec(_leaf_rule(path, leaf), leaf.shape, mesh)
        if dp <= 1:
            return spec
        entries = list(spec) + [None] * (leaf.ndim - len(spec))
        for i, (dim, e) in enumerate(zip(leaf.shape, entries)):
            if e is None and dim % dp == 0:
                entries[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                return P(*entries)
        return spec

    return jax.tree_util.tree_map_with_path(one, params)
