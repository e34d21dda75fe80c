"""Logical-axis sharding rules (MaxText-style).

Model code annotates parameters and activations with *logical* axis names
(``batch``, ``heads``, ``mlp``, ``expert``, ...). A ``ShardingRules`` table
maps logical names to mesh axes for the active mesh; changing the mesh
(tests: 1 CPU device; production: 16x16 or 2x16x16) changes one table, not
the model code.

``shard(x, *names)`` applies ``with_sharding_constraint`` when a rules
context is active and is a no-op otherwise, so all model code runs unchanged
outside pjit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingRules",
    "default_rules",
    "use_rules",
    "leading_sharding",
    "logical_spec",
    "replicated_sharding",
    "shard",
    "named_sharding",
]

Axis = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or tuple of mesh axes, or None)."""

    table: Dict[str, Axis]

    def resolve(self, names: Sequence[Optional[str]]) -> P:
        axes = []
        used: set = set()
        for n in names:
            ax = self.table.get(n) if n is not None else None
            # A mesh axis may appear at most once in a PartitionSpec.
            flat = (ax,) if isinstance(ax, str) else (ax or ())
            if any(a in used for a in flat):
                ax = None
            else:
                used.update(flat)
            axes.append(ax)
        return P(*axes)


def default_rules(
    mesh: Mesh,
    *,
    n_heads: int = 0,
    n_kv_heads: int = 0,
    n_experts: int = 0,
    decode: bool = False,
    prefill: bool = False,
    seq_parallel: bool = True,
) -> ShardingRules:
    """The production rules table (DESIGN.md Sec. 5), resolved against the
    mesh's actual axes and the architecture's divisibility.

    * ``batch`` -> all data-parallel axes (pod + data when present);
    * ``heads``/``mlp``/``vocab`` -> ``model`` (tensor parallelism);
    * ``kv_heads`` -> ``model`` only when the head count divides evenly,
      else replicated (standard GQA practice when n_kv < TP degree);
    * ``expert`` -> ``model`` (expert parallelism);
    * ``kv_seq`` -> ``model`` for decode (flash-decoding style sequence
      sharding of the KV cache), unsharded otherwise;
    * ``seq_resid`` -> ``model`` (Megatron-style sequence parallelism of
      the residual stream): the layer-scan carry — the tensor the remat
      policy must keep alive per layer — is 1/TP the size; GSPMD inserts
      the all-gather before QKV/FF projections and the reduce-scatter
      after, exactly the Megatron-SP schedule. Disabled for decode
      (seq = 1).
    """
    axis_names = mesh.axis_names
    data_axes = tuple(a for a in ("pod", "data") if a in axis_names)
    model = "model" if "model" in axis_names else None
    model_size = mesh.shape["model"] if model else 1
    kv = model if (model and n_kv_heads and n_kv_heads % model_size == 0) else None
    expert = model if (model and n_experts and n_experts % model_size == 0) else None
    # GQA score blocks: for train, GSPMD factorizes the model axis across
    # the (KV, R) dims of the reshaped q (e.g. 16 = 8x2 for command-r) —
    # measured better than forcing a query-position sharding. For PREFILL
    # the propagation fails in heterogeneous periods (jamba's 1-attn-in-8:
    # replicated 8 GiB f32 [B,KV,R,bq,32k] score blocks), so the blocked-
    # attention body pins the query-position dim ("seq_q") there.
    heads_div = bool(model) and (n_heads == 0 or n_heads % model_size == 0)
    table: Dict[str, Axis] = {
        "batch": data_axes if data_axes else None,
        "seq": None,
        "seq_q": model if prefill else None,
        "seq_resid": model if (seq_parallel and not decode) else None,
        "embed": None,
        "heads": model,
        "kv_heads": kv,
        "head_dim": None,
        "mlp": model,
        "vocab": model,
        "expert": expert,
        "expert_mlp": None if expert else model,
        "kv_seq": model if decode else None,
        "kv_batch": data_axes if data_axes else None,
        "state": None,
        "inner": model,  # SSM inner channels
    }
    return ShardingRules(table)


_ctx = threading.local()


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: ShardingRules):
    prev = getattr(_ctx, "state", None)
    _ctx.state = (mesh, rules)
    try:
        yield
    finally:
        _ctx.state = prev


def _active() -> Optional[Tuple[Mesh, ShardingRules]]:
    return getattr(_ctx, "state", None)


def current_mesh() -> Optional[Mesh]:
    state = _active()
    return state[0] if state else None


def mesh_axis(logical: str) -> Axis:
    """The mesh axis a logical name resolves to under the active rules."""
    state = _active()
    if state is None:
        return None
    return state[1].table.get(logical)


def logical_spec(names: Sequence[Optional[str]]) -> P:
    """Resolve logical names to a PartitionSpec under the active rules
    (fully replicated when no context is active)."""
    state = _active()
    if state is None:
        return P()
    return state[1].resolve(names)


def named_sharding(names: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    state = _active()
    if state is None:
        return None
    mesh, rules = state
    return NamedSharding(mesh, rules.resolve(names))


def shard(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Apply a logical sharding constraint (no-op without a context)."""
    state = _active()
    if state is None:
        return x
    mesh, rules = state
    spec = rules.resolve(names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def leading_sharding(mesh: Mesh, axis: str, ndim: int = 1) -> NamedSharding:
    """Shard dimension 0 over one mesh axis, replicate the rest — the
    layout of every per-shard stacked array in the sharded SpGEMM executor
    (``[n_shards, ...]`` with the shard dim on ``axis``)."""
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicate across the mesh (the B-operand layout in the
    sharded SpGEMM executor)."""
    return NamedSharding(mesh, P())


def divisible_spec(shape: Sequence[int], spec: P, mesh: Mesh) -> P:
    """Drop mesh axes whose size does not divide the corresponding dim
    (jit in/out_shardings demand exact divisibility, unlike
    with_sharding_constraint)."""
    axes = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, ax in zip(shape, axes):
        flat = (ax,) if isinstance(ax, str) else (ax or ())
        size = 1
        for a in flat:
            size *= mesh.shape[a]
        out.append(ax if (size and dim % size == 0) else None)
    return P(*out)


def divisible_sharding(
    shape: Sequence[int], names: Sequence[Optional[str]],
    rules: ShardingRules, mesh: Mesh,
) -> NamedSharding:
    """Resolve logical axes to a divisibility-safe NamedSharding."""
    return NamedSharding(mesh, divisible_spec(shape, rules.resolve(names), mesh))
