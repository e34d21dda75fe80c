"""Public jit'd wrappers around the Pallas kernels + jnp fallbacks.

Backend policy (DESIGN.md Sec. 2): Pallas kernels target TPU; this container
is CPU-only, so ``backend="auto"`` selects

* ``"pallas"`` (interpret=False) on a real TPU backend,
* ``"jnp"`` (the ref.py oracle path, pure XLA) elsewhere — used by the
  multi-pod dry-run so collected HLO FLOPs/bytes reflect honest dense math.

Tests force ``backend="pallas_interpret"`` to execute the kernel bodies in
interpret mode on CPU and allclose them against the oracles.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.schedule import SpGEMMSchedule
from repro.kernels import ref
from repro.kernels.bsr_spmm import bsr_spmm, plan_bsr
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.sparse.formats import BCSR, BCSV, CSR
from repro.spgemm.cache import PlanCache
from repro.spgemm.executor import kernel_interpret
from repro.spgemm.plan import SpGEMMPlan, resolve_backend, spgemm_plan

__all__ = [
    "resolve_backend",
    "spgemm",
    "sparse_dense_matmul",
    "grouped_matmul",
    "attention",
]


# ---------------------------------------------------------------------------
# Sparse x sparse: compatibility shim over the plan/execute API
# ---------------------------------------------------------------------------

def spgemm(
    a: BCSV,
    b: BCSR,
    *,
    backend: str = "auto",
    schedule: Optional[SpGEMMSchedule] = None,
    cache: Optional[PlanCache] = None,
) -> CSR:
    """C = A @ B for block-sparse A (BCSV) and B (BCSR).

    Thin compatibility shim over :mod:`repro.spgemm`: builds — or fetches
    from the plan cache (process-level by default; pass ``cache`` to
    isolate) — an :class:`SpGEMMPlan` for this sparsity pattern and runs
    its numeric phase with the given values. Callers that reuse one
    pattern should hold a plan directly (``repro.spgemm.spgemm_plan``)
    instead of round-tripping through here.

    The returned CSR has C's *structural* pattern (every element of every
    structurally nonzero C block): elements that compute to exact zero are
    stored explicitly, so the pattern is value-independent — the contract
    that keeps output assembly inside the plan's jitted executor.
    """
    if schedule is not None:
        # Caller already ran the symbolic phase; honor it without caching.
        plan = SpGEMMPlan.from_blocks(a, b, backend=backend, schedule=schedule)
        return plan.execute()
    plan = spgemm_plan(a, b, backend=backend, cache=cache)
    try:
        # Passing values explicitly makes the rebind + launch atomic even
        # when the cached plan is shared across threads.
        return plan.execute(a.blocks, b.blocks)
    finally:
        # One-shot semantics: free the device copies (the scarce resource)
        # but keep host values staged — the plan is shared with any direct
        # spgemm_plan holder of this pattern, whose no-arg execute() must
        # keep working. Host-side this pins only references to the
        # caller's own block arrays, bounded by the cache capacity.
        plan.release_device_values()


# ---------------------------------------------------------------------------
# Sparse weights x dense activations (SparseLinear forward)
# ---------------------------------------------------------------------------

def sparse_dense_matmul(
    x: jax.Array,  # [M, K]
    w: BCSV,  # [K, N] block-sparse weight
    *,
    backend: str = "auto",
    tm: int = 128,
) -> jax.Array:
    """y = x @ W with W block-sparse (zero column panels handled)."""
    backend = resolve_backend(backend)
    bk, bn = w.block_shape
    k, n = w.shape
    assert x.shape[1] == k
    # W is stored row-group-major (BCSV over K); the SpMM kernel wants
    # column-panel-major with every N panel covered.
    order, brow, bcol, flags = plan_bsr(w.brow, w.bcol)
    blocks = w.blocks[order]
    # Pad a zero block for every absent column panel.
    present = np.zeros(n // bn, bool)
    present[bcol] = True
    missing = np.nonzero(~present)[0].astype(np.int32)
    if missing.size:
        blocks = np.concatenate(
            [blocks, np.zeros((missing.size, bk, bn), blocks.dtype)]
        )
        brow = np.concatenate([brow, np.zeros(missing.size, np.int32)])
        bcol = np.concatenate([bcol, missing])
        flags = np.concatenate([flags, np.full(missing.size, 3, np.int32)])
        order2 = np.lexsort((brow, bcol))
        blocks, brow, bcol, flags = (
            blocks[order2], brow[order2], bcol[order2], flags[order2]
        )

    m = x.shape[0]
    pad_m = (-m) % tm
    xp = jnp.pad(x, ((0, pad_m), (0, 0))) if pad_m else x

    if backend in ("pallas", "pallas_interpret"):
        y = bsr_spmm(
            xp,
            jnp.asarray(blocks),
            jnp.asarray(brow),
            jnp.asarray(bcol),
            jnp.asarray(flags),
            n=n,
            tm=tm,
            interpret=kernel_interpret(backend),
        )
    else:
        y = ref.bsr_spmm_ref(xp, jnp.asarray(blocks), brow, bcol, n)
    return y[:m] if pad_m else y


# ---------------------------------------------------------------------------
# Grouped matmul (MoE dispatch)
# ---------------------------------------------------------------------------

def grouped_matmul(
    x: jax.Array,  # [T, D] tokens sorted by expert (padded per expert)
    w: jax.Array,  # [E, D, F]
    tile_expert: jax.Array,  # [T // tm]
    *,
    tm: int = 128,
    backend: str = "auto",
) -> jax.Array:
    backend = resolve_backend(backend)
    if backend in ("pallas", "pallas_interpret"):
        d, f = w.shape[1], w.shape[2]
        return moe_gmm(
            x, w, tile_expert,
            tm=tm,
            bd=min(512, d) if d % min(512, d) == 0 else d,
            bf=min(512, f) if f % min(512, f) == 0 else f,
            interpret=kernel_interpret(backend),
        )
    return ref.moe_gmm_ref(x, w, np.asarray(tile_expert), tm)


# ---------------------------------------------------------------------------
# Attention (prefill hot-spot) with a recompute-based VJP
# ---------------------------------------------------------------------------

@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6),
)
def attention(
    q: jax.Array,  # [BH, Sq, D]
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    backend: str = "auto",
) -> jax.Array:
    be = resolve_backend(backend)
    if be in ("pallas", "pallas_interpret"):
        return flash_attention(
            q, k, v,
            causal=causal, window=window, q_offset=q_offset,
            interpret=kernel_interpret(be),
        ).astype(q.dtype)
    return ref.flash_attention_ref(
        q, k, v, causal=causal, window=window, q_offset=q_offset
    ).astype(q.dtype)


def _attention_fwd(q, k, v, causal, window, q_offset, backend):
    out = attention(q, k, v, causal, window, q_offset, backend)
    return out, (q, k, v)


def _attention_bwd(causal, window, q_offset, backend, res, g):
    q, k, v = res
    # Recompute-based backward through the oracle (flash-bwd kernel is a
    # TPU-side optimization; semantics identical).
    def f(q_, k_, v_):
        return ref.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window, q_offset=q_offset
        ).astype(q_.dtype)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


attention.defvjp(_attention_fwd, _attention_bwd)
