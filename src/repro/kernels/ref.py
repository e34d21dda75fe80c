"""Pure-jnp oracles for every Pallas kernel in this package.

Each function computes the same mathematical result as its kernel twin with
no Pallas machinery — used by tests/test_kernels.py (shape/dtype sweeps with
``assert_allclose``) and as the portable fallback path on non-TPU backends
(``ops.py`` dispatches on ``jax.default_backend()``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "bind_ref",
    "spgemm_scheduled_ref",
    "bsr_spmm_ref",
    "moe_gmm_ref",
    "flash_attention_ref",
]


def bind_ref(vals, scatter, shape) -> np.ndarray:
    """The value bind on the host: one operand's ``[nnz]`` values placed in
    zeroed packed blocks of ``shape`` at the plan's flat scatter indices
    (``blocks.flat[scatter] = vals``). The executor's device bind
    (``repro.spgemm.executor._bind``) must equal it bit for bit."""
    vals = np.asarray(vals)
    blocks = np.zeros(shape, vals.dtype)
    blocks.reshape(-1)[np.asarray(scatter)] = vals
    return blocks


def spgemm_scheduled_ref(
    a_blocks: jax.Array,  # [nnzb_a, bm, bk]
    b_blocks: jax.Array,  # [nnzb_b, bk, bn]
    a_slot: jax.Array,  # [T] (numpy or device array)
    b_slot: jax.Array,  # [T]
    panel: jax.Array,  # [T]
    sub_row: jax.Array,  # [T]
    n_panels: int,
    group: int,
    panels: Optional[jax.Array] = None,
) -> jax.Array:
    """Execute the SpGEMM triple schedule densely: for each triple t,
    ``panels[panel[t], sub_row[t]*bm : ..., :] += A[a_slot[t]] @ B[b_slot[t]]``.

    ``panels`` (``[n_panels, group*bm, bn]``, the result of an earlier
    slice of the same schedule) is the array the products are added into;
    zeros when it is not given.

    Pure jnp on traced arrays — safe to wrap in ``jax.jit`` and to ``vmap``
    over the block operands with a constant schedule (the batched executor
    path in ``repro.spgemm.executor``). Returns panels
    [n_panels, group*bm, bn] in float32.
    """
    bm = a_blocks.shape[1]
    bn = b_blocks.shape[2]
    # HIGHEST: full f32 products on a TPU too, like the Pallas kernel.
    prod = jnp.einsum(
        "tij,tjk->tik",
        a_blocks[jnp.asarray(a_slot)].astype(jnp.float32),
        b_blocks[jnp.asarray(b_slot)].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )  # [T, bm, bn]
    # Scatter-add each product at its flat panel-row offset: panels laid out
    # as [n_panels * group * bm, bn], triple t starts at row
    # panel[t]*group*bm + sub_row[t]*bm.
    row0 = jnp.asarray(panel, jnp.int32) * (group * bm) \
        + jnp.asarray(sub_row, jnp.int32) * bm
    rows = row0[:, None] + jnp.arange(bm, dtype=jnp.int32)[None, :]  # [T, bm]
    if panels is None:
        flat = jnp.zeros((n_panels * group * bm, bn), jnp.float32)
    else:
        flat = panels.reshape(n_panels * group * bm, bn)
    flat = flat.at[rows].add(prod)
    return flat.reshape(n_panels, group * bm, bn)


def bsr_spmm_ref(
    x: jax.Array,  # [M, K] dense activations
    w_blocks: jax.Array,  # [nnzb, bk, bn]
    w_brow: np.ndarray,  # [nnzb] K-block index
    w_bcol: np.ndarray,  # [nnzb] N-block index
    n: int,
) -> jax.Array:
    """y = x @ W with W block-sparse; densify W then one matmul (oracle)."""
    bk, bn = w_blocks.shape[1], w_blocks.shape[2]
    k = x.shape[1]
    w = jnp.zeros((k // bk, n // bn, bk, bn), w_blocks.dtype)
    w = w.at[jnp.asarray(w_brow), jnp.asarray(w_bcol)].set(w_blocks)
    w = w.transpose(0, 2, 1, 3).reshape(k, n)
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))


def moe_gmm_ref(
    x: jax.Array,  # [T, D] tokens sorted (grouped) by expert
    w: jax.Array,  # [E, D, F]
    tile_expert: np.ndarray,  # [T // tm] expert id of each token tile
    tm: int,
) -> jax.Array:
    """Grouped matmul oracle: each tm-token tile matmuls its expert's W."""
    t, d = x.shape
    xt = x.reshape(t // tm, tm, d).astype(jnp.float32)
    wt = w[jnp.asarray(tile_expert)].astype(jnp.float32)  # [nt, D, F]
    return jnp.einsum("tid,tdf->tif", xt, wt).reshape(t, w.shape[2])


def flash_attention_ref(
    q: jax.Array,  # [BH, Sq, D]
    k: jax.Array,  # [BH, Skv, D]
    v: jax.Array,  # [BH, Skv, D]
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Plain softmax attention (the oracle for the flash kernel).

    ``q_offset`` positions the query block inside the kv sequence (prefill
    continuation / decode). ``window`` is a sliding-window bound (SWA):
    key j is visible to query i iff  i + q_offset - window < j <= i + q_offset
    (when causal).
    """
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)) * s
    sq, skv = q.shape[1], k.shape[1]
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= kj > qi - window
    logits = jnp.where(mask[None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    # Fully-masked rows (can happen with windows) produce NaN in softmax;
    # zero them like the kernel does.
    probs = jnp.where(jnp.any(mask, axis=-1)[None, :, None], probs, 0.0)
    return jnp.einsum("bqk,bkd->bqd", probs, v.astype(jnp.float32))
