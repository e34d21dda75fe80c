"""Flash attention Pallas kernel (prefill hot-spot).

Online-softmax tiled attention: grid (batch*heads, q_blocks, kv_blocks) with
running (max, denom, acc) in VMEM scratch — the kv axis is the innermost
"arbitrary" dimension so the scratch carries across kv steps and the output
block is written exactly once per q block (on the last kv step).

Supports causal masking, a sliding window (SWA, h2o-danube / jamba), and a
``q_offset`` so chunked prefill can continue against an existing KV cache.
Oracle: kernels/ref.py::flash_attention_ref.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _kernel(
    q_ref,  # [1, bq, d]
    k_ref,  # [1, bk, d]
    v_ref,  # [1, bk, d]
    o_ref,  # [1, bq, d]
    m_ref,  # [bq, 128] running max
    l_ref,  # [bq, 128] running denom
    acc_ref,  # [bq, d] running numerator
    *,
    bq: int,
    bk: int,
    causal: bool,
    window: Optional[int],
    q_offset: int,
    scale: float,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [bq, bk]

    i = pl.program_id(1)
    qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= kj > qi - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[:, :1]  # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)  # rescale of old accumulation
    p = jnp.exp(s - m_new)  # [bq, bk]
    p = jnp.where(mask, p, 0.0)
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        denom = l_ref[:, :1]
        safe = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "scale", "bq", "bk", "interpret"
    ),
)
def flash_attention(
    q: jax.Array,  # [BH, Sq, D]
    k: jax.Array,  # [BH, Skv, D]
    v: jax.Array,  # [BH, Skv, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    bq: int = 512,
    bk: int = 512,
    interpret: bool = True,
) -> jax.Array:
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0
    scale_val = float(scale) if scale is not None else 1.0 / float(np.sqrt(d))
    grid = (bh, sq // bq, skv // bk)
    kern = functools.partial(
        _kernel,
        bq=bq,
        bk=bk,
        causal=causal,
        window=window,
        q_offset=q_offset,
        scale=scale_val,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )(q, k, v)
