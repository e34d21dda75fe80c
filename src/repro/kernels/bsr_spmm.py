"""Block-sparse-weight matmul (SpMM) Pallas kernel: y = x @ W, W in BCSV.

This is the Gustavson specialization used inside the LM models
(``SparseLinear``): the *weight* matrix W [K, N] is block-sparse and the
activation x [M, K] is dense, so every "B row" of Gustavson is a dense
activation tile. W's blocks are stored column-panel-major — sorted by
``(bcol, brow)``, the CSV vector-major order with the output panel as the
vector axis — so:

* the packed W-blocks array streams sequentially from HBM (CSV regularity);
* all blocks of one output column panel are consecutive, so the f32
  accumulator tile lives in VMEM scratch for exactly one run (the PE's
  double buffer) and is written back once per (m-tile, column panel).

Scalars ``w_brow/w_bcol/first/last`` are the load-kernel side channel
(paper Table 1: B_NUM_VEC / RESET become first/last run flags).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["bsr_spmm", "plan_bsr"]


def plan_bsr(
    w_brow: np.ndarray, w_bcol: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Column-panel-major ordering + run flags for the kernel.

    Returns (order, brow_sorted, bcol_sorted, flags) where flags[t] is
    1 for the first block of a bcol run, 2 for the last, 3 for both.
    """
    order = np.lexsort((w_brow, w_bcol))
    br, bc = w_brow[order], w_bcol[order]
    t = br.shape[0]
    first = np.empty(t, bool)
    last = np.empty(t, bool)
    first[0] = True
    first[1:] = bc[1:] != bc[:-1]
    last[-1] = True
    last[:-1] = bc[1:] != bc[:-1]
    flags = first.astype(np.int32) + 2 * last.astype(np.int32)
    return order, br.astype(np.int32), bc.astype(np.int32), flags


def _kernel(brow_ref, bcol_ref, flag_ref, x_ref, w_ref, o_ref, acc_ref):
    t = pl.program_id(1)
    flag = flag_ref[t]

    @pl.when(flag & 1 == 1)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(flag & 2 == 2)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n", "tm", "out_dtype", "interpret")
)
def bsr_spmm(
    x: jax.Array,  # [M, K] dense (M % tm == 0)
    w_blocks: jax.Array,  # [nnzb, bk, bn] in column-panel-major order
    w_brow: jax.Array,  # [nnzb] int32 (K-block index)
    w_bcol: jax.Array,  # [nnzb] int32 (N-block index), non-decreasing
    flags: jax.Array,  # [nnzb] int32 run flags from plan_bsr
    *,
    n: int,
    tm: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = True,
) -> jax.Array:
    """y[M, N] = x @ W for block-sparse W. Absent column panels stay zero?

    No — absent column panels are never visited, so the wrapper requires the
    plan to cover every N panel (callers guarantee ≥1 block per column panel;
    ``ops.sparse_dense_matmul`` pads a zero block for empty panels).
    """
    m, k = x.shape
    nnzb, bk, bn = w_blocks.shape
    grid = (m // tm, nnzb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, bk), lambda i, t, br, bc, fl: (i, br[t])),
            pl.BlockSpec((1, bk, bn), lambda i, t, br, bc, fl: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, bn), lambda i, t, br, bc, fl: (i, bc[t])),
        scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(w_brow, w_bcol, flags, x, w_blocks)
