"""Run-wise output assembly: C's values placed from the kernel's panels run
by run in VMEM, not fetched one element at a time.

The block-Gustavson kernel leaves C in panels ``[n_panels, G*bm, bn]``;
assembly packs C's values out of them in CSR order. As one element gather
(``panels.reshape(-1)[gather]``) that is one 4-byte HBM fetch per value.
But a C row's values in one block column sit side by side in a panel row,
so the gather is mostly stretches of consecutive indices: *runs*
(:class:`~repro.core.schedule.AssemblyRuns`, built from the gather at
executor build by :func:`~repro.core.schedule.build_assembly_runs`).

This kernel writes the output in blocks of ``BLOCK_ROWS`` x 128 values.
Panels are sorted by (group, block column), so one block's values come
from a few rows of the panels of one or two groups: the grid walks
*items*, each one block and one source *tile* (``TILE_PANELS`` panels x
``TILE_ROWS`` lane rows, one strided DMA that the pipeline overlaps with
the step before). An item places its runs from the tile in VMEM: it loads
the run's 128-lane source row, rotates it (``pltpu.roll``) so the run's
first lane lands on its output lane, and selects it into the one or two
128-lane output rows the run covers. Values are copied, never combined:
the result is bitwise ``panels.reshape(-1)[gather]``, -0.0 and NaN
included. Consecutive items of one block keep the block in VMEM, and it
is written back once.

Each item's block, tile and run range are scalar-prefetched; each
block's runs reach SMEM as a blocked input. An item table longer than one
call's SMEM holds runs as several calls, cut at block starts
(:func:`~repro.kernels.gustavson_spgemm.schedule_cuts`), each writing its
blocks into the output of the call before it (aliased, as the SpGEMM
kernel's schedule split does).

A leading batch axis (``bsz`` value sets whose panels are stacked) is the
grid's first dimension: the run table is shared, and each set reads its
own panels and writes its own output.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import perfmodel
from repro.core.schedule import LANES, AssemblyRuns, build_assembly_runs
from repro.kernels.gustavson_spgemm import schedule_cuts

__all__ = [
    "BLOCK_ROWS",
    "RUNS_PER_STEP",
    "TILE_PANELS",
    "TILE_ROWS",
    "assemble_runs_impl",
    "lane_rows",
    "plan_assembly_runs",
]

# Output block: 256 x 128 values (128 KiB), at most 2**15 as a run's
# packed offset takes.
BLOCK_ROWS = 256
# Source tile: 16 panels x 32 lane rows (256 KiB). At the benchmark's
# shapes the tiles the items read add up to 1.4-1.5 times the panel array
# (items x tile bytes, counted on the host).
TILE_PANELS = 16
TILE_ROWS = 32
# Runs placed a loop step. At one a step the loop's latency binds: 81.5 ns
# a run on a TPU v5e at every tile tried, 47.5 ns at two a step (the kernel
# alone on both benchmark patterns); more a step overlap their loads and
# rotations further.
RUNS_PER_STEP = 16


def lane_rows(group: int, bm: int, bn: int) -> Optional[int]:
    """128-lane rows of one panel, or ``None`` where a panel is not a
    whole number of them (the run-wise view does not exist)."""
    size = group * bm * bn
    return size // LANES if size % LANES == 0 else None


def _tile(n_panels: int, rows: int) -> Tuple[int, int]:
    """The source tile over ``n_panels`` panels of ``rows`` lane rows:
    (panels, lane rows). A panel whose lane rows do not split into
    ``TILE_ROWS`` is read whole."""
    return min(TILE_PANELS, n_panels), (
        TILE_ROWS if rows % TILE_ROWS == 0 else rows)


def plan_assembly_runs(
    gather: np.ndarray, *, n_panels: int, group: int, bm: int, bn: int,
) -> Optional[Tuple[AssemblyRuns, np.ndarray]]:
    """The run table of an assembly ``gather`` over ``n_panels`` panels
    of ``[group*bm, bn]``, and the item bounds of the calls it runs as;
    ``None`` where panels have no 128-lane view or the map is empty."""
    rows = lane_rows(group, bm, bn)
    if rows is None or not gather.shape[0] or not n_panels:
        return None
    tile_panels, tile_rows = _tile(n_panels, rows)
    runs = build_assembly_runs(
        gather, n_panels=n_panels, lane_rows=rows, tile_panels=tile_panels,
        tile_rows=tile_rows, block_rows=BLOCK_ROWS,
    )
    first = np.ones(runs.item_block.shape[0], np.int8)
    first[1:] = runs.item_block[1:] != runs.item_block[:-1]
    budget = perfmodel.assembly_items_per_call(runs.width)
    return runs, schedule_cuts(first, budget)


def _kernel(
    # scalar prefetch (SMEM)
    item_block_ref,
    item_tile_ref,
    item_end_ref,
    # the item's block of the run table (SMEM) and source tile (VMEM)
    table_ref,  # [2 * width]
    src_ref,  # [tile_panels, tile_rows, 128]
    *refs,  # [the earlier call's output (HBM, aliased),] out block
    width: int,
    block_rows: int,
    unroll: int,
):
    out_ref = refs[-1]  # [block_rows, 128]
    i = pl.program_id(1)
    block = item_block_ref[i]
    prev = jnp.maximum(i - 1, 0)
    first = (i == 0) | (item_block_ref[prev] != block)

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    lo = jnp.where(first, 0, item_end_ref[prev])
    hi = item_end_ref[i]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def fetch(j):
        """Run ``j``'s source row, rotated onto its output lanes."""
        w0 = table_ref[j]
        w1 = table_ref[width + j]
        offset = w1 & 0x7FFF
        start = offset & (LANES - 1)
        x = src_ref[w0 >> 16, pl.ds(w0 & 0xFFFF, 1), :]
        y = pltpu.roll(x, (w1 >> 15) & (LANES - 1), 1)
        return offset >> 7, start, start + (w1 >> 22), y

    def put(row, start, end, y):
        """Select the run's lanes into its output row. The lanes rotated
        past lane 127 go to the next row; a run that fits one row selects
        none there (and never reaches past the block)."""
        here = out_ref[pl.ds(row, 1), :]
        out_ref[pl.ds(row, 1), :] = jnp.where(
            (lane >= start) & (lane < end), y, here)
        nxt = jnp.minimum(row + 1, block_rows - 1)
        there = out_ref[pl.ds(nxt, 1), :]
        out_ref[pl.ds(nxt, 1), :] = jnp.where(lane < end - LANES, y, there)

    def step(k, carry):
        # Several runs a loop step: their loads and rotations overlap.
        placed = [fetch(lo + k * unroll + u) for u in range(unroll)]
        for run in placed:
            put(*run)
        return carry

    def one(j, carry):
        put(*fetch(j))
        return carry

    whole = (hi - lo) // unroll
    jax.lax.fori_loop(0, whole, step, 0)
    jax.lax.fori_loop(lo + whole * unroll, hi, one, 0)


def assemble_runs_impl(
    panels: jax.Array,  # [bsz * n_panels, group*bm, bn]
    table: jax.Array,  # [n_blocks * 2 * width] int32
    items,  # ((item_block, item_tile, item_end), ...) int32, per call
    *,
    bsz: int,
    nnz: int,
    interpret: bool = True,
) -> jax.Array:
    """Place C's ``nnz`` values out of ``bsz`` stacked panel arrays by the
    run table ``table`` (one call's items per entry of ``items``, cut at
    block starts; :func:`plan_assembly_runs`). Returns ``[bsz, nnz]``,
    bitwise the element gather's."""
    if not nnz:
        return jnp.zeros((bsz, 0), panels.dtype)
    n_panels = panels.shape[0] // bsz
    rows = panels.shape[1] * panels.shape[2] // LANES
    tile_panels, tile_rows = _tile(n_panels, rows)
    row_tiles = rows // tile_rows
    n_blocks = -(-nnz // (BLOCK_ROWS * LANES))
    width = table.shape[0] // (2 * n_blocks)
    src = panels.reshape(bsz, n_panels, rows, LANES)
    out = None
    for item_block, item_tile, item_end in items:
        carry_spec, carried, aliases = [], (), {}
        if out is not None:
            # Operand index: three prefetch arrays, the table, the panels.
            carry_spec, carried, aliases = (
                [pl.BlockSpec(memory_space=pl.ANY)], (out,), {5: 0})
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bsz, item_block.shape[0]),
            in_specs=[
                pl.BlockSpec((2 * width,),
                             lambda b, i, blk, tile, end: (blk[i],),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(
                    (None, tile_panels, tile_rows, LANES),
                    lambda b, i, blk, tile, end: (
                        b, tile[i] // row_tiles, tile[i] % row_tiles, 0)),
            ] + carry_spec,
            out_specs=pl.BlockSpec(
                (None, BLOCK_ROWS, LANES),
                lambda b, i, blk, tile, end: (b, blk[i], 0)),
        )
        out = pl.pallas_call(
            functools.partial(_kernel, width=width, block_rows=BLOCK_ROWS,
                              unroll=RUNS_PER_STEP),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (bsz, n_blocks * BLOCK_ROWS, LANES), panels.dtype),
            interpret=interpret,
            # Each set writes only its own output; items revisit a block
            # in one contiguous stretch.
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
            ),
            input_output_aliases=aliases,
        )(item_block, item_tile, item_end, table, src, *carried)
    return out.reshape(bsz, -1)[:, :nnz]
