"""Block-Gustavson SpGEMM Pallas kernel (the paper's FPGA kernel on TPU).

Hardware adaptation (DESIGN.md Sec. 2): the FPGA's NUM_PE parallel PEs with
a shared B-row buffer become a *static triple schedule* executed by a Pallas
grid. Each grid step t performs one (bm x bk) @ (bk x bn) MXU matmul:

    panels[panel[t]][sub_row[t]*bm : (sub_row[t]+1)*bm, :] += A[a_slot[t]] @ B[b_slot[t]]

The schedule (core/schedule.py) is in BCSV vector-major order, so

* the packed A-blocks array is streamed **sequentially** from HBM — the CSV
  format's "regular access pattern" (paper Sec. 3);
* consecutive triples sharing ``b_slot`` hit the Pallas revisit-elision: the
  B block stays in VMEM and is **not** re-fetched — the paper's Sec. 4.1
  buffering scheme, with OMAR (Eq. 1) counting exactly the elided copies;
* each output panel (the G·bm x bn accumulator = the union of the G PEs'
  double buffers) is visited in one contiguous run, so it lives in VMEM for
  the whole run and is written back to HBM once.

Scalar prefetch (PrefetchScalarGridSpec) plays the role of the load kernel's
scheduling side-channel (A_DS of Table 1): slot/panel/sub-row indices are
resident in SMEM before the grid body runs.

**Schedule split.** The five prefetch arrays of one call must fit the
core's SMEM, so a schedule longer than
:data:`repro.core.perfmodel.SCHEDULE_TRIPLES_PER_CALL` runs as several
calls over contiguous slices of it (:func:`schedule_cuts`), cut at panel
starts: each panel is accumulated whole, in schedule order, inside one
call, and every call after the first writes its panels into the panel
buffer of the call before it (``input_output_aliases``), so the calls
fill one buffer with no copy. A schedule within the budget is one slice,
and one call.

**Batched variant** (:func:`spgemm_scheduled_batch_impl`): a value batch is
folded into the grid as a leading dimension — grid ``(bsz, t_pad)``, with
the shared triple schedule replicated per batch element through the
BlockSpec index maps (element ``b`` reads A slot ``b * nnzb_a + a_slot[t]``
and writes panel ``b * (n_panels + 1) + panel[t]``). The grid iterates the
triple dimension innermost, so each element executes its full schedule
consecutively: per-element accumulation order — and therefore the result —
is bitwise-identical to running the single-set kernel once per element, and
the schedule arrays themselves are staged on device once regardless of
batch size.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import perfmodel


__all__ = [
    "compact_csr_indptr_impl",
    "compact_row_counts_impl",
    "pad_schedule_arrays",
    "schedule_cuts",
    "spgemm_scheduled",
    "spgemm_scheduled_batch",
    "spgemm_scheduled_batch_impl",
    "spgemm_scheduled_impl",
]


def _kernel(
    # scalar prefetch (SMEM)
    a_slot_ref,
    b_slot_ref,
    panel_ref,
    sub_row_ref,
    start_ref,
    # VMEM blocks
    a_ref,  # [1, bm, bk]
    b_ref,  # [1, bk, bn]
    *refs,  # [the earlier call's panel buffer (HBM, aliased),] out panel
    bm: int,
    t_dim: int = 0,
):
    o_ref = refs[-1]  # [1, G*bm, bn]
    # ``t_dim`` is the grid dimension that walks the triple schedule: 0 for
    # the single-set grid ``(t_pad,)``, 1 for the batch-folded grid
    # ``(bsz, t_pad)`` (the schedule is shared across batch elements, so
    # only the triple index selects into the prefetched SMEM arrays).
    t = pl.program_id(t_dim)
    # Zero the whole panel on its first triple (paper: PE buffers reset on
    # row change / RESET token).
    @pl.when(start_ref[t] == 1)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # HIGHEST makes the MXU multiply in full f32. Mosaic's default for f32
    # operands is one bf16 pass: on a TPU v5e a 128x128 f32 dot came out
    # 2.6e-3 of max off the f64 product (1.2e-7 at HIGHEST), and
    # 2cubes_sphere A @ A^T 2.8e-3 of max|C| off scipy's f32 product.
    prod = jnp.dot(
        a_ref[0].astype(jnp.float32),
        b_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    row0 = sub_row_ref[t] * bm
    cur = o_ref[0, pl.dslice(row0, bm), :]
    o_ref[0, pl.dslice(row0, bm), :] = cur + prod.astype(o_ref.dtype)


def pad_schedule_arrays(
    a_slot: np.ndarray,
    b_slot: np.ndarray,
    panel: np.ndarray,
    sub_row: np.ndarray,
    start: np.ndarray,
    n_panels: int,
    pad_to: int | None = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    """Pad the triple schedule to a fixed length with dummy-panel triples.

    Padding triples write to panel ``n_panels`` (an extra scratch panel the
    wrapper strips), with start=1 so they never accumulate garbage.
    """
    t = int(a_slot.shape[0])
    t_pad = pad_to if pad_to is not None else max(1, t)
    if t_pad < t:
        raise ValueError(f"pad_to={t_pad} < schedule length {t}")
    pad = t_pad - t

    def _p(x, fill):
        return np.concatenate([x, np.full(pad, fill, x.dtype)]) if pad else x

    return (
        _p(a_slot, 0),
        _p(b_slot, 0),
        _p(panel, n_panels),
        _p(sub_row, 0),
        _p(start, 1),
        t_pad,
    )


def schedule_cuts(start: np.ndarray, budget: int | None = None) -> np.ndarray:
    """Bounds ``[0, c_1, ..., T]`` of the calls a schedule runs as: the
    fewest contiguous slices of at most ``budget`` triples (default
    :data:`repro.core.perfmodel.SCHEDULE_TRIPLES_PER_CALL`), each cut at a
    panel start (``start == 1``), so no panel spans two calls. A schedule
    within the budget is one slice; an empty one is one empty slice.
    Raises ``ValueError`` when one panel alone holds more than ``budget``
    triples."""
    if budget is None:
        budget = perfmodel.SCHEDULE_TRIPLES_PER_CALL
    t = int(start.shape[0])
    # Where a slice may end: a panel start, or the end of the schedule.
    ends = np.append(np.flatnonzero(start), t)
    cuts = [0]
    while True:
        lo = cuts[-1]
        hi = int(ends[np.searchsorted(ends, lo + budget, side="right") - 1])
        if hi <= lo and t:
            raise ValueError(
                f"a panel at triple {lo} holds more than {budget} triples, "
                f"more than one pallas_call's SMEM takes")
        cuts.append(hi)
        if hi == t:
            return np.asarray(cuts, np.int64)


def _carry(panels):
    """A call's extra in-spec, operand and alias that write its panels into
    ``panels``, the buffer of the call before it, in place: the buffer
    stays in HBM (``pl.ANY``), unread, and aliases the output; the panels
    this call does not visit keep what they hold. The first call has no
    buffer to carry."""
    if panels is None:
        return [], (), {}
    # Operand index: five prefetch arrays, A blocks, B blocks, the buffer.
    return [pl.BlockSpec(memory_space=pl.ANY)], (panels,), {7: 0}


def spgemm_scheduled_impl(
    a_blocks: jax.Array,  # [nnzb_a, bm, bk] packed BCSV blocks (stream order)
    b_blocks: jax.Array,  # [nnzb_b, bk, bn] packed BCSR blocks
    slices,  # ((a_slot, b_slot, panel, sub_row, start), ...) int32, per call
    *,
    n_panels: int,
    group: int,
    interpret: bool = True,
) -> jax.Array:
    """Unjitted body of :func:`spgemm_scheduled`.

    ``slices`` is the padded schedule as the calls run it: one tuple of
    the five int32 arrays per ``pallas_call`` (dummy panel = ``n_panels``),
    each slice starting a panel (:func:`schedule_cuts`). The calls run in
    order into one panel buffer.

    Exposed so callers that fuse further device work around the kernel
    (``repro.spgemm.executor`` chains it with value rebind and output
    assembly) can place the whole pipeline under one ``jax.jit`` without
    nesting jits. Returns panels [n_panels, group*bm, bn] float32 (dummy
    panel stripped).
    """
    bm, bk = a_blocks.shape[1], a_blocks.shape[2]
    bn = b_blocks.shape[2]
    out = None
    for sched in slices:
        carry_spec, carried, aliases = _carry(out)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(sched[0].shape[0],),
            in_specs=[
                pl.BlockSpec((1, bm, bk),
                             lambda t, a_s, b_s, p, sr, st: (a_s[t], 0, 0)),
                pl.BlockSpec((1, bk, bn),
                             lambda t, a_s, b_s, p, sr, st: (b_s[t], 0, 0)),
            ] + carry_spec,
            out_specs=pl.BlockSpec(
                (1, group * bm, bn), lambda t, a_s, b_s, p, sr, st: (p[t], 0, 0)
            ),
        )
        out = pl.pallas_call(
            functools.partial(_kernel, bm=bm),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (n_panels + 1, group * bm, bn), jnp.float32),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            input_output_aliases=aliases,
        )(*sched, a_blocks, b_blocks, *carried)
    return out[:n_panels]


spgemm_scheduled = jax.jit(
    spgemm_scheduled_impl,
    static_argnames=("n_panels", "group", "interpret"),
)
spgemm_scheduled.__doc__ = (
    "Run the scheduled block-Gustavson SpGEMM (jitted entry point).\n\n"
    "Returns panels [n_panels, group*bm, bn] float32 (dummy panel "
    "stripped). See :func:`spgemm_scheduled_impl` for the unjitted body."
)


def spgemm_scheduled_batch_impl(
    a_blocks: jax.Array,  # [bsz * nnzb_a, bm, bk] stacked packed BCSV blocks
    b_blocks: jax.Array,  # [bsz * nnzb_b, bk, bn] stacked packed BCSR blocks
    slices,  # ((a_slot, b_slot, panel, sub_row, start), ...) shared, per call
    *,
    bsz: int,
    n_panels: int,
    group: int,
    interpret: bool = True,
) -> jax.Array:
    """Batch-folded scheduled kernel: one Pallas grid for a value batch
    per schedule slice (``slices`` as :func:`spgemm_scheduled_impl` takes
    them, the calls writing into one panel buffer).

    The batch is the leading grid dimension — grid step ``(b, t)`` runs
    triple ``t`` of element ``b`` against that element's slice of the
    stacked block arrays (``[bsz * slots, ...]``, the layout the executor's
    batched rebind already produces). Triples iterate innermost, so each
    element's panels are visited in the same contiguous runs as the
    single-set grid: B-block revisit-elision and single panel write-back
    still apply per element, and results are bitwise-equal to ``bsz``
    single-set calls.

    Each element owns ``n_panels + 1`` output panels (its own dummy slot for
    the padding triples, mirroring :func:`spgemm_scheduled_impl`). Returns
    ``[bsz, n_panels, group*bm, bn]`` float32 with the dummies stripped.
    """
    a_slots = a_blocks.shape[0] // bsz
    b_slots = b_blocks.shape[0] // bsz
    bm, bk = a_blocks.shape[1], a_blocks.shape[2]
    bn = b_blocks.shape[2]
    stride = n_panels + 1
    out = None
    for sched in slices:
        carry_spec, carried, aliases = _carry(out)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(bsz, sched[0].shape[0]),
            in_specs=[
                pl.BlockSpec(
                    (1, bm, bk),
                    lambda b, t, a_s, b_s, p, sr, st: (b * a_slots + a_s[t], 0, 0),
                ),
                pl.BlockSpec(
                    (1, bk, bn),
                    lambda b, t, a_s, b_s, p, sr, st: (b * b_slots + b_s[t], 0, 0),
                ),
            ] + carry_spec,
            out_specs=pl.BlockSpec(
                (1, group * bm, bn),
                lambda b, t, a_s, b_s, p, sr, st: (b * stride + p[t], 0, 0),
            ),
        )
        out = pl.pallas_call(
            functools.partial(_kernel, bm=bm, t_dim=1),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (bsz * stride, group * bm, bn), jnp.float32
            ),
            interpret=interpret,
            # The batch axis is race-free, so it may be declared "parallel":
            # element b only ever writes output slots b*stride + panel[t]
            # with panel[t] in [0, n_panels], i.e. inside its private
            # half-open range [b*stride, (b+1)*stride) — no slot is shared
            # across b (proven statically per plan by
            # repro.analysis.verify.check_batch_races). The triple axis
            # stays "arbitrary": panels are revisited across contiguous
            # runs of t, a sequential accumulate dependence.
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
            ),
            input_output_aliases=aliases,
        )(*sched, a_blocks, b_blocks, *carried)
    return out.reshape(bsz, stride, group * bm, bn)[:, :n_panels]


def compact_row_counts_impl(row_ids: jax.Array, *, m: int) -> jax.Array:
    """Device-side per-row nnz counts of a compacted C.

    ``row_ids`` is the static per-nnz row id stream of the compact
    assembly map (CSR order). One segment-sum over a ones vector — the
    device half of the compaction bookkeeping; the host precomputed the
    same counts at plan time, so the two must agree elementwise (a test
    invariant, not a runtime check). Returns ``[m]`` int32.
    """
    return jax.ops.segment_sum(
        jnp.ones(row_ids.shape, jnp.int32), row_ids, num_segments=m
    )


def compact_csr_indptr_impl(row_ids: jax.Array, *, m: int) -> jax.Array:
    """Device-resident CSR ``indptr`` for the compacted output.

    Segment-sum counts + ``jnp.cumsum`` prefix — the device-side
    compaction stage. Paired with the compact gather (which is fused into
    the assemble step as one static gather), this yields a full CSR
    replica of C on device with zero host round trips, which is what lets
    chained plans (``repro.spgemm.plan.execute_chain``) hand C straight to
    the next stage. Returns ``[m + 1]`` int32 (int32 covers every plan the
    executor accepts: gather indices themselves are int32 until the flat
    panel space exceeds 2**31).
    """
    counts = compact_row_counts_impl(row_ids, m=m)
    indptr = jnp.zeros(m + 1, jnp.int32)
    return indptr.at[1:].set(jnp.cumsum(counts))


spgemm_scheduled_batch = jax.jit(
    spgemm_scheduled_batch_impl,
    static_argnames=("bsz", "n_panels", "group", "interpret"),
)
spgemm_scheduled_batch.__doc__ = (
    "Run the batch-folded scheduled SpGEMM (jitted entry point).\n\n"
    "Returns panels [bsz, n_panels, group*bm, bn] float32. See\n"
    ":func:`spgemm_scheduled_batch_impl` for the unjitted body."
)
