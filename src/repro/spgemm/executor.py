"""Device-resident SpGEMM numeric executor.

FSpGEMM's throughput claim (PAPER Sec. 4) rests on the numeric phase being a
pure streaming pipeline once host pre-processing is done. This module is
that pipeline as a *functional core*: a pure, jittable function

    (packed A blocks, packed B blocks) -> packed C values

chaining three device-side stages under one ``jax.jit``:

1. **value rebind** (optional, element plans): scatter fresh ``[nnz]`` value
   vectors into zeroed packed block arrays at the plan's precomputed flat
   scatter indices — one indexed element per value, so the work follows
   nnz, not the block arrays' slot count;
2. **the scheduled kernel**: the Pallas block-Gustavson kernel
   (:func:`repro.kernels.gustavson_spgemm.spgemm_scheduled_impl`) or the
   pure-jnp path (:func:`repro.kernels.ref.spgemm_scheduled_ref`);
3. **output assembly**: C's values placed out of the kernel's panels by
   the symbolic phase's :class:`~repro.core.schedule.AssemblyMap` — no
   data-dependent ``nonzero``, no per-panel host loop. On pallas backends
   the run-wise assembly kernel places them run by run in VMEM
   (:mod:`repro.kernels.assemble_runs`, from the map's run table); on jnp,
   and in the sharded programs, one static element gather.

Because every stage is shape-static, the core batches over a leading value
axis (:func:`numeric_core_batch`, the engine behind
``SpGEMMPlan.execute_batch``): semantically ``jax.vmap`` of the core,
lowered by folding the batch into the triple schedule — on pallas backends
the batch becomes the leading dimension of one scalar-prefetch Pallas grid
(:func:`~repro.kernels.gustavson_spgemm.spgemm_scheduled_batch_impl`), on
jnp an offset-folded schedule so XLA sees the same op shapes as the
single-set path. The jitted entry points are module-level with static
config arguments, so plans sharing shapes share executables;
:class:`SpGEMMExecutor` wraps them with a plan's device-resident constants
(schedule arrays, ``[nnz]`` scatter indices, the run table or the gather
map — shipped to device once).

The same shape-static property is what makes the phase meshable:
:class:`ShardedSpGEMMExecutor` (the numeric phase of
``repro.spgemm.plan.ShardedSpGEMMPlan``) stacks per-shard padded copies of
those constants along a leading shard axis, lays them out over one mesh
axis, and runs all three stages under a single ``shard_map`` — A
row-sharded, B replicated, C row-sharded and concatenated on host.

**Pipeline surface.** Next to the fused cores, the value bind is also
exposed as its own module-level jit (``bind_core`` / ``bind_batch_core``)
and both executors carry a three-step pipeline protocol::

    staged = ex.pipe_stage(a, b, mode=...)   # H2D + value rebind dispatch
    packed = ex.pipe_kernel(staged, mode)    # kernel + assembly dispatch
    out    = ex.pipe_collect(packed, mode)   # the ONLY blocking call (D2H)

Every step but ``pipe_collect`` merely *dispatches* device work (JAX
async dispatch returns immediately), so a driver that stages step
``s + 1`` before collecting step ``s`` overlaps ``s + 1``'s H2D copy and
rebind with ``s``'s kernel — the paper's double-buffered operand fetch,
expressed functionally: each in-flight step owns its own staged packed
A/B block arrays on device (per shard on the sharded executor), so a
pipeline of depth *d* is a *d*-deep operand buffer ring.
:class:`repro.spgemm.pipeline.SpGEMMPipeline` stages steps that way.
``pipe_kernel`` runs the kernel and the assembly as one program, the
fused core from staged blocks (``numeric_core`` / ``numeric_core_batch``;
the sharded ``kernel`` / ``batch_blocks`` programs), so the panel array
is that program's temporary: it never exists as a buffer of its own
between two programs, and every product's assembly reads it where the
program's temporaries lie, not wherever the allocator placed that
product's kernel output. The steps run exactly the ops of the fused
cores (shared helper functions, same schedules), so pipelined results
are bitwise-equal to the synchronous path on both kernel backends.

**Schedule split.** One kernel call's schedule has to fit the core's
SMEM. The executors cut the schedule at build into the fewest slices of
at most :data:`repro.core.perfmodel.SCHEDULE_TRIPLES_PER_CALL` triples,
at panel starts (:func:`~repro.kernels.gustavson_spgemm.schedule_cuts`),
and stage the slices on device; ``_run_schedule`` and
``_run_schedule_batch`` run one kernel call per slice into one panel
array, on every path and backend. Each panel is accumulated whole inside
one call, so the result is bitwise the unsplit one; a schedule within
the budget is one slice, and lowers to one call.

Every path runs the three stages through the same helpers (``_bind``,
``_run_schedule``, ``_assemble`` and their batch forms), each under a
``jax.named_scope`` — ``spgemm.bind``, ``spgemm.kernel``,
``spgemm.assemble`` — so the device ops of a profiler trace carry their
stage in the HLO ``op_name`` metadata, whichever jit ran them. Scopes are
metadata only: no op, fusion or result depends on them.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.schedule import (
    AssemblyMap,
    ScheduleShard,
    SpGEMMSchedule,
    stack_shard_schedules,
)
from repro.kernels import ref
from repro.kernels.assemble_runs import assemble_runs_impl, plan_assembly_runs
from repro.kernels.gustavson_spgemm import (
    compact_csr_indptr_impl,
    pad_schedule_arrays,
    schedule_cuts,
    spgemm_scheduled_batch_impl,
    spgemm_scheduled_impl,
)
from repro.launch.sharding import leading_sharding, replicated_sharding

__all__ = [
    "CHUNK_BYTES_ENV",
    "ShardedSpGEMMExecutor",
    "SpGEMMExecutor",
    "bind_batch_core",
    "bind_core",
    "kernel_interpret",
    "numeric_core",
    "numeric_core_batch",
    "resolve_chunk_bytes",
    "shard_program",
]

# Per-backend working-set budget for fusing batch elements into one device
# call: (per_set_budget_bytes, target_cache_bytes). The per-set budget is
# the knee where a fused chunk's accumulator working set leaves the fast
# memory tier; the calibration probe (repro.core.tuning.measure_chunk_knee,
# runnable as `python -m benchmarks.bench_chunk_knee` or the "Chunk-fusion
# knee calibration" bench section) is the measurement path for every row,
# and the env knob overrides any row without a code change.
#
# * cpu — measured by the probe on the CI-class container (2026-08, jnp
#   plans, batch 8): fused run_batch wins x1.1-2.0 per set up to
#   ~0.58 MiB/set and regresses from ~1.1 MiB/set (x0.86, collapsing to
#   x0.5 by 4 MiB), so the budget splits that bracket at 0.75 MiB; the
#   chunk sweep improved monotonically through chunk=8, keeping the 8 MiB
#   L3-class chunk cap.
# * tpu — probe methodology applied to the VMEM hierarchy pending an
#   on-device run: the batch-folded Pallas grid holds one (G*bm, bn) panel
#   + A/B tiles in VMEM per step regardless of batch, so the knee tracks a
#   set's panel-array footprint vs. usable VMEM
#   (repro.core.tuning.TPU_V5E.vmem_bytes = 16 MiB), HBM-side chunk cap 4x.
# * gpu — same methodology against an A100-class 40 MiB L2: budget L2/8,
#   chunk cap the full L2.
CHUNK_BYTES_ENV = "REPRO_SPGEMM_CHUNK_BYTES"
_CHUNK_POLICY = {
    "cpu": ((3 << 20) // 4, 8 << 20),
    "tpu": (16 << 20, 64 << 20),
    "gpu": (5 << 20, 40 << 20),
}


def resolve_chunk_bytes(chunk_bytes: Optional[int] = None) -> Tuple[int, int]:
    """Resolve the batch-fusion working-set budget.

    Precedence: ``REPRO_SPGEMM_CHUNK_BYTES`` env var > explicit
    ``chunk_bytes`` (constructor arg) > the per-backend default table.
    Returns ``(per_set_budget, cache_bytes)``; the cache target scales with
    an overridden budget so chunk sizing keeps its shape.
    """
    backend = jax.default_backend()
    if backend not in _CHUNK_POLICY:
        raise ValueError(
            f"no batch-fusion chunk policy for jax backend {backend!r}; "
            f"known: {sorted(_CHUNK_POLICY)}"
        )
    default_set, default_cache = _CHUNK_POLICY[backend]
    env = os.environ.get(CHUNK_BYTES_ENV)
    if env is not None:
        per_set = int(env)
    elif chunk_bytes is not None:
        per_set = int(chunk_bytes)
    else:
        return default_set, default_cache
    if per_set < 1:
        raise ValueError(f"chunk bytes must be >= 1, got {per_set}")
    scale = per_set / max(default_set, 1)
    return per_set, max(per_set, int(default_cache * scale))


def kernel_interpret(backend: str) -> bool:
    """The Pallas ``interpret`` flag for a kernel backend.

    ``"pallas_interpret"`` runs the kernel bodies in interpret mode on any
    platform; ``"pallas"`` compiles the real kernel and therefore needs a
    TPU — asking for it elsewhere is an error, never a silent fall back to
    the interpreter. ``"jnp"`` runs no Pallas kernel."""
    if backend == "pallas" and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"backend='pallas' compiles the Pallas kernel for a TPU, but the "
            f"jax default backend is {jax.default_backend()!r}; use "
            f"backend='pallas_interpret' to run the kernel in interpret mode"
        )
    return backend == "pallas_interpret"


_STATICS = ("n_panels", "group", "backend", "interpret", "run_nnz")
_PALLAS = ("pallas", "pallas_interpret")


def _stage_slices(arrays, cuts):
    """The schedule's device constants: one tuple of ``arrays`` cut to
    ``[lo, hi)`` per kernel call (the schedule as ``_run_schedule`` and
    ``_run_schedule_batch`` take it)."""
    return tuple(
        tuple(jnp.asarray(x[lo:hi]) for x in arrays)
        for lo, hi in zip(cuts[:-1], cuts[1:])
    )


def _run_schedule(
    a_blocks, b_blocks, sched, *, n_panels, group, backend, interpret
):
    """Dispatch the scheduled kernel, one call per slice of ``sched`` into
    one panel array. Each slice is the backend's device tuple:
    (a_slot, b_slot, panel, sub_row, start) padded for pallas,
    (a_slot, b_slot, panel, sub_row) raw for jnp."""
    with jax.named_scope("spgemm.kernel"):
        if backend in ("pallas", "pallas_interpret"):
            return spgemm_scheduled_impl(
                a_blocks, b_blocks, sched,
                n_panels=n_panels, group=group, interpret=interpret,
            )
        panels = None
        for a_slot, b_slot, panel, sub_row in sched:
            panels = ref.spgemm_scheduled_ref(
                a_blocks, b_blocks, a_slot, b_slot, panel, sub_row, n_panels,
                group, panels,
            )
        return panels


def _bind(vals, scatter, shape, mode="promise_in_bounds"):
    """Device-side value rebind: one scatter of the ``[nnz]`` values into
    a zeroed block array at the plan's flat indices (``blocks.flat[scatter]
    = vals``), so the device indexes nnz elements, not every slot. The
    indices are unique by construction; ``mode="drop"`` skips those past
    the array's end (a shard's elements outside its slot range)."""
    with jax.named_scope("spgemm.bind"):
        blocks = jnp.zeros(math.prod(shape), vals.dtype)
        return blocks.at[scatter].set(
            vals, unique_indices=True, mode=mode).reshape(shape)


def _assemble(panels, asm, bsz=None, run_nnz=None, interpret=False):
    """Output assembly: the packed C values out of the kernel's panels
    (``[bsz, nnz_c]`` per batch element when ``bsz`` is given). ``asm`` is
    the element gather map; or, where ``run_nnz`` gives the values, the
    run table and its calls' items, which the run-wise kernel places
    (:func:`~repro.kernels.assemble_runs.assemble_runs_impl`): bitwise the
    gather's values."""
    with jax.named_scope("spgemm.assemble"):
        if run_nnz is not None:
            table, items = asm
            out = assemble_runs_impl(
                panels, table, items, bsz=bsz or 1, nnz=run_nnz,
                interpret=interpret,
            )
            return out if bsz is not None else out[0]
        if bsz is None:
            return panels.reshape(-1)[asm]
        return panels.reshape(bsz, -1)[:, asm]


@functools.partial(jax.jit, static_argnames=_STATICS)
def numeric_core(
    a_blocks, b_blocks, sched, asm, *, n_panels, group, backend, interpret,
    run_nnz=None,
):
    """Functional numeric phase: packed blocks -> packed C values."""
    panels = _run_schedule(
        a_blocks, b_blocks, sched,
        n_panels=n_panels, group=group, backend=backend, interpret=interpret,
    )
    return _assemble(panels, asm, run_nnz=run_nnz, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=_STATICS + ("a_shape", "b_shape")
)
def numeric_core_values(
    a_vals, b_vals, a_scatter, b_scatter, sched, asm, *,
    a_shape, b_shape, n_panels, group, backend, interpret, run_nnz=None,
):
    """Numeric phase from [nnz] value vectors: rebind + kernel + assembly."""
    a_blocks = _bind(a_vals, a_scatter, a_shape)
    b_blocks = _bind(b_vals, b_scatter, b_shape)
    return numeric_core(
        a_blocks, b_blocks, sched, asm,
        n_panels=n_panels, group=group, backend=backend, interpret=interpret,
        run_nnz=run_nnz,
    )


def _bind_batch(vals, scatter, shape, mode="promise_in_bounds"):
    """Batched value rebind: ``[batch, nnz]`` values scattered through the
    shared ``[nnz]`` map into one zeroed ``[batch, slots * bm * bk]`` array
    (as :func:`_bind`), returned stacked along the slot axis."""
    bsz = vals.shape[0]
    with jax.named_scope("spgemm.bind"):
        blocks = jnp.zeros((bsz, math.prod(shape)), vals.dtype)
        return blocks.at[:, scatter].set(
            vals, unique_indices=True, mode=mode,
        ).reshape((bsz * shape[0],) + tuple(shape[1:]))


def _fold_schedule(sched, bsz, a_slots, b_slots, n_panels):
    """Fold a value batch into one slice of the triple schedule (jnp
    path): slot/panel indices of all batch elements offset per element, so
    the batch executes as one ``batch * T``-triple schedule over
    ``batch * n_panels`` panels while preserving each element's
    accumulation order exactly."""
    a_slot, b_slot, panel, sub_row = sched
    off = jnp.arange(bsz, dtype=jnp.int32)[:, None]
    return (
        (off * a_slots + a_slot[None, :]).reshape(-1),
        (off * b_slots + b_slot[None, :]).reshape(-1),
        (off * n_panels + panel[None, :]).reshape(-1),
        jnp.tile(sub_row, bsz),
    )


def _run_schedule_batch(
    a_blocks, b_blocks, sched, bsz, a_slots, b_slots,
    *, n_panels, group, backend, interpret,
):
    """Dispatch the batch-folded scheduled kernel over stacked blocks
    (``[bsz * slots, ...]``), one call per slice of ``sched`` (as
    :func:`_run_schedule`). On ``pallas``/``pallas_interpret`` the fold
    is the grid itself (:func:`spgemm_scheduled_batch_impl`, grid
    ``(bsz, t)`` over each padded slice); on ``jnp`` it is the
    offset-folded slice through the scatter-add reference. Both return
    panels ``[bsz * n_panels, group*bm, bn]`` with identical per-element
    accumulation order."""
    with jax.named_scope("spgemm.kernel"):
        if backend in ("pallas", "pallas_interpret"):
            panels = spgemm_scheduled_batch_impl(
                a_blocks, b_blocks, sched,
                bsz=bsz, n_panels=n_panels, group=group, interpret=interpret,
            )
            return panels.reshape((bsz * n_panels,) + panels.shape[2:])
        panels = None
        for piece in sched:
            a_slot_b, b_slot_b, panel_b, sub_row_b = _fold_schedule(
                piece, bsz, a_slots, b_slots, n_panels
            )
            panels = ref.spgemm_scheduled_ref(
                a_blocks, b_blocks, a_slot_b, b_slot_b, panel_b, sub_row_b,
                bsz * n_panels, group, panels,
            )
        return panels


@functools.partial(
    jax.jit,
    static_argnames=("a_shape", "b_shape", "rebind") + _STATICS,
)
def numeric_core_batch(
    a_vals, b_vals, a_scatter, b_scatter, sched, asm, *,
    a_shape, b_shape, rebind, n_panels, group, backend, interpret,
    run_nnz=None,
):
    """Batched numeric phase over a leading value axis.

    Semantically ``jax.vmap`` of the functional core, lowered by *folding
    the batch into the triple schedule* (:func:`_run_schedule_batch`): on
    pallas backends the batch becomes the leading grid dimension of one
    scalar-prefetch Pallas call; on jnp the schedule indices are offset per
    element into one long sorted scatter (which XLA lowers far better than
    a batched scatter on CPU). Both preserve each element's accumulation
    order exactly — batch results are bitwise equal to single executes on
    the same backend.

    ``rebind=True`` takes [batch, nnz] value vectors (element plans);
    ``rebind=False`` takes batched packed block arrays (block plans).
    """
    bsz = a_vals.shape[0]
    if rebind:
        a_blocks = _bind_batch(a_vals, a_scatter, a_shape)
        b_blocks = _bind_batch(b_vals, b_scatter, b_shape)
    else:
        a_blocks = a_vals.reshape((bsz * a_shape[0],) + tuple(a_shape[1:]))
        b_blocks = b_vals.reshape((bsz * b_shape[0],) + tuple(b_shape[1:]))
    panels = _run_schedule_batch(
        a_blocks, b_blocks, sched, bsz, a_shape[0], b_shape[0],
        n_panels=n_panels, group=group, backend=backend, interpret=interpret,
    )
    return _assemble(panels, asm, bsz, run_nnz=run_nnz, interpret=interpret)


# -- the pipeline's bind (the first step of the protocol) -----------------
#
# Module-level like the fused cores, so same-shaped plans share the bind
# executables too. The bind runs exactly the ops of the fused cores' bind
# (shared helpers), which is what keeps pipelined results bitwise-equal to
# synchronous executes; the kernel and the assembly then run as one fused
# core from the bound blocks.


@functools.partial(jax.jit, static_argnames=("shape",))
def bind_core(vals, scatter, *, shape):
    """Stage 1 (element plans): [nnz] values -> packed blocks on device."""
    return _bind(vals, scatter, shape)


@functools.partial(jax.jit, static_argnames=("shape",))
def bind_batch_core(vals, scatter, *, shape):
    """Stage 1, batched: [batch, nnz] values -> packed blocks
    ``[batch, slots, ...]``, as :func:`numeric_core_batch` takes them."""
    bsz = vals.shape[0]
    return _bind_batch(vals, scatter, shape).reshape((bsz,) + tuple(shape))


class SpGEMMExecutor:
    """A plan's numeric phase with device-resident constants.

    Stages the triple schedule, the scatter indices, and the assembly's
    run table (or gather map) on device once; ``run``/``run_values``/
    ``run_batch`` then call the module-level jitted cores (shared executables across same-shaped plans)
    with zero per-call host work beyond operand transfer.

    Every entry point honors the plan's backend: single-shot calls run the
    scalar-prefetch Pallas grid on pallas plans, and ``run_batch`` runs its
    batch-folded variant (:func:`~repro.kernels.gustavson_spgemm.
    spgemm_scheduled_batch_impl` — the batch is a leading grid dimension,
    so pallas plans never leave the MXU path when batched). The jnp
    (pure-XLA) kernel serves ``backend="jnp"`` plans on every path.
    """

    def __init__(
        self,
        *,
        schedule: SpGEMMSchedule,
        assembly: AssemblyMap,
        backend: str,
        a_scatter: Optional[np.ndarray] = None,
        b_scatter: Optional[np.ndarray] = None,
        a_shape: Tuple[int, ...] = (),
        b_shape: Tuple[int, ...] = (),
        chunk_bytes: Optional[int] = None,
    ):
        self.backend = backend
        self._chunk_policy = resolve_chunk_bytes(chunk_bytes)
        self.n_panels = schedule.n_panels
        self.group = schedule.group
        self.a_shape = tuple(a_shape)
        self.b_shape = tuple(b_shape)
        self._interpret = kernel_interpret(backend)
        # Per-set f32 rows the batched schedule touches (panel accumulator
        # + einsum products) — the working-set basis for batch_chunk().
        bm = a_shape[1] if len(a_shape) == 3 else 0
        self._bn = b_shape[2] if len(b_shape) == 3 else 0
        self._per_set_rows = (
            schedule.n_panels * schedule.group + schedule.num_triples
        ) * bm
        # The assembly map is the *active* output map: the plan passes its
        # block-structural map for output="block" and the element-exact
        # compact map for output="compact" — every path below places C's
        # values through it, so the compaction is fused into assembly for
        # free. Pallas plans place them run by run where the panels have a
        # 128-lane view (their run table); jnp plans, or panels without
        # the view, gather one value at a time.
        self.assemble_values = int(assembly.nnz)
        self.assembly_runs = None
        self._run_nnz: Optional[int] = None
        planned = None
        if backend in _PALLAS and len(a_shape) == 3 and len(b_shape) == 3:
            planned = plan_assembly_runs(
                np.asarray(assembly.gather), n_panels=schedule.n_panels,
                group=schedule.group, bm=bm, bn=self._bn,
            )
        if planned is None:
            self._asm = jnp.asarray(assembly.gather)
            self.assemble_runs = self.assemble_values
        else:
            runs, item_cuts = planned
            self.assembly_runs = runs
            self._run_nnz = runs.nnz
            self._asm = (
                jnp.asarray(runs.table.reshape(-1)),
                _stage_slices(
                    (runs.item_block, runs.item_tile, runs.item_end),
                    item_cuts),
            )
            self.assemble_runs = runs.n_runs
        self._out_rows = int(assembly.shape[0])
        self._indptr_host = np.asarray(assembly.indptr)
        self._row_ids: Optional[jax.Array] = None
        # The schedule in slices of one kernel call each, cut at panel
        # starts to fit one call's SMEM. Pallas plans stage the padded
        # 5-tuple per slice, shared by the single and batch-folded grids;
        # jnp plans the raw 4-tuple.
        cuts = schedule_cuts(schedule.start)
        self.kernel_calls = len(cuts) - 1
        self.triples = schedule.num_triples
        if backend in _PALLAS:
            self._sched = _stage_slices(pad_schedule_arrays(
                schedule.a_slot, schedule.b_slot, schedule.panel,
                schedule.sub_row, schedule.start, schedule.n_panels,
            )[:5], cuts)
        else:
            self._sched = _stage_slices((
                schedule.a_slot, schedule.b_slot, schedule.panel,
                schedule.sub_row,
            ), cuts)
        # Rebind maps: the plan's [nnz] flat scatter indices, as they are.
        self._a_scatter = (
            jnp.asarray(a_scatter, jnp.int32) if a_scatter is not None
            else None
        )
        self._b_scatter = (
            jnp.asarray(b_scatter, jnp.int32) if b_scatter is not None
            else None
        )

    @property
    def can_rebind(self) -> bool:
        return self._a_scatter is not None and self._b_scatter is not None

    def set_chunk_bytes(self, chunk_bytes: Optional[int]) -> None:
        """Re-resolve the chunk policy with a new per-set budget.

        The autotuner applies its winning ``chunk_bytes`` here after the
        executor is built; ``REPRO_SPGEMM_CHUNK_BYTES`` still wins inside
        :func:`resolve_chunk_bytes`, so an operator env override always
        beats a tuned (or constructor) value.
        """
        self._chunk_policy = resolve_chunk_bytes(chunk_bytes)

    def batch_chunk(
        self,
        small_set_bytes: Optional[int] = None,
        cache_bytes: Optional[int] = None,
    ) -> int:
        """Max batch elements per fused device call.

        Fusing pays only when one set's working bytes (panel accumulator +
        einsum intermediates, ``4 * per_set_rows * bn``) are small: chunks
        sized to keep ``chunk * per_set`` under ``cache_bytes`` then cut
        per-set cost 1.1-2x by amortizing dispatch (probe-measured, CPU).
        Above ``small_set_bytes`` per set, measured chunks *regress* (the
        fused accumulator leaves cache: x0.86 at 1.1 MiB/set falling to
        x0.5 by 4 MiB on the calibration container), so larger problems
        run one set per call — matching a single ``execute()`` minus its
        host rebind/staging work.

        Both knobs default to the resolved per-backend policy (constructor
        ``chunk_bytes`` arg, overridden by ``REPRO_SPGEMM_CHUNK_BYTES``):
        the CPU knee is an L2/L3 property and wrong for VMEM, so TPU/GPU
        backends get their own table rows. All rows are re-measured with
        :func:`repro.core.tuning.measure_chunk_knee` (see the
        ``_CHUNK_POLICY`` provenance note).
        """
        if small_set_bytes is None:
            small_set_bytes = self._chunk_policy[0]
        if cache_bytes is None:
            cache_bytes = self._chunk_policy[1]
        per_set = 4 * self._per_set_rows * self._bn
        if per_set <= small_set_bytes:
            return max(1, cache_bytes // max(per_set, 1))
        return 1

    def device_indptr(self) -> jax.Array:
        """Device-resident CSR ``indptr`` of the active output map.

        The device half of the compaction bookkeeping: segment-sum row
        counts + ``jnp.cumsum`` prefix over the map's static row-id stream
        (:func:`~repro.kernels.gustavson_spgemm.compact_csr_indptr_impl`).
        Together with the packed values a ``run*`` call returns, this is a
        complete CSR replica of C on device — the handoff structure
        ``execute_chain`` keeps resident between stages. Must agree
        elementwise with the plan's host-precomputed ``indptr`` (a test
        invariant)."""
        if self._row_ids is None:
            self._row_ids = jnp.asarray(np.repeat(
                np.arange(self._out_rows, dtype=np.int32),
                np.diff(self._indptr_host),
            ))
        return compact_csr_indptr_impl(self._row_ids, m=self._out_rows)

    def run(self, a_blocks, b_blocks) -> jax.Array:
        """Packed blocks -> packed C values (plan's backend)."""
        return numeric_core(
            a_blocks, b_blocks, self._sched, self._asm,
            n_panels=self.n_panels, group=self.group, backend=self.backend,
            interpret=self._interpret, run_nnz=self._run_nnz,
        )

    def run_values(self, a_vals, b_vals) -> jax.Array:
        """[nnz] value vectors -> packed C values, rebind included."""
        return numeric_core_values(
            a_vals, b_vals, self._a_scatter, self._b_scatter,
            self._sched, self._asm,
            a_shape=self.a_shape, b_shape=self.b_shape,
            n_panels=self.n_panels, group=self.group, backend=self.backend,
            interpret=self._interpret, run_nnz=self._run_nnz,
        )

    def run_batch(self, a_vals, b_vals, *, rebind: bool) -> jax.Array:
        """Batched values -> packed C values [batch, nnz_c] (plan's
        backend: the batch-folded Pallas grid on pallas plans)."""
        return numeric_core_batch(
            jnp.asarray(a_vals), jnp.asarray(b_vals),
            self._a_scatter, self._b_scatter,
            self._sched, self._asm,
            a_shape=self.a_shape, b_shape=self.b_shape, rebind=rebind,
            n_panels=self.n_panels, group=self.group, backend=self.backend,
            interpret=self._interpret, run_nnz=self._run_nnz,
        )

    # -- pipeline protocol (non-blocking until collect) --------------------
    #
    # ``mode`` for pipe_stage: "values" ([nnz] vectors, element plans),
    # "batch_values" ([batch, nnz]), "batch_blocks" ([batch, slots, ...]
    # packed blocks). Single-shot block operands are staged by the plan's
    # ``_stage_a``/``_stage_b`` hooks and enter at pipe_kernel directly.
    # ``mode`` for kernel/collect: "single" or "batch". Both dispatch on
    # the plan's backend (like ``run``/``run_batch``): pallas plans run the
    # scalar-prefetch grid, batch-folded in batch mode.

    def pipe_stage(self, a, b, *, mode: str):
        """H2D transfer + value-rebind dispatch; returns staged device
        packed blocks without blocking."""
        if mode == "values":
            return (
                bind_core(jax.device_put(a), self._a_scatter,
                          shape=self.a_shape),
                bind_core(jax.device_put(b), self._b_scatter,
                          shape=self.b_shape),
            )
        if mode == "batch_values":
            return (
                bind_batch_core(jax.device_put(a), self._a_scatter,
                                shape=self.a_shape),
                bind_batch_core(jax.device_put(b), self._b_scatter,
                                shape=self.b_shape),
            )
        if mode == "batch_blocks":
            return (
                jnp.asarray(a).reshape((-1,) + self.a_shape),
                jnp.asarray(b).reshape((-1,) + self.b_shape),
            )
        raise ValueError(f"unknown stage mode {mode!r}")  # pragma: no cover

    def pipe_kernel(self, staged, *, mode: str):
        """Kernel and assembly over staged blocks, one program (the fused
        core from blocks: ``run``'s, or ``run_batch``'s with
        ``rebind=False``); returns packed C values without blocking."""
        a_blocks, b_blocks = staged
        if mode == "single":
            return self.run(a_blocks, b_blocks)
        return numeric_core_batch(
            a_blocks, b_blocks, self._a_scatter, self._b_scatter,
            self._sched, self._asm,
            a_shape=self.a_shape, b_shape=self.b_shape, rebind=False,
            n_panels=self.n_panels, group=self.group, backend=self.backend,
            interpret=self._interpret, run_nnz=self._run_nnz,
        )

    def pipe_collect(self, packed, *, mode: str) -> np.ndarray:
        """Materialize packed C values on host (the only blocking step)."""
        return np.asarray(packed)


def shard_program(
    kind: str, *, mesh: Mesh, axis: str, backend: str, interpret: bool,
    group: int, a_max: int, p_max: int, a_shape: Tuple[int, ...],
    b_shape: Tuple[int, ...],
):
    """One jitted ``shard_map`` program of the sharded numeric phase.

    ``kind`` names the program: the fused cores from staged blocks
    (``kernel``, ``batch_blocks``: the kernel and the assembly, which
    ``run`` / ``run_batch`` and the pipeline's ``pipe_kernel`` run) or from
    values (``run_values``, ``batch_values``), or the pipeline's bind
    (``bind``, ``bind_batch``). Every input is ``[n_shards, ...]`` stacked over
    ``axis`` except the replicated B side; the schedule ``sched`` is one
    argument, a tuple of slices of five ``[n_shards, t]`` arrays, one
    kernel call each. Built from shapes and the mesh only, so it can be
    lowered for devices that are described rather than attached;
    :class:`ShardedSpGEMMExecutor` caches one per kind."""
    ax = axis
    bm, bk = a_shape[1], a_shape[2]
    # Every shard-local schedule slice is padded to its widest shard and
    # to p_max panels, so on pallas backends each device runs its own
    # scalar-prefetch grids over p_max + 1 panels — the same panel count
    # the jnp reference produces, keeping stage outputs shape-identical
    # across backends. The shard's own dummy triples target panel p_max
    # (never gathered); the impl-level dummy p_max + 1 is stripped inside
    # the call.

    def local(sched):
        """This device's rows of the stacked slices, as the backend's
        kernel takes them (jnp reads no start flags)."""
        rows = tuple(tuple(x[0] for x in piece) for piece in sched)
        if backend in ("pallas", "pallas_interpret"):
            return rows
        return tuple(piece[:4] for piece in rows)

    def kernel(a_blocks, b_blocks, sched, gth):
        panels = _run_schedule(
            a_blocks, b_blocks, local(sched),
            n_panels=p_max + 1, group=group, backend=backend,
            interpret=interpret,
        )
        return _assemble(panels, gth)

    def kernel_batch(a_blocks, b_blocks, sched, gth, bsz):
        panels = _run_schedule_batch(
            a_blocks, b_blocks, local(sched), bsz, a_max, b_shape[0],
            n_panels=p_max + 1, group=group, backend=backend,
            interpret=interpret,
        )
        return _assemble(panels, gth, bsz)

    out = P(ax)
    # pallas_call has no shard_map replication rule, so the programs
    # that contain the kernel disable the replication check on pallas
    # backends; the bind programs keep the check on.
    vma = True
    if kind == "kernel":
        def body(a_bl, b_bl, sched, gth):
            return kernel(a_bl[0], b_bl, sched, gth[0])[None]
        specs = (P(ax), P(), P(ax), P(ax))
        vma = False
    elif kind == "run_values":
        def body(a_vals, b_vals, a_sc, b_sc, sched, gth):
            a_bl = _bind(a_vals[0], a_sc[0], (a_max, bm, bk), mode="drop")
            b_bl = _bind(b_vals, b_sc, b_shape)
            return kernel(a_bl, b_bl, sched, gth[0])[None]
        specs = (P(ax), P(), P(ax), P(), P(ax), P(ax))
        vma = False
    elif kind == "batch_values":
        def body(a_vals, b_vals, a_sc, b_sc, sched, gth):
            bsz = a_vals.shape[1]
            a_bl = _bind_batch(a_vals[0], a_sc[0], (a_max, bm, bk),
                               mode="drop")
            b_bl = _bind_batch(b_vals, b_sc, b_shape)
            return kernel_batch(a_bl, b_bl, sched, gth[0], bsz)[None]
        specs = (P(ax), P(), P(ax), P(), P(ax), P(ax))
        vma = False
    elif kind == "batch_blocks":
        def body(a_vals, b_vals, sched, gth):
            bsz = a_vals.shape[1]
            a_bl = a_vals[0].reshape((bsz * a_max, bm, bk))
            b_bl = b_vals.reshape(
                (bsz * b_shape[0],) + tuple(b_shape[1:]))
            return kernel_batch(a_bl, b_bl, sched, gth[0], bsz)[None]
        specs = (P(ax), P(), P(ax), P(ax))
        vma = False
    # -- the pipeline's bind kinds: the fused bodies' bind, one shard_map
    # program of its own so staging step s+1 dispatches independently of
    # step s's kernel (which then runs as ``kernel`` / ``batch_blocks``).
    elif kind == "bind":
        def body(a_vals, b_vals, a_sc, b_sc):
            a_bl = _bind(a_vals[0], a_sc[0], (a_max, bm, bk), mode="drop")
            b_bl = _bind(b_vals, b_sc, b_shape)
            return a_bl[None], b_bl
        specs = (P(ax), P(), P(ax), P())
        out = (P(ax), P())
    elif kind == "bind_batch":
        def body(a_vals, b_vals, a_sc, b_sc):
            bsz = a_vals.shape[1]
            a_bl = _bind_batch(a_vals[0], a_sc[0], (a_max, bm, bk),
                               mode="drop")
            b_bl = _bind_batch(b_vals, b_sc, b_shape)
            return (
                a_bl.reshape((bsz, a_max, bm, bk))[None],
                b_bl.reshape((bsz,) + tuple(b_shape)),
            )
        specs = (P(ax), P(), P(ax), P())
        out = (P(ax), P())
    else:  # pragma: no cover - internal
        raise ValueError(kind)

    if backend not in ("pallas", "pallas_interpret"):
        vma = True
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=specs, out_specs=out, check_vma=vma,
    ))


def _stack_shard_slices(shards, t_max, p_max):
    """The stacked shard schedules (:func:`stack_shard_schedules`) in
    slices of one kernel call each: every shard's schedule is cut at its
    own panel starts (:func:`schedule_cuts`), and slice ``k`` stacks each
    shard's ``k``-th piece, padded with dummy triples to the widest piece
    (all dummies on a shard with fewer pieces). One piece per shard gives
    the stacked schedule as it is."""
    full = stack_shard_schedules(shards, t_max, p_max)
    cuts = [schedule_cuts(sh.schedule.start) for sh in shards]
    fill = (0, 0, p_max, 0, 1)  # a dummy triple, as the stacking pads
    out = []
    for k in range(max(len(c) for c in cuts) - 1):
        spans = [(c[k], c[k + 1]) if k + 1 < len(c) else (0, 0) for c in cuts]
        width = max(1, max(hi - lo for lo, hi in spans))
        piece = tuple(np.full((len(shards), width), f, np.int32) for f in fill)
        for i, (lo, hi) in enumerate(spans):
            for dst, src in zip(piece, full):
                dst[i, : hi - lo] = src[i, lo:hi]
        out.append(piece)
    return tuple(out)


class ShardedSpGEMMExecutor:
    """Numeric phase of a mesh-partitioned plan: one ``shard_map`` call.

    Drop-in for :class:`SpGEMMExecutor` on the plan side (same
    ``run``/``run_values``/``run_batch``/``batch_chunk`` surface), but the
    device-resident constants are *stacked per shard and laid out on the
    mesh*: every per-shard array (``[n_shards, ...]``, padded to the
    largest shard) is sharded over one mesh axis, B-side arrays are
    replicated, and the numeric phase runs under a single
    ``jax.jit(shard_map(...))`` — each device executes its own (padded)
    triple schedule against its own A blocks and the replicated B blocks,
    and emits its own packed C segment through its shard's
    :class:`~repro.core.schedule.AssemblyMap` gather.

    Layout contract (the tentpole's sharding policy):

    * A values / packed A blocks — **row-sharded**: shard ``i`` holds the
      slots ``[a_lo_i, a_hi_i)`` (elements ``[e_lo_i, e_hi_i)``), which are
      contiguous because BCSV packs blocks group-major;
    * B values / packed B blocks — **replicated** (the paper's shared
      B-buffer scheme lifted to the mesh);
    * C — **row-sharded**: the final CSR data is one host concatenation of
      the per-shard segments along the precomputed indptr boundaries.

    The kernel inside ``shard_map`` honors the plan's backend: every
    shard's rebased schedule is a contiguous standalone program, so on
    pallas plans each device runs its own scalar-prefetch Pallas grid over
    its padded schedule slice (batch-folded in the batched kinds) —
    ``shard_map`` is told ``check_vma=False`` for those programs since
    ``pallas_call`` carries no replication rule. The jnp (pure-XLA) path
    serves ``backend="jnp"``. On either backend, padding triples write to
    a dummy panel and padded gather slots are trimmed on host, so ragged
    and empty shards are handled by construction.
    """

    def __init__(
        self,
        *,
        shards: Sequence[ScheduleShard],
        assemblies: Sequence[AssemblyMap],
        mesh: Mesh,
        axis: str,
        backend: str,
        a_scatter: Optional[np.ndarray] = None,
        b_scatter: Optional[np.ndarray] = None,
        a_shape: Tuple[int, ...] = (),
        b_shape: Tuple[int, ...] = (),
        a_val_bounds: Optional[np.ndarray] = None,
        chunk_bytes: Optional[int] = None,
    ):
        if len(shards) != int(mesh.shape[axis]):
            raise ValueError(
                f"{len(shards)} shards for mesh axis {axis!r} of size "
                f"{mesh.shape[axis]}"
            )
        self.backend = backend
        self.mesh = mesh
        self.axis = axis
        self.a_shape = tuple(a_shape)
        self.b_shape = tuple(b_shape)
        self._interpret = kernel_interpret(backend)
        self._chunk_policy = resolve_chunk_bytes(chunk_bytes)
        self._shards = list(shards)
        s0 = shards[0].schedule
        self.group = s0.group
        self._s = len(shards)
        bm, bk = a_shape[1], a_shape[2]
        self._bm, self._bn = bm, b_shape[2]
        self._t_max = max(1, max(s.num_triples for s in shards))
        self._p_max = max(1, max(s.n_panels for s in shards))
        self._a_max = max(1, max(s.a_hi - s.a_lo for s in shards))
        self._assemblies = list(assemblies)
        self._nnz_c = [asm.nnz for asm in assemblies]
        self._c_max = max(1, max(self._nnz_c))
        self._row_ids: Optional[jax.Array] = None
        # Per-shard working set mirrors SpGEMMExecutor's basis, taken over
        # the *largest* shard (each device only holds its own panels).
        self._per_set_rows = (
            (self._p_max + 1) * self.group + self._t_max
        ) * bm

        self._sep = leading_sharding(mesh, axis)
        self._rep = replicated_sharding(mesh)

        def put(arr, sharding):
            return jax.device_put(np.ascontiguousarray(arr), sharding)

        # Stacked, padded schedule [n_shards, t_max] incl. per-shard start
        # flags (stack_shard_schedules): pads execute a real (block 0) x
        # (block 0) matmul into the dummy panel p_max, which no gather
        # reads; start=1 on pads keeps the pallas accumulator clean. Staged
        # in slices of one kernel call each (_stack_shard_slices).
        slices = _stack_shard_slices(shards, self._t_max, self._p_max)
        self.kernel_calls = len(slices)
        self.triples = sum(sh.num_triples for sh in shards)
        self._sched = tuple(
            tuple(put(x, self._sep) for x in piece) for piece in slices
        )
        gdtype = np.result_type(*(asm.gather.dtype for asm in assemblies))
        gather = np.zeros((self._s, self._c_max), gdtype)
        for i, asm in enumerate(assemblies):
            gather[i, : asm.nnz] = asm.gather
        self._gather = put(gather, self._sep)
        # The shard programs assemble by the element gather.
        self.assemble_values = self.assemble_runs = sum(self._nnz_c)

        # Rebind maps (element plans): per shard, the scatter index of each
        # element of its padded value slice into its own A block array;
        # B keeps the plan's plain map, replicated.
        self._a_scatter = self._b_scatter = None
        self._e_bounds: Optional[np.ndarray] = None
        self._e_max = 1
        if a_scatter is not None and b_scatter is not None:
            if a_val_bounds is None:
                raise ValueError("element shards need a_val_bounds")
            self._e_bounds = np.asarray(a_val_bounds, np.int64)
            self._e_max = max(1, int(np.diff(self._e_bounds).max(initial=0)))
            self._nnz_b = int(b_scatter.shape[0])
            # Elements of A blocks outside the shard's slot range never feed
            # a triple (no matching B block), and the slice's padding holds
            # no element: both get a distinct index past the shard's array,
            # which the bind drops (unique, so the bind's promise holds).
            flat_a = self._a_max * bm * bk
            a_sc = flat_a + np.tile(
                np.arange(self._e_max, dtype=np.int64), (self._s, 1))
            for i, sh in enumerate(shards):
                e_lo, e_hi = int(self._e_bounds[i]), int(self._e_bounds[i + 1])
                pos = a_scatter[e_lo:e_hi] - sh.a_lo * bm * bk
                sel = (pos >= 0) & (pos < (sh.a_hi - sh.a_lo) * bm * bk)
                a_sc[i, : e_hi - e_lo][sel] = pos[sel]
            self._a_scatter = put(a_sc.astype(np.int32), self._sep)
            self._b_scatter = put(np.asarray(b_scatter, np.int32), self._rep)
        self._fns: dict = {}

    # -- layout helpers (host side) ---------------------------------------

    @property
    def can_rebind(self) -> bool:
        return self._a_scatter is not None and self._b_scatter is not None

    def set_chunk_bytes(self, chunk_bytes: Optional[int]) -> None:
        """Re-resolve the chunk policy with a new per-set budget.

        The autotuner applies its winning ``chunk_bytes`` here after the
        executor is built; ``REPRO_SPGEMM_CHUNK_BYTES`` still wins inside
        :func:`resolve_chunk_bytes`, so an operator env override always
        beats a tuned (or constructor) value.
        """
        self._chunk_policy = resolve_chunk_bytes(chunk_bytes)

    def batch_chunk(
        self,
        small_set_bytes: Optional[int] = None,
        cache_bytes: Optional[int] = None,
    ) -> int:
        """Same policy as :meth:`SpGEMMExecutor.batch_chunk`, applied to
        the largest shard's per-device working set."""
        if small_set_bytes is None:
            small_set_bytes = self._chunk_policy[0]
        if cache_bytes is None:
            cache_bytes = self._chunk_policy[1]
        per_set = 4 * self._per_set_rows * self._bn
        if per_set <= small_set_bytes:
            return max(1, cache_bytes // max(per_set, 1))
        return 1

    def device_indptr(self) -> jax.Array:
        """Plan-wide device CSR ``indptr`` (see
        :meth:`SpGEMMExecutor.device_indptr`). Shard row ranges are
        contiguous and ascending, so the plan-wide row-id stream is the
        offset concatenation of the per-shard assembly streams — the same
        order :meth:`_concat` emits values in."""
        if self._row_ids is None:
            ids, off = [], 0
            for asm in self._assemblies:
                rows = int(asm.shape[0])
                ids.append(off + np.repeat(
                    np.arange(rows, dtype=np.int32),
                    np.diff(np.asarray(asm.indptr)),
                ).astype(np.int32))
                off += rows
            self._out_rows = off
            self._row_ids = jnp.asarray(
                np.concatenate(ids) if ids
                else np.zeros(0, np.int32)
            )
        return compact_csr_indptr_impl(self._row_ids, m=self._out_rows)

    def _concat(self, out: np.ndarray) -> np.ndarray:
        """Trim per-shard pads and concatenate along the shard axis (the
        CSR data order: shard row ranges are contiguous and ascending)."""
        return np.concatenate(
            [out[i, ..., : self._nnz_c[i]] for i in range(self._s)], axis=-1
        )

    def stage_a(self, blocks: np.ndarray) -> jax.Array:
        """Full packed A blocks -> stacked per-shard device layout."""
        return jax.device_put(self._stack_a(np.asarray(blocks)), self._sep)

    def stage_b(self, blocks: np.ndarray) -> jax.Array:
        """Full packed B blocks -> replicated device layout."""
        return jax.device_put(np.asarray(blocks), self._rep)

    def _stack_a(self, blocks: np.ndarray) -> np.ndarray:
        """Full packed A ([..batch..], nnzb_a, bm, bk) -> per-shard slot
        slices stacked and padded: (n_shards, [..batch..], a_max, bm, bk)."""
        lead = blocks.shape[:-3]
        out = np.zeros(
            (self._s,) + lead + (self._a_max,) + blocks.shape[-2:],
            blocks.dtype,
        )
        for i, sh in enumerate(self._shards):
            out[i, ..., : sh.a_hi - sh.a_lo, :, :] = (
                blocks[..., sh.a_lo: sh.a_hi, :, :]
            )
        return out

    def _slice_a_vals(self, vals: np.ndarray) -> np.ndarray:
        """[.., nnz_a] values -> [n_shards, .., e_max] padded slices."""
        lead = vals.shape[:-1]
        out = np.zeros((self._s,) + lead + (self._e_max,), vals.dtype)
        for i in range(self._s):
            e_lo, e_hi = int(self._e_bounds[i]), int(self._e_bounds[i + 1])
            out[i, ..., : e_hi - e_lo] = vals[..., e_lo:e_hi]
        return out

    # -- shard_map cores ---------------------------------------------------

    def _fn(self, kind: str):
        fn = self._fns.get(kind)
        if fn is None:
            fn = self._fns[kind] = shard_program(
                kind, mesh=self.mesh, axis=self.axis, backend=self.backend,
                interpret=self._interpret, group=self.group,
                a_max=self._a_max, p_max=self._p_max, a_shape=self.a_shape,
                b_shape=self.b_shape,
            )
        return fn

    # -- public surface (SpGEMMExecutor drop-in) ---------------------------

    def run(self, a_staged, b_staged) -> np.ndarray:
        """Staged (stacked/replicated) packed blocks -> packed C values.

        ``a_staged``/``b_staged`` come from :meth:`stage_a`/:meth:`stage_b`
        (the sharded plan's device staging hooks).
        """
        out = np.asarray(
            self._fn("kernel")(a_staged, b_staged, self._sched, self._gather)
        )
        return self._concat(out)

    def run_values(self, a_vals, b_vals) -> np.ndarray:
        """[nnz] value vectors -> packed C values; A row-sharded on the
        mesh, B replicated, rebind + kernel + assembly inside shard_map."""
        a_sh = jax.device_put(
            self._slice_a_vals(np.asarray(a_vals)), self._sep)
        b_d = jax.device_put(np.asarray(b_vals), self._rep)
        out = np.asarray(self._fn("run_values")(
            a_sh, b_d, self._a_scatter, self._b_scatter, self._sched,
            self._gather,
        ))
        return self._concat(out)

    def run_batch(self, a_vals, b_vals, *, rebind: bool) -> np.ndarray:
        """Batched values -> packed C values [batch, nnz_c]; the batch is
        folded into each shard's triple schedule (exact vmap semantics,
        like the unsharded batch path) inside the one shard_map call."""
        a_vals = np.asarray(a_vals)
        b_vals = np.asarray(b_vals)
        if rebind:
            a_sh = jax.device_put(self._slice_a_vals(a_vals), self._sep)
            b_d = jax.device_put(b_vals, self._rep)
            out = np.asarray(self._fn("batch_values")(
                a_sh, b_d, self._a_scatter, self._b_scatter, self._sched,
                self._gather,
            ))
        else:
            a_sh = jax.device_put(self._stack_a(a_vals), self._sep)
            b_d = jax.device_put(b_vals, self._rep)
            out = np.asarray(self._fn("batch_blocks")(
                a_sh, b_d, self._sched, self._gather
            ))
        return self._concat(out)

    # -- pipeline protocol (same surface as SpGEMMExecutor) ----------------

    def pipe_stage(self, a, b, *, mode: str):
        """Mesh layout + H2D + per-shard rebind dispatch; non-blocking.

        A values are host-sliced per shard and placed on the shard axis, B
        replicated; the rebind runs as its own ``shard_map`` program so it
        dispatches independently of the previous step's kernel."""
        if mode == "values":
            a_sh = jax.device_put(
                self._slice_a_vals(np.asarray(a)), self._sep)
            b_d = jax.device_put(np.asarray(b), self._rep)
            return self._fn("bind")(a_sh, b_d, self._a_scatter,
                                    self._b_scatter)
        if mode == "batch_values":
            a_sh = jax.device_put(
                self._slice_a_vals(np.asarray(a)), self._sep)
            b_d = jax.device_put(np.asarray(b), self._rep)
            return self._fn("bind_batch")(a_sh, b_d, self._a_scatter,
                                          self._b_scatter)
        if mode == "batch_blocks":
            return (
                jax.device_put(self._stack_a(np.asarray(a)), self._sep),
                jax.device_put(np.asarray(b), self._rep),
            )
        raise ValueError(f"unknown stage mode {mode!r}")  # pragma: no cover

    def pipe_kernel(self, staged, *, mode: str):
        """Per-shard kernel and assembly over staged blocks, one shard_map
        program (``run``'s, or ``run_batch``'s from blocks); returns the
        stacked packed C values without blocking."""
        a_bl, b_bl = staged
        kind = "kernel" if mode == "single" else "batch_blocks"
        return self._fn(kind)(a_bl, b_bl, self._sched, self._gather)

    def pipe_collect(self, packed, *, mode: str) -> np.ndarray:
        """Blocking D2H + per-shard pad trim + host concatenation."""
        return self._concat(np.asarray(packed))
