"""Static plan/schedule verifier: prove plan invariants without executing.

:func:`verify_plan` takes a built :class:`~repro.spgemm.plan.SpGEMMPlan`
(or :class:`~repro.spgemm.plan.ShardedSpGEMMPlan`) and checks, on the host
with numpy only:

1. **Schedule well-formedness** — every triple's slot/panel/sub-row index
   in bounds, start flags exactly marking the first triple of each panel,
   every panel visited in one contiguous run (the precondition for Pallas
   output revisiting), panel and C-block key arrays in the ascending order
   ``build_assembly_map`` requires.
2. **Dummy-pad-panel discipline** — the pad panel the kernel wrappers
   append (``n_panels`` in the single grid, per-element slot
   ``b * (n_panels + 1) + n_panels`` in the batch-folded grid, ``p_max``
   in the stacked shard schedules) is *write-only*: no assembly gather
   index ever reads it.
3. **Assembly coverage** — C's structural CSR is exact: indptr monotone
   and consistent, column indices in range and strictly ascending per
   row, every gather index in range and used **exactly once**, and the
   total nnz equal to the schedule's structural block pattern trimmed to
   the true output shape.
4. **Write-write race freedom** — for the batch-folded grid
   (:func:`~repro.kernels.gustavson_spgemm.spgemm_scheduled_batch_impl`)
   and the per-shard stacked schedules
   (:func:`~repro.core.schedule.stack_shard_schedules`), the scatter
   targets of distinct batch elements / shards are disjoint, and within
   one element each output slot's writers form a single contiguous run of
   grid steps. This is the proof obligation behind declaring the batch
   grid axis ``"parallel"``.
5. **Shard-partition exactness** (sharded plans) — shard group ranges are
   disjoint, contiguous, and cover all groups; triple/panel/A-slot spans
   tile the parent schedule; and re-deriving every shard from the bounds
   vector (:func:`~repro.core.schedule.shards_from_bounds`) reproduces
   the plan's shards **bitwise**, including each shard's rebased local
   schedule and its per-shard assembly slice.
6. **Compact-output exactness** (``output="compact"`` plans) — the
   compacted gather map is a well-formed canonical CSR, a *subset* of the
   block assembly's gather space with every slot read at most once (the
   exactly-once coverage proof carries over: block coverage + subset +
   no duplicates), and bitwise re-derivable from the block assembly and
   the compact pattern via
   :func:`~repro.core.schedule.build_compact_map`.
7. **Run-table coverage** (plans whose executor assembles run by run,
   :mod:`repro.kernels.assemble_runs`) — decoded as the kernel reads it,
   the run table writes every position of the active output map exactly
   once, each from the panel slot the map's gather names, and no run
   reads the dummy pad panel or past the end of a 128-lane panel row.

Plans also surface configuration-provenance warnings here: a persisted
tuned config whose symbolic facts no longer match the plan
(``apply_tuned_config`` fell back to defaults) is reported as a
``tuned.stale-config`` warning rather than silently ignored.

Everything here is value-independent; a verified plan can still compute
wrong numbers only if the kernels themselves are wrong — which is what
the bitwise dispatch tests (and :mod:`repro.analysis.kernel_lint`) cover.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple

import numpy as np

from repro.core.schedule import (
    LANES,
    AssemblyMap,
    AssemblyRuns,
    SpGEMMSchedule,
    build_assembly_map,
    build_compact_map,
    decode_assembly_runs,
    shards_from_bounds,
    shards_to_bounds,
)
from repro.kernels.gustavson_spgemm import pad_schedule_arrays

__all__ = [
    "Finding",
    "PlanVerificationError",
    "VerifyReport",
    "check_assembly_runs",
    "check_compact",
    "verify_plan",
]


@dataclasses.dataclass
class Finding:
    """One verifier finding. ``check`` is a dotted id (e.g.
    ``"schedule.panel-bounds"``); ``severity`` is ``"error"`` (invariant
    violated) or ``"warning"`` (suspicious but not provably wrong)."""

    check: str
    severity: str
    message: str

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return f"[{self.severity}] {self.check}: {self.message}"


@dataclasses.dataclass
class VerifyReport:
    """The result of one :func:`verify_plan` pass."""

    plan_kind: str  # "element" | "block"
    sharded: bool
    backend: str
    checks_run: List[str]
    findings: List[Finding]
    elapsed_s: float = 0.0

    @property
    def ok(self) -> bool:
        return not any(f.severity == "error" for f in self.findings)

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    def raise_if_failed(self) -> "VerifyReport":
        if not self.ok:
            raise PlanVerificationError(self)
        return self

    def summary(self) -> str:
        status = "ok" if self.ok else f"FAILED ({len(self.errors)} error(s))"
        lines = [
            f"verify_plan: {status} — {len(self.checks_run)} checks, "
            f"{self.elapsed_s * 1e3:.1f} ms "
            f"[{self.plan_kind}{', sharded' if self.sharded else ''}, "
            f"{self.backend}]"
        ]
        lines.extend(f"  {f}" for f in self.findings)
        return "\n".join(lines)


class PlanVerificationError(AssertionError):
    """A plan failed static verification. Carries the full report."""

    def __init__(self, report: VerifyReport):
        self.report = report
        super().__init__(report.summary())


def _err(findings: List[Finding], check: str, message: str) -> None:
    findings.append(Finding(check=check, severity="error", message=message))


def _bounds_check(
    findings: List[Finding], check: str, arr: np.ndarray, lo: int, hi: int,
    what: str,
) -> None:
    """Assert ``lo <= arr < hi`` elementwise, reporting the first offender."""
    arr = np.asarray(arr)
    if arr.size == 0:
        return
    bad = (arr < lo) | (arr >= hi)
    if bad.any():
        i = int(np.argmax(bad))
        _err(findings, check,
             f"{what}[{i}] = {int(arr.flat[i])} outside [{lo}, {hi})")


# ---------------------------------------------------------------------------
# Check families. Each takes the raw symbolic artifacts (never the plan's
# executor or any device array) and appends findings.
# ---------------------------------------------------------------------------


def check_schedule(
    schedule: SpGEMMSchedule,
    nnzb_a: int,
    nnzb_b: int,
    findings: List[Finding],
    label: str = "schedule",
) -> None:
    """Family 1: triple-schedule well-formedness."""
    t = schedule.num_triples
    arrays = {
        "a_slot": schedule.a_slot, "b_slot": schedule.b_slot,
        "panel": schedule.panel, "sub_row": schedule.sub_row,
        "start": schedule.start,
    }
    for name, arr in arrays.items():
        if np.asarray(arr).shape != (t,):
            _err(findings, f"{label}.lengths",
                 f"{name} has shape {np.asarray(arr).shape}, expected ({t},)")
            return  # everything downstream indexes by t
    n_panels = schedule.n_panels
    _bounds_check(findings, f"{label}.a-slot-bounds", schedule.a_slot,
                  0, max(nnzb_a, 1), "a_slot")
    _bounds_check(findings, f"{label}.b-slot-bounds", schedule.b_slot,
                  0, max(nnzb_b, 1), "b_slot")
    _bounds_check(findings, f"{label}.panel-bounds", schedule.panel,
                  0, max(n_panels, 1), "panel")
    _bounds_check(findings, f"{label}.sub-row-bounds", schedule.sub_row,
                  0, max(schedule.group, 1), "sub_row")
    start = np.asarray(schedule.start)
    if start.size and not np.isin(start, (0, 1)).all():
        _err(findings, f"{label}.start-domain",
             "start flags must be 0 or 1")
    if t:
        panel = np.asarray(schedule.panel)
        # Contiguous panel runs: each panel id appears in exactly one run.
        # (This is what lets the Pallas out BlockSpec revisit the panel
        # accumulator in VMEM and write it back exactly once.)
        run_first = np.empty(t, dtype=bool)
        run_first[0] = True
        run_first[1:] = panel[1:] != panel[:-1]
        run_panels = panel[run_first]
        uniq, counts = np.unique(run_panels, return_counts=True)
        if (counts > 1).any():
            p = int(uniq[np.argmax(counts > 1)])
            _err(findings, f"{label}.panel-contiguity",
                 f"panel {p} is visited in {int(counts.max())} separate "
                 f"runs; each output panel must be one contiguous run")
        elif uniq.shape[0] != n_panels:
            _err(findings, f"{label}.panel-coverage",
                 f"{uniq.shape[0]} of {n_panels} panels receive triples; "
                 f"build_spgemm_schedule never emits empty panels")
        # start == 1 exactly on the first triple of each panel run.
        if not np.array_equal(start.astype(bool), run_first):
            i = int(np.argmax(start.astype(bool) != run_first))
            _err(findings, f"{label}.start-flags",
                 f"start[{i}] = {int(start[i])} but triple {i} is "
                 f"{'the first' if run_first[i] else 'not the first'} of "
                 f"its panel run")
    # Panel keys ascending (the searchsorted precondition in
    # build_assembly_map) and in range.
    _bounds_check(findings, f"{label}.panel-group-bounds",
                  schedule.panel_group, 0,
                  max(-(-schedule.grid_m // max(schedule.group, 1)), 1),
                  "panel_group")
    _bounds_check(findings, f"{label}.panel-bcol-bounds",
                  schedule.panel_bcol, 0, max(schedule.grid_n, 1),
                  "panel_bcol")
    pkey = (schedule.panel_group.astype(np.int64) * schedule.grid_n
            + schedule.panel_bcol)
    if pkey.size and (np.diff(pkey) <= 0).any():
        _err(findings, f"{label}.panel-order",
             "panel (group, bcol) keys are not strictly ascending")
    # C block pattern sorted and in range.
    _bounds_check(findings, f"{label}.c-brow-bounds", schedule.c_brow,
                  0, max(schedule.grid_m, 1), "c_brow")
    _bounds_check(findings, f"{label}.c-bcol-bounds", schedule.c_bcol,
                  0, max(schedule.grid_n, 1), "c_bcol")
    ckey = (schedule.c_brow.astype(np.int64) * schedule.grid_n
            + schedule.c_bcol)
    if ckey.size and (np.diff(ckey) <= 0).any():
        _err(findings, f"{label}.c-block-order",
             "C block (brow, bcol) keys are not strictly ascending")


def check_assembly(
    schedule: SpGEMMSchedule,
    assembly: AssemblyMap,
    block_shape: Tuple[int, int],
    findings: List[Finding],
    label: str = "assembly",
) -> None:
    """Families 2+3: pad panel never gathered; structural coverage exact."""
    bm, bn = block_shape
    m, n = assembly.shape
    g = schedule.group
    indptr = np.asarray(assembly.indptr)
    indices = np.asarray(assembly.indices)
    gather = np.asarray(assembly.gather)
    nnz = assembly.nnz
    if indptr.shape != (m + 1,):
        _err(findings, f"{label}.indptr-shape",
             f"indptr shape {indptr.shape}, expected ({m + 1},)")
        return
    if indptr.size and int(indptr[0]) != 0:
        _err(findings, f"{label}.indptr-origin",
             f"indptr[0] = {int(indptr[0])}, expected 0")
    if (np.diff(indptr) < 0).any():
        i = int(np.argmax(np.diff(indptr) < 0))
        _err(findings, f"{label}.indptr-monotone",
             f"indptr decreases at row {i}")
    elif int(indptr[-1]) != nnz:
        _err(findings, f"{label}.indptr-total",
             f"indptr[-1] = {int(indptr[-1])} != nnz {nnz}")
    if gather.shape != (nnz,):
        _err(findings, f"{label}.gather-shape",
             f"gather shape {gather.shape}, expected ({nnz},)")
        return
    _bounds_check(findings, f"{label}.indices-bounds", indices, 0,
                  max(n, 1), "indices")
    # Columns strictly ascending within each row (canonical CSR — results
    # share these arrays, so duplicates would silently alias C entries).
    if nnz and (np.diff(indptr) >= 0).all() and int(indptr[-1]) == nnz:
        row_of = np.repeat(np.arange(m, dtype=np.int64), np.diff(indptr))
        key = row_of * (int(n) + 1) + indices.astype(np.int64)
        if (np.diff(key) <= 0).any():
            i = int(np.argmax(np.diff(key) <= 0))
            _err(findings, f"{label}.column-order",
                 f"columns not strictly ascending within row "
                 f"{int(row_of[i])} (nnz position {i})")
    # Pad-panel discipline: the flat gather space is the *real* panels
    # only. Any index >= n_panels*g*bm*bn reads the dummy pad panel the
    # kernel wrapper appends (single grid) — or, in the batch-folded grid
    # with per-element stride n_panels+1, another element's panels.
    flat = schedule.n_panels * g * bm * bn
    bad = (gather < 0) | (gather >= max(flat, 1))
    if bad.any():
        i = int(np.argmax(bad))
        _err(findings, f"{label}.pad-panel-read",
             f"gather[{i}] = {int(gather[i])} outside the real panel "
             f"space [0, {flat}): it reads the write-only dummy pad panel")
    elif nnz:
        # Exactly-once: every structural C nnz has a distinct source slot.
        uniq = np.unique(gather)
        if uniq.shape[0] != nnz:
            _err(findings, f"{label}.gather-duplicate",
                 f"{nnz - uniq.shape[0]} duplicated gather index(es): two "
                 f"C entries read the same panel slot")
    # Structural coverage: nnz must equal the schedule's C block pattern
    # trimmed to the true shape (ceil-padded edge blocks overhang).
    rows_in = np.clip(m - schedule.c_brow.astype(np.int64) * bm, 0, bm)
    cols_in = np.clip(n - schedule.c_bcol.astype(np.int64) * bn, 0, bn)
    expect = int((rows_in * cols_in).sum())
    if nnz != expect:
        _err(findings, f"{label}.coverage",
             f"assembly holds {nnz} structural nnz, schedule implies "
             f"{expect}")


def check_batch_races(
    schedule: SpGEMMSchedule,
    findings: List[Finding],
    bsz: int = 2,
    label: str = "races.batch",
) -> None:
    """Family 4 (batch-folded grid): prove single-writer per output slot.

    Reconstructs the padded schedule exactly as
    :func:`~repro.kernels.gustavson_spgemm.pad_schedule_arrays` does and
    evaluates the batch grid's out index map
    ``slot = b * (n_panels + 1) + panel[t]`` over every grid step. The
    batch axis is race-free — and therefore safely declared
    ``"parallel"`` — iff slots of distinct ``b`` never collide, which
    holds exactly when every padded panel id sits in ``[0, n_panels]``.
    The triple axis must stay ``"arbitrary"``: within one element, a
    panel slot *is* revisited, legally, by one contiguous run of steps.
    """
    n_panels = schedule.n_panels
    a_slot, b_slot, panel, sub_row, start, t_pad = pad_schedule_arrays(
        schedule.a_slot, schedule.b_slot, schedule.panel,
        schedule.sub_row, schedule.start, n_panels,
    )
    stride = n_panels + 1
    _bounds_check(findings, f"{label}.padded-panel-bounds", panel, 0,
                  stride, "padded panel")
    if findings and findings[-1].check == f"{label}.padded-panel-bounds":
        return
    # Explicit slot map over the full (bsz, t_pad) grid: distinct batch
    # elements must write disjoint slot sets, and one slot's writers must
    # be contiguous in t (the revisit-run condition the single-writer
    # argument reduces to under sequential-innermost iteration).
    b_of = np.repeat(np.arange(bsz, dtype=np.int64), t_pad)
    t_of = np.tile(np.arange(t_pad, dtype=np.int64), bsz)
    slot = b_of * stride + panel[t_of].astype(np.int64)
    order = np.lexsort((t_of, slot))
    slot_s, b_s, t_s = slot[order], b_of[order], t_of[order]
    same = np.zeros(slot_s.shape[0], dtype=bool)
    same[1:] = slot_s[1:] == slot_s[:-1]
    if same.any():
        cross = same & (b_s != np.roll(b_s, 1))
        if cross.any():
            i = int(np.argmax(cross))
            _err(findings, f"{label}.cross-element",
                 f"output slot {int(slot_s[i])} written by batch elements "
                 f"{int(b_s[i - 1])} and {int(b_s[i])}: the batch axis is "
                 f"NOT race-free")
        gap = same & (t_s != np.roll(t_s, 1) + 1)
        # Pad triples all target one dummy slot per element with start=1
        # (each write begins by zeroing), so non-contiguity there is safe;
        # real panels must still be single contiguous runs.
        real = (slot_s % stride) < n_panels
        if (gap & real).any():
            i = int(np.argmax(gap & real))
            _err(findings, f"{label}.revisit-gap",
                 f"slot {int(slot_s[i])} revisited non-contiguously at "
                 f"grid steps t={int(t_s[i - 1])} and t={int(t_s[i])}")


def check_stacked_shards(
    shards,
    findings: List[Finding],
    label: str = "races.shards",
) -> None:
    """Family 4 (stacked shard schedules): the ``[n_shards, t_max]``
    constants from :func:`~repro.core.schedule.stack_shard_schedules` keep
    each shard's writes inside its own ``p_max + 1``-panel buffer, with
    pads confined to the write-only dummy panel ``p_max``."""
    from repro.core.schedule import stack_shard_schedules

    if not shards:
        return
    t_max = max(1, max(s.num_triples for s in shards))
    p_max = max(1, max(s.n_panels for s in shards))
    _, _, panel, _, start = stack_shard_schedules(shards, t_max, p_max)
    for i, sh in enumerate(shards):
        t = sh.num_triples
        row = panel[i]
        if (row[t:] != p_max).any():
            _err(findings, f"{label}.pad-target",
                 f"shard {i}: pad triples target panel(s) other than the "
                 f"dummy {p_max}")
        if (start[i, t:] != 1).any():
            _err(findings, f"{label}.pad-start",
                 f"shard {i}: pad triples missing start=1 (accumulator "
                 f"would carry garbage)")
        _bounds_check(findings, f"{label}.real-panel-bounds", row[:t], 0,
                      max(sh.n_panels, 1), f"shard {i} panel")
        # Shard-local gathers must never read past the shard's own real
        # panels (the stacked buffer is p_max+1 panels; slots in
        # [n_panels, p_max] are scratch, p_max the shared dummy).


def check_shard_partition(
    plan,
    findings: List[Finding],
    label: str = "shards",
) -> None:
    """Family 5: partition exactness + bitwise reconstruction."""
    shards = plan._shards
    schedule: SpGEMMSchedule = plan.schedule
    if not shards:
        return
    g = schedule.group
    n_groups = -(-schedule.grid_m // g) if schedule.grid_m else 0
    # Disjoint + contiguous + covering group ranges.
    if shards[0].group_lo != 0:
        _err(findings, f"{label}.origin",
             f"first shard starts at group {shards[0].group_lo}, not 0")
    for i in range(len(shards) - 1):
        if shards[i].group_hi != shards[i + 1].group_lo:
            _err(findings, f"{label}.contiguity",
                 f"shard {i} ends at group {shards[i].group_hi} but shard "
                 f"{i + 1} starts at {shards[i + 1].group_lo}: ranges "
                 f"must tile [0, n_groups) disjointly")
    if schedule.num_triples and shards[-1].group_hi != n_groups:
        _err(findings, f"{label}.coverage",
             f"shards cover [0, {shards[-1].group_hi}) but the schedule "
             f"has exactly {n_groups} groups (under- and over-coverage "
             f"are both partition violations)")
    # Triple/panel/A spans tile the parent arrays.
    for name, lo_f, hi_f, total in (
        ("triple", "triple_lo", "triple_hi", schedule.num_triples),
        ("panel", "panel_lo", "panel_hi", schedule.n_panels),
    ):
        pos = 0
        for i, sh in enumerate(shards):
            lo, hi = getattr(sh, lo_f), getattr(sh, hi_f)
            if lo != pos or hi < lo:
                _err(findings, f"{label}.{name}-span",
                     f"shard {i} {name} span [{lo}, {hi}) does not "
                     f"continue at {pos}")
                return
            pos = hi
        if pos != total:
            _err(findings, f"{label}.{name}-span",
                 f"shard {name} spans cover {pos} of {total}")
    # Bitwise reconstruction from the serialized bounds vector — the
    # exact round trip persistence relies on.
    bounds = shards_to_bounds(shards)
    try:
        rebuilt = shards_from_bounds(schedule, bounds)
    except ValueError as e:
        _err(findings, f"{label}.bounds", f"bounds rejected: {e}")
        return
    for i, (sh, rb) in enumerate(zip(shards, rebuilt)):
        for f in ("group_lo", "group_hi", "triple_lo", "triple_hi",
                  "panel_lo", "panel_hi", "a_lo", "a_hi"):
            if getattr(sh, f) != getattr(rb, f):
                _err(findings, f"{label}.rebase",
                     f"shard {i}.{f}: stored {getattr(sh, f)} != "
                     f"rebuilt {getattr(rb, f)}")
        for f in ("a_slot", "b_slot", "panel", "sub_row", "start",
                  "panel_group", "panel_bcol", "c_brow", "c_bcol"):
            a = np.asarray(getattr(sh.schedule, f))
            b = np.asarray(getattr(rb.schedule, f))
            if a.shape != b.shape or a.dtype != b.dtype \
                    or not np.array_equal(a, b):
                _err(findings, f"{label}.rebase",
                     f"shard {i} local schedule field {f!r} differs from "
                     f"its bitwise reconstruction")
                break
    # Per-shard assembly slices concatenate to the plan assembly.
    asms = plan._shard_assemblies
    if asms:
        if sum(a.nnz for a in asms) != plan.assembly.nnz:
            _err(findings, f"{label}.assembly-cover",
                 f"shard assemblies hold "
                 f"{sum(a.nnz for a in asms)} nnz, plan assembly "
                 f"{plan.assembly.nnz}")
        else:
            cat = np.concatenate(
                [np.asarray(a.indices) for a in asms]
            ) if plan.assembly.nnz else np.asarray(plan.assembly.indices)
            if not np.array_equal(cat, np.asarray(plan.assembly.indices)):
                _err(findings, f"{label}.assembly-concat",
                     "concatenated shard CSR columns differ from the "
                     "plan-wide assembly")
        for i, (sh, asm) in enumerate(zip(shards, asms)):
            flat = sh.n_panels * g * plan._bm * plan._bn
            gth = np.asarray(asm.gather)
            if gth.size and (int(gth.max()) >= max(flat, 1)
                             or int(gth.min()) < 0):
                _err(findings, f"{label}.gather-bounds",
                     f"shard {i} gather reads outside its {sh.n_panels} "
                     f"real panels (flat space {flat})")


def check_compact(
    plan,
    findings: List[Finding],
    label: str = "compact",
) -> None:
    """Family 6: the compacted nnz-exact output map.

    The compact map reuses the exactly-once coverage proof of the block
    assembly (family 3): it must be a canonical CSR whose gather is a
    duplicate-free *subset* of the block gather. Combined with the block
    map's pad-panel and exactly-once checks, that proves every compacted
    C element reads exactly one kernel output slot and no slot feeds two
    elements.
    """
    assembly: AssemblyMap = plan.assembly
    compact: AssemblyMap = plan.compact
    m, n = compact.shape
    indptr = np.asarray(compact.indptr)
    indices = np.asarray(compact.indices)
    gather = np.asarray(compact.gather)
    nnz = compact.nnz
    if tuple(compact.shape) != tuple(assembly.shape):
        _err(findings, f"{label}.shape",
             f"compact shape {compact.shape} != assembly {assembly.shape}")
        return
    if indptr.shape != (m + 1,):
        _err(findings, f"{label}.indptr-shape",
             f"indptr shape {indptr.shape}, expected ({m + 1},)")
        return
    if indptr.size and int(indptr[0]) != 0:
        _err(findings, f"{label}.indptr-origin",
             f"indptr[0] = {int(indptr[0])}, expected 0")
    if (np.diff(indptr) < 0).any():
        i = int(np.argmax(np.diff(indptr) < 0))
        _err(findings, f"{label}.indptr-monotone",
             f"indptr decreases at row {i}")
    elif int(indptr[-1]) != nnz:
        _err(findings, f"{label}.indptr-total",
             f"indptr[-1] = {int(indptr[-1])} != nnz {nnz}")
    if gather.shape != (nnz,):
        _err(findings, f"{label}.gather-shape",
             f"gather shape {gather.shape}, expected ({nnz},)")
        return
    _bounds_check(findings, f"{label}.indices-bounds", indices, 0,
                  max(n, 1), "indices")
    if nnz > assembly.nnz:
        _err(findings, f"{label}.size",
             f"compact map holds {nnz} nnz, more than the {assembly.nnz} "
             f"block-structural slots it selects from")
    if nnz and (np.diff(indptr) >= 0).all() and int(indptr[-1]) == nnz:
        row_of = np.repeat(np.arange(m, dtype=np.int64), np.diff(indptr))
        key = row_of * (int(n) + 1) + indices.astype(np.int64)
        if (np.diff(key) <= 0).any():
            i = int(np.argmax(np.diff(key) <= 0))
            _err(findings, f"{label}.column-order",
                 f"columns not strictly ascending within row "
                 f"{int(row_of[i])} (nnz position {i})")
    if nnz:
        # Exactly-once, inherited: subset of the block gather space...
        if not np.isin(gather, np.asarray(assembly.gather)).all():
            _err(findings, f"{label}.subset",
                 "compact gather reads slot(s) outside the block "
                 "assembly's gather space")
        # ...with no slot feeding two compacted elements.
        uniq = np.unique(gather)
        if uniq.shape[0] != nnz:
            _err(findings, f"{label}.gather-duplicate",
                 f"{nnz - uniq.shape[0]} duplicated gather index(es): two "
                 f"compacted C entries read the same panel slot")
    # Bitwise re-derivation from the block assembly + the compact pattern
    # itself — the compact analogue of assembly.rebuild.
    if not any(f.severity == "error" and f.check.startswith(label)
               for f in findings):
        rows = np.repeat(np.arange(m, dtype=np.int64), np.diff(indptr))
        try:
            fresh = build_compact_map(assembly, rows, indices)
        except Exception as e:  # noqa: BLE001 - any failure is a finding
            _err(findings, f"{label}.rebuild",
                 f"compact map not re-derivable from the block assembly: "
                 f"{type(e).__name__}: {e}")
            return
        for f in ("gather", "indptr", "indices"):
            a = np.asarray(getattr(compact, f))
            b = np.asarray(getattr(fresh, f))
            if a.shape != b.shape or not np.array_equal(a, b):
                _err(findings, f"{label}.rebuild",
                     f"stored compact {f!r} differs from its re-derived "
                     f"map")
                return
    # Sharded plans slice the compact map per shard; the slices must
    # exactly tile it (the executor's packed-value layout depends on it).
    shard_compacts = getattr(plan, "_shard_compacts", None)
    if shard_compacts:
        if sum(a.nnz for a in shard_compacts) != nnz:
            _err(findings, f"{label}.shard-cover",
                 f"shard compact maps hold "
                 f"{sum(a.nnz for a in shard_compacts)} nnz, plan compact "
                 f"{nnz}")
        elif nnz:
            cat = np.concatenate(
                [np.asarray(a.indices) for a in shard_compacts]
            )
            if not np.array_equal(cat, indices):
                _err(findings, f"{label}.shard-concat",
                     "concatenated shard compact columns differ from the "
                     "plan-wide compact map")


def check_assembly_runs(
    runs: AssemblyRuns,
    gather: np.ndarray,
    findings: List[Finding],
    label: str = "runs",
) -> None:
    """Family 7: the run table against the map it was built from. Every
    run is decoded as the kernel reads it; the values they place must
    cover ``[0, nnz)`` exactly once, each from ``gather``'s slot, and no
    run may read the dummy pad panel or wrap past a lane row's end (the
    kernel's lane rotation would bring that row's first lanes)."""
    gather = np.asarray(gather)
    nnz = int(gather.shape[0])
    if runs.nnz != nnz:
        _err(findings, f"{label}.size",
             f"run table holds {runs.nnz} values, the map {nnz}")
        return
    d = decode_assembly_runs(runs)
    length = d["length"]
    if (length < 1).any() or (d["lane"] + length > LANES).any():
        i = int(np.argmax((length < 1) | (d["lane"] + length > LANES)))
        _err(findings, f"{label}.lane-row",
             f"run {i} ({int(length[i])} values from lane "
             f"{int(d['lane'][i])}) is not inside one 128-lane row")
        return
    if (d["panel"] >= runs.n_panels).any() \
            or (d["row"] >= runs.lane_rows).any():
        i = int(np.argmax((d["panel"] >= runs.n_panels)
                          | (d["row"] >= runs.lane_rows)))
        _err(findings, f"{label}.pad-panel-read",
             f"run {i} reads panel {int(d['panel'][i])} row "
             f"{int(d['row'][i])}, outside the {runs.n_panels} real panels")
        return
    per_block = runs.block_rows * LANES
    last = d["dest"] + length - 1
    if (d["dest"] < 0).any() or (last >= nnz).any() \
            or (d["dest"] // per_block != last // per_block).any():
        _err(findings, f"{label}.bounds",
             "a run writes outside its output block or past the map's end")
        return
    step = (np.arange(int(length.sum()))
            - np.repeat(np.cumsum(length) - length, length))
    dest = np.repeat(d["dest"], length) + step
    times = np.bincount(dest, minlength=nnz)
    if (times == 0).any():
        _err(findings, f"{label}.dropped",
             f"{int((times == 0).sum())} output value(s) no run writes, "
             f"the first at {int(np.argmax(times == 0))}")
    if (times > 1).any():
        _err(findings, f"{label}.duplicate",
             f"{int((times > 1).sum())} output value(s) written by more "
             f"than one run, the first at {int(np.argmax(times > 1))}")
    src = np.repeat(d["src"], length) + step
    wrong = src != gather[dest]
    if wrong.any():
        i = int(np.argmax(wrong))
        _err(findings, f"{label}.source",
             f"output value {int(dest[i])} is placed from panel slot "
             f"{int(src[i])}, the map gathers {int(gather[dest[i]])}")


def _rebuild_cross_check(plan, findings: List[Finding]) -> None:
    """Re-derive the assembly map from the plan's own schedule and compare
    bitwise — the strongest corruption detector for persisted artifacts
    (a digest-valid file whose arrays were *consistently* rewritten still
    cannot match an independent re-derivation)."""
    try:
        fresh = build_assembly_map(
            plan.schedule, (plan._bm, plan._bn), (plan._m, plan._n)
        )
    except Exception as e:  # noqa: BLE001 - any failure is a finding
        _err(findings, "assembly.rebuild",
             f"assembly map not re-derivable from the schedule: "
             f"{type(e).__name__}: {e}")
        return
    for f in ("gather", "indptr", "indices"):
        a = np.asarray(getattr(plan.assembly, f))
        b = np.asarray(getattr(fresh, f))
        if a.shape != b.shape or not np.array_equal(a, b):
            _err(findings, "assembly.rebuild",
                 f"stored assembly {f!r} differs from the schedule's "
                 f"re-derived map")
            return
    if tuple(plan.assembly.shape) != tuple(fresh.shape):
        _err(findings, "assembly.rebuild",
             f"stored assembly shape {plan.assembly.shape} != re-derived "
             f"{fresh.shape}")


def verify_plan(
    plan,
    *,
    batch_sizes: Tuple[int, ...] = (2, 3),
    rebuild_check: bool = True,
) -> VerifyReport:
    """Statically verify one plan. Returns a :class:`VerifyReport`;
    ``report.raise_if_failed()`` raises :class:`PlanVerificationError`.

    ``batch_sizes`` are the symbolic batch widths the race check runs at
    (disjointness is stride-structural, so two small sizes suffice).
    ``rebuild_check=False`` skips the full assembly re-derivation (the
    one check whose cost is O(symbolic build); everything else is a few
    linear passes over the schedule arrays).
    """
    t0 = time.perf_counter()
    findings: List[Finding] = []
    checks = [
        "schedule", "assembly", "races.batch",
    ]
    schedule: SpGEMMSchedule = plan.schedule
    nnzb_a = int(plan._a_shape[0]) if len(plan._a_shape) == 3 else 0
    nnzb_b = int(plan._b_shape[0]) if len(plan._b_shape) == 3 else 0
    check_schedule(schedule, nnzb_a, nnzb_b, findings)
    check_assembly(schedule, plan.assembly, (plan._bm, plan._bn), findings)
    for bsz in batch_sizes:
        check_batch_races(schedule, findings, bsz=bsz)
    if getattr(plan, "compact", None) is not None:
        checks.append("compact")
        check_compact(plan, findings)
    runs = getattr(getattr(plan, "_executor", None), "assembly_runs", None)
    if runs is not None:
        checks.append("runs")
        check_assembly_runs(runs, plan._active().gather, findings)
    if rebuild_check:
        checks.append("assembly.rebuild")
        _rebuild_cross_check(plan, findings)
    # Configuration provenance: a tuned config that no longer matches the
    # plan's symbolic facts was ignored at apply time — surface it.
    stale = getattr(plan, "_stale_tuned", None)
    if stale is not None:
        checks.append("tuned")
        findings.append(Finding(
            check="tuned.stale-config",
            severity="warning",
            message=(
                f"persisted tuned config {stale!r} no longer matches the "
                f"plan's symbolic facts; it was ignored and the plan runs "
                f"with config_source="
                f"{plan.report.config_source!r} (re-run the autotuner to "
                f"refresh the sidecar)"
            ),
        ))
    sharded = hasattr(plan, "_shards") and getattr(plan, "n_shards", 0) > 0
    if sharded:
        checks += ["shards", "races.shards"]
        check_shard_partition(plan, findings)
        check_stacked_shards(plan._shards, findings)
        for i, sh in enumerate(plan._shards):
            if sh.num_triples:
                check_schedule(
                    sh.schedule, sh.a_hi - sh.a_lo, nnzb_b, findings,
                    label=f"shard{i}.schedule",
                )
    element = getattr(plan, "_a_scatter", None) is not None \
        and getattr(plan, "_b_scatter", None) is not None
    return VerifyReport(
        plan_kind="element" if element else "block",
        sharded=bool(sharded),
        backend=getattr(plan, "backend", "?"),
        checks_run=checks,
        findings=findings,
        elapsed_s=time.perf_counter() - t0,
    )
