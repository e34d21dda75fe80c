"""Static lint over the Pallas kernel specs in
``repro.kernels.gustavson_spgemm``.

Two layers, both execution-free:

* :func:`lint_kernel_module` — an AST pass over the kernel module's
  source: the accumulation dtype must be fp32 everywhere (the
  ``preferred_element_type`` of the MXU dot and both ``out_shape``
  dtypes) and the dot's products full f32 (``precision=HIGHEST``), and
  the declared ``dimension_semantics`` must match what the
  verifier proves — the triple axis is ``"arbitrary"`` (panels are
  revisited by contiguous runs of steps, a sequential dependence), the
  batch axis ``"parallel"`` (distinct elements write disjoint
  ``n_panels + 1``-strided slot ranges; see
  :func:`repro.analysis.verify.check_batch_races`).
* :func:`lint_plan_kernel_specs` — given a built plan, evaluate the
  ``BlockSpec`` index maps over **every** grid coordinate with the actual
  prefetch arrays (pure numpy, mirroring the lambdas in
  ``spgemm_scheduled_impl`` / ``spgemm_scheduled_batch_impl``) and check
  each block index stays inside its operand, block shapes tile the
  operand shapes exactly, the grid sizes match the padded schedule, and
  the per-grid-step VMEM working set
  (:func:`repro.core.perfmodel.spgemm_grid_step_vmem`: one A block, one
  B block, one ``group*bm x bn`` output panel, double-buffered) fits the
  :data:`repro.core.perfmodel.TPU_VMEM_BYTES` budget — an oversized
  (tile, group) is a lint finding *before* any compile attempt — and the
  scalar-prefetch schedule fits :data:`repro.core.perfmodel.
  TPU_SMEM_BYTES` (``kernel.smem-schedule``: too many triples for one
  ``pallas_call``).

The module lint pins the *source*; the plan lint pins the *instance* —
together they are the static half of the "Pallas on every numeric path"
contract that the bitwise dispatch tests check dynamically.
"""
from __future__ import annotations

import ast
import inspect
from typing import List, Optional, Tuple

import numpy as np

from repro.analysis.verify import Finding, _bounds_check, _err

__all__ = ["lint_kernel_module", "lint_plan_kernel_specs"]

# The proven-safe semantics per grid (see module docstring).
EXPECTED_SEMANTICS = {
    "spgemm_scheduled_impl": ("arbitrary",),
    "spgemm_scheduled_batch_impl": ("parallel", "arbitrary"),
}


def _kernel_module_tree():
    from repro.kernels import gustavson_spgemm

    return ast.parse(inspect.getsource(gustavson_spgemm)), gustavson_spgemm


def _tuple_of_constants(node: ast.AST) -> Optional[Tuple]:
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if not isinstance(elt, ast.Constant):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


def _find_semantics(fn: ast.FunctionDef) -> Optional[Tuple]:
    """The ``dimension_semantics=`` tuple inside one impl function."""
    for node in ast.walk(fn):
        if isinstance(node, ast.keyword) and node.arg == "dimension_semantics":
            return _tuple_of_constants(node.value)
    return None


def _dotted(node: ast.AST) -> str:
    """'jnp.float32' for an Attribute/Name chain, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def lint_kernel_module() -> List[Finding]:
    """AST lint of ``repro.kernels.gustavson_spgemm`` (see module doc)."""
    findings: List[Finding] = []
    tree, _ = _kernel_module_tree()
    fns = {
        n.name: n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)
    }
    # dimension_semantics must match the race-freedom proof.
    for name, expect in EXPECTED_SEMANTICS.items():
        fn = fns.get(name)
        if fn is None:
            _err(findings, "kernel.semantics",
                 f"kernel impl {name} not found in module source")
            continue
        got = _find_semantics(fn)
        if got != expect:
            _err(findings, "kernel.semantics",
                 f"{name} declares dimension_semantics={got!r}, the "
                 f"verifier's race analysis supports exactly {expect!r}")
    # fp32 accumulation: the MXU dot's preferred_element_type ...
    kern = fns.get("_kernel")
    if kern is None:
        _err(findings, "kernel.accum-dtype", "_kernel not found")
    else:
        kw = {node.arg: _dotted(node.value) for node in ast.walk(kern)
              if isinstance(node, ast.keyword)
              and node.arg in ("preferred_element_type", "precision")}
        pref = kw.get("preferred_element_type")
        if pref != "jnp.float32":
            _err(findings, "kernel.accum-dtype",
                 f"_kernel dot preferred_element_type is {pref!r}, "
                 f"expected jnp.float32")
        # ... and full-f32 products: Mosaic's default precision for f32
        # operands is a single bf16 pass.
        if kw.get("precision") != "jax.lax.Precision.HIGHEST":
            _err(findings, "kernel.accum-dtype",
                 f"_kernel dot precision is {kw.get('precision')!r}, "
                 f"expected jax.lax.Precision.HIGHEST (f32 products)")
    # ... and both pallas_call out_shape dtypes.
    for name in EXPECTED_SEMANTICS:
        fn = fns.get(name)
        if fn is None:
            continue
        out_dtype = None
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and _dotted(node.func) == "jax.ShapeDtypeStruct"
                    and len(node.args) >= 2):
                out_dtype = _dotted(node.args[1])
        if out_dtype != "jnp.float32":
            _err(findings, "kernel.accum-dtype",
                 f"{name} out_shape dtype is {out_dtype!r}, expected "
                 f"jnp.float32 (fp32 accumulation)")
    return findings


def _pad_for(plan):
    from repro.kernels.gustavson_spgemm import pad_schedule_arrays

    s = plan.schedule
    return pad_schedule_arrays(
        s.a_slot, s.b_slot, s.panel, s.sub_row, s.start, s.n_panels
    )


def lint_plan_kernel_specs(plan, bsz: int = 2) -> List[Finding]:
    """Evaluate the kernel grids' BlockSpec index maps for ``plan`` over
    every grid coordinate (numpy mirror of the lambdas) and check
    in-boundedness + exact block tiling. ``bsz`` is the symbolic batch
    width for the batch-folded grid."""
    findings: List[Finding] = []
    nnzb_a = int(plan._a_shape[0]) if len(plan._a_shape) == 3 else 0
    nnzb_b = int(plan._b_shape[0]) if len(plan._b_shape) == 3 else 0
    if not plan.schedule.num_triples or not nnzb_a or not nnzb_b:
        return findings  # empty plan: no kernel is ever launched
    bm, bk = int(plan._a_shape[1]), int(plan._a_shape[2])
    bn = int(plan._b_shape[2])
    n_panels = plan.schedule.n_panels
    group = plan._group
    # VMEM budget: the per-grid-step resident set (A block + B block +
    # output panel, double-buffered by the Pallas pipeline) must fit
    # per-core VMEM. An oversized config fails at compile time at best
    # and silently spills at worst — catch it here, statically.
    from repro.core import perfmodel
    from repro.core.perfmodel import TPU_VMEM_BYTES, spgemm_grid_step_vmem

    dtype_bytes = int(np.dtype(np.float32).itemsize)
    step_bytes = spgemm_grid_step_vmem(
        tile=(bm, bk, bn), group=group, dtype_bytes=dtype_bytes
    )
    if step_bytes > TPU_VMEM_BYTES:
        _err(findings, "kernel.vmem-working-set",
             f"per-grid-step VMEM working set "
             f"{int(step_bytes)} B (tile=({bm}, {bk}, {bn}), "
             f"group={group}, double-buffered) exceeds the "
             f"{TPU_VMEM_BYTES} B per-core budget; shrink tile or group")
    # Block shapes must tile the packed operand arrays exactly: the specs
    # use (1, bm, bk) / (1, bk, bn) / (1, group*bm, bn) blocks, so the
    # trailing operand dims must equal the block dims (divisibility with
    # quotient 1 — anything else would silently stride into neighbors).
    if tuple(plan._a_shape[1:]) != (bm, bk):
        _err(findings, "kernel.block-shape",
             f"A blocks {plan._a_shape} not tiled by (1, {bm}, {bk})")
    if tuple(plan._b_shape[1:]) != (bk, bn):
        _err(findings, "kernel.block-shape",
             f"B blocks {plan._b_shape} not tiled by (1, {bk}, {bn})")
    a_slot, b_slot, panel, sub_row, start, t_pad = _pad_for(plan)
    # SMEM budget: the five int32 scalar-prefetch arrays of a call are
    # resident in scalar memory for its whole grid, so each slice of the
    # schedule the executor staged (one pallas_call each, per device on a
    # sharded plan, padded to the widest shard) may hold at most the
    # per-call budget.
    budget = perfmodel.SCHEDULE_TRIPLES_PER_CALL
    ex = plan._executor
    calls = ([] if ex is None
             else [int(piece[0].shape[-1]) for piece in ex._sched])
    if calls and max(calls) > budget:
        _err(findings, "kernel.smem-schedule",
             f"a kernel call runs {max(calls)} triples of the schedule "
             f"({len(calls)} calls), more than the {budget} whose "
             f"scalar-prefetch arrays one pallas_call's SMEM holds "
             f"({perfmodel.TPU_SMEM_BYTES} B less "
             f"{perfmodel.SCHEDULE_SMEM_MARGIN_BYTES} B, at "
             f"{perfmodel.SCHEDULE_SMEM_BYTES_PER_TRIPLE} B a triple); the "
             f"compiler refuses such a call")
    t = np.arange(t_pad)
    # Single grid (t_pad,): index maps t -> (a_s[t],·,·) etc., out panel
    # space n_panels + 1 (the appended dummy).
    _bounds_check(findings, "kernel.index-map.single", a_slot[t], 0,
                  nnzb_a, "a index")
    _bounds_check(findings, "kernel.index-map.single", b_slot[t], 0,
                  nnzb_b, "b index")
    _bounds_check(findings, "kernel.index-map.single", panel[t], 0,
                  n_panels + 1, "out panel index")
    _bounds_check(findings, "kernel.index-map.single",
                  sub_row[t] * bm + (bm - 1), 0, group * bm,
                  "panel row window")
    # Batch grid (bsz, t_pad): per-element offsets into the stacked
    # operands and the (n_panels + 1)-strided output.
    stride = n_panels + 1
    b = np.repeat(np.arange(bsz), t_pad)
    tt = np.tile(t, bsz)
    _bounds_check(findings, "kernel.index-map.batch",
                  b * nnzb_a + a_slot[tt], 0, bsz * nnzb_a, "a index")
    _bounds_check(findings, "kernel.index-map.batch",
                  b * nnzb_b + b_slot[tt], 0, bsz * nnzb_b, "b index")
    _bounds_check(findings, "kernel.index-map.batch",
                  b * stride + panel[tt], 0, bsz * stride,
                  "out panel index")
    return findings
