"""The served path's instrumentation: host spans on the profiler's clock
and named scopes on the device programs.

A small HPCG operator squared (``bench/matrices/hpcg27.py``), planned in
interpret mode on CPU, is traced under ``jax.profiler``; the real
``.xplane.pb`` is read back with :func:`bench.spans.load`. Each product's
spans must nest as ``repro.spgemm.plan`` describes and share one ``step``.
The compiled programs of every path must carry the three stage scopes in
their ``op_name`` metadata.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

from bench import spans, trace  # noqa: E402
from bench.matrices import hpcg27  # noqa: E402
from repro.core import perfmodel  # noqa: E402
from repro.sparse.formats import COO  # noqa: E402
from repro.spgemm import PlanCache, spgemm_plan  # noqa: E402
from repro.spgemm.executor import (  # noqa: E402
    bind_core,
    numeric_core,
    numeric_core_batch,
    numeric_core_values,
    shard_program,
)

SCOPES = ("spgemm.bind", "spgemm.kernel", "spgemm.assemble")


@pytest.fixture(scope="module")
def plan():
    row, col, shape = hpcg27.pattern({"grid": [4, 4, 8]})
    vals = np.random.default_rng(0).standard_normal(row.size).astype(np.float32)
    a = COO(row, col, vals, shape)
    return spgemm_plan(a, a, tile=16, group=2, backend="pallas_interpret",
                       output="compact", cache=PlanCache())


def _values(plan, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(plan.report.nnz_a).astype(np.float32),
            rng.standard_normal(plan.report.nnz_b).astype(np.float32))


def _traced(tmp_path, fn):
    """The program's spans while ``fn`` runs under the profiler."""
    fn()  # compile outside the trace
    trace.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            fn()
    finally:
        trace.stop()
    return spans.load(trace.newest_xplane(str(tmp_path)))["spans"]


def _inside(child, parent):
    return parent[1] <= child[1] and child[1] + child[2] <= parent[1] + parent[2]


def _one(found, name):
    hits = [sp for sp in found if sp[0] == name]
    assert len(hits) == 1, (name, [sp[0] for sp in found])
    return hits[0]


def _check_collect(found, collect):
    """``spgemm.collect`` holds ``spgemm.wait`` and then ``spgemm.d2h``."""
    wait = [sp for sp in found if sp[0] == "spgemm.wait" and _inside(sp, collect)]
    d2h = [sp for sp in found if sp[0] == "spgemm.d2h" and _inside(sp, collect)]
    assert len(wait) == len(d2h) >= 1
    for w, d in zip(wait, d2h):
        assert w[1] + w[2] <= d[1]
    return d2h


def test_execute_spans_nest_and_share_the_step(plan, tmp_path):
    a_vals, b_vals = _values(plan, 1)
    found = _traced(tmp_path, lambda: plan.execute(a_vals, b_vals))
    step = plan.report.executes
    assert {sp[3]["step"] for sp in found} == {step}
    top = _one(found, "spgemm.execute")
    assert all(_inside(sp, top) for sp in found)
    rebinds = [sp for sp in found if sp[0] == "spgemm.rebind"]
    slots = [int(np.prod(plan._a_shape)), int(np.prod(plan._b_shape))]
    assert [(sp[3]["operand"], sp[3]["values"], sp[3]["slots"]) for sp in rebinds] == [
        ("a", plan.report.nnz_a, slots[0]), ("b", plan.report.nnz_b, slots[1])]
    dispatch = _one(found, "spgemm.dispatch")
    nnz = plan.report.nnz_a + plan.report.nnz_b
    assert dispatch[3]["h2d_bytes"] == 4 * nnz
    assert (dispatch[3]["bind_values"], dispatch[3]["bind_slots"]) == (nnz, sum(slots))
    collect = _one(found, "spgemm.collect")
    assert rebinds[-1][1] + rebinds[-1][2] <= dispatch[1]
    assert dispatch[1] + dispatch[2] <= collect[1]
    (d2h,) = _check_collect(found, collect)
    assert d2h[3]["d2h_bytes"] == 4 * plan.compact.nnz


def test_block_staged_execute_has_no_host_rebind(plan, tmp_path):
    found = _traced(tmp_path, plan.execute)
    assert "spgemm.rebind" not in {sp[0] for sp in found}
    dispatch = _one(found, "spgemm.dispatch")
    assert dispatch[3]["bind_values"] == dispatch[3]["bind_slots"] == 0
    assert dispatch[3]["h2d_bytes"] == 0  # staged by the call before the trace
    assert {sp[3]["step"] for sp in found} == {plan.report.executes}


def test_staged_blocks_count_in_the_dispatch(plan, tmp_path):
    """Fresh A values alone rebind A on the host and stage its blocks
    inside ``spgemm.dispatch``, which counts their bytes."""
    a_vals, _ = _values(plan, 4)
    found = _traced(tmp_path, lambda: plan.execute(a_vals))
    (rebind,) = [sp for sp in found if sp[0] == "spgemm.rebind"]
    assert rebind[3]["operand"] == "a"
    dispatch = _one(found, "spgemm.dispatch")
    assert dispatch[3]["h2d_bytes"] == 4 * int(np.prod(plan._a_shape))
    assert dispatch[3]["bind_values"] == dispatch[3]["bind_slots"] == 0
    assert rebind[1] + rebind[2] <= dispatch[1]


def test_sharded_execute_runs_in_one_span(tmp_path):
    """A sharded plan's executor blocks through the D2H: ``spgemm.run``
    covers that call, and ``spgemm.collect`` holds only the CSR wrap; its
    pipeline dispatches without blocking, as the single-device one does."""
    row, col, shape = hpcg27.pattern({"grid": [4, 4, 4]})
    vals = np.random.default_rng(0).standard_normal(row.size).astype(np.float32)
    a = COO(row, col, vals, shape)
    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    sharded = spgemm_plan(a, a, tile=16, group=2, backend="pallas_interpret",
                          output="compact", cache=PlanCache(), mesh=mesh)
    a_vals, b_vals = _values(sharded, 2)
    found = _traced(tmp_path, lambda: sharded.execute(a_vals, b_vals))
    names = {sp[0] for sp in found}
    assert {"spgemm.run", "spgemm.collect"} <= names
    assert not names & {"spgemm.dispatch", "spgemm.wait", "spgemm.d2h"}
    run = _one(found, "spgemm.run")
    assert run[3]["bind_values"] == sharded.report.nnz_a + sharded.report.nnz_b
    assert run[1] + run[2] <= _one(found, "spgemm.collect")[1]
    found = _traced(tmp_path,
                    lambda: list(sharded.execute_stream([(a_vals, b_vals)], depth=1)))
    assert "spgemm.run" not in {sp[0] for sp in found}
    _one(found, "spgemm.dispatch")
    _check_collect(found, _one(found, "spgemm.collect"))


def test_stream_spans_per_pipeline_index(plan, tmp_path):
    sets = [_values(plan, s) for s in range(3)]
    found = _traced(tmp_path, lambda: list(plan.execute_stream(sets, depth=2)))
    assert "spgemm.rebind" not in {sp[0] for sp in found}
    for step in range(3):
        mine = [sp for sp in found if sp[3]["step"] == step]
        submit = _one(mine, "spgemm.submit")
        dispatch = _one(mine, "spgemm.dispatch")
        assert _inside(dispatch, submit)
        assert dispatch[3]["bind_values"] == plan.report.nnz_a + plan.report.nnz_b
        collect = _one(mine, "spgemm.collect")
        assert submit[1] + submit[2] <= collect[1]
        _check_collect(mine, collect)
    assert {sp[3]["step"] for sp in found} == {0, 1, 2}


def test_execute_batch_spans(plan, tmp_path):
    a, b = zip(*[_values(plan, s) for s in range(3)])
    a, b = np.stack(a), np.stack(b)
    found = _traced(tmp_path, lambda: plan.execute_batch(a, b))
    top = _one(found, "spgemm.execute_batch")
    assert top[3]["batch"] == 3
    assert {sp[3]["step"] for sp in found} == {plan.report.executes - 2}
    assert all(_inside(sp, top) for sp in found)
    assert "spgemm.rebind" not in {sp[0] for sp in found}
    dispatches = [sp for sp in found if sp[0] == "spgemm.dispatch"]
    assert sum(sp[3]["bind_values"] for sp in dispatches) == 3 * (
        plan.report.nnz_a + plan.report.nnz_b)
    collects = [sp for sp in found if sp[0] == "spgemm.collect"]
    assert len(collects) == len(dispatches)
    for collect in collects:
        _check_collect(found, collect)


def test_dispatch_and_run_count_kernel_calls_and_triples(plan, tmp_path):
    """``spgemm.dispatch`` and a sharded plan's ``spgemm.run`` carry the
    Pallas calls the product dispatches and the block triples it runs:
    one call on the plan within the budget, one a slice on a plan whose
    schedule is cut to a third of it."""
    t = plan.schedule.num_triples
    a_vals, b_vals = _values(plan, 8)
    found = _traced(tmp_path, lambda: plan.execute(a_vals, b_vals))
    args = _one(found, "spgemm.dispatch")[3]
    assert (args["kernel_calls"], args["triples"]) == (1, t)
    panel_end = np.append(np.flatnonzero(plan.schedule.start), t)
    budget = max(int(np.diff(panel_end).max()), t // 3)
    a = COO(plan.a_pattern.row, plan.a_pattern.col, a_vals, plan.a_pattern.shape)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(perfmodel, "SCHEDULE_TRIPLES_PER_CALL", budget)
        split = spgemm_plan(a, a, tile=16, group=2, backend="pallas_interpret",
                            output="compact", cache=PlanCache())
        sharded = spgemm_plan(a, a, tile=16, group=2, backend="pallas_interpret",
                              output="compact", cache=PlanCache(),
                              mesh=Mesh(np.array(jax.devices()[:1]), ("shard",)))
    calls = split.report.kernel_calls
    assert calls >= 3 and sharded.report.kernel_calls == calls
    found = _traced(tmp_path, lambda: split.execute(a_vals, a_vals))
    args = _one(found, "spgemm.dispatch")[3]
    assert (args["kernel_calls"], args["triples"]) == (calls, t)
    batch = np.stack([a_vals, b_vals])
    found = _traced(tmp_path, lambda: split.execute_batch(batch, batch))
    args = _one(found, "spgemm.dispatch")[3]
    assert (args["kernel_calls"], args["triples"]) == (calls, 2 * t)
    found = _traced(tmp_path, lambda: list(split.execute_stream(
        [(a_vals, a_vals)] * 2, depth=2)))
    assert [(sp[3]["kernel_calls"], sp[3]["triples"]) for sp in found
            if sp[0] == "spgemm.dispatch"] == [(calls, t)] * 2
    found = _traced(tmp_path, lambda: sharded.execute(a_vals, a_vals))
    args = _one(found, "spgemm.run")[3]
    assert (args["kernel_calls"], args["triples"]) == (calls, t)


def test_dispatch_and_run_count_the_assembly_runs_and_values(plan, tmp_path):
    """``spgemm.dispatch`` carries the C values a product assembles and
    the runs they are placed in, the executor's table counts: fewer runs
    than values where the run-wise kernel places them, one a value where
    the assembly gathers (a sharded plan's ``spgemm.run``), per set."""
    ex = plan._executor
    nnz_c = plan.compact.nnz
    assert ex.assemble_values == nnz_c
    assert ex.assemble_runs == ex.assembly_runs.n_runs < nnz_c
    a_vals, b_vals = _values(plan, 9)
    found = _traced(tmp_path, lambda: plan.execute(a_vals, b_vals))
    args = _one(found, "spgemm.dispatch")[3]
    assert (args["assemble_runs"], args["assemble_values"]) == (
        ex.assemble_runs, nnz_c)
    batch = np.stack([a_vals, b_vals])
    found = _traced(tmp_path, lambda: plan.execute_batch(batch, batch))
    args = _one(found, "spgemm.dispatch")[3]
    assert (args["assemble_runs"], args["assemble_values"]) == (
        2 * ex.assemble_runs, 2 * nnz_c)
    a = COO(plan.a_pattern.row, plan.a_pattern.col, a_vals, plan.a_pattern.shape)
    sharded = spgemm_plan(a, a, tile=16, group=2, backend="pallas_interpret",
                          output="compact", cache=PlanCache(),
                          mesh=Mesh(np.array(jax.devices()[:1]), ("shard",)))
    found = _traced(tmp_path, lambda: sharded.execute(a_vals, a_vals))
    args = _one(found, "spgemm.run")[3]
    assert args["assemble_runs"] == args["assemble_values"] == nnz_c


def test_spans_reduce_to_the_layer_metrics(plan, tmp_path):
    """The readers of ``bench/metrics`` find the spans of a real CPU trace
    (the CPU has no device plane, so device scopes read nothing)."""
    a_vals, b_vals = _values(plan, 7)
    trace.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for _ in range(2):
                plan.execute(a_vals, b_vals)
    finally:
        trace.stop()
    events = spans.load(trace.newest_xplane(str(tmp_path)))
    assert events["devices"] == {} and events["scopes"] == {}
    events["devices"] = {"/device:TPU:0": []}
    events["scopes"] = {"/device:TPU:0": []}
    red = spans.reduce(events)
    for name in ("spgemm.execute", "spgemm.rebind", "spgemm.dispatch",
                 "spgemm.collect", "spgemm.wait", "spgemm.d2h"):
        assert red["span_s"][name] > 0
    args = red["span_args"]["spgemm.dispatch"]
    assert 0 < args["bind_values"] < args["bind_slots"]
    assert red["scope_s"] == {}


# -- named scopes in the compiled programs ---------------------------------


def _scopes_in(lowered):
    """The outermost ``spgemm.*`` scopes of a compiled program's ops."""
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    return {m.group(1) for m in map(spans.SCOPE.search, names) if m}


@pytest.mark.parametrize("program", ["fused", "stages", "batch", "sharded"])
def test_compiled_programs_carry_the_stage_scopes(program):
    """Small jnp-backend programs: only the metadata is checked (the
    chip's compile of the Pallas programs: tests/test_tpu_compile.py)."""
    backend = "jnp"
    shape = (4, 8, 8)
    piece = tuple(jnp.zeros(6, jnp.int32) for _ in range(4))
    sched = (piece,)  # the schedule as one call's slice
    scatter = jnp.arange(10, dtype=jnp.int32)  # the bind's [nnz] map
    statics = dict(n_panels=2, group=2, backend=backend, interpret=False)
    if program == "fused":
        found = _scopes_in(numeric_core_values.lower(
            jnp.ones(10), jnp.ones(10), scatter, scatter, sched, jnp.zeros(20, jnp.int32),
            a_shape=shape, b_shape=shape, **statics))
    elif program == "stages":
        blocks = jnp.ones(shape)
        found = (_scopes_in(bind_core.lower(jnp.ones(10), scatter, shape=shape))
                 | _scopes_in(numeric_core.lower(blocks, blocks, sched,
                                                 jnp.zeros(20, jnp.int32), **statics)))
    elif program == "batch":
        found = _scopes_in(numeric_core_batch.lower(
            jnp.ones((2, 10)), jnp.ones((2, 10)), scatter, scatter, sched,
            jnp.zeros(20, jnp.int32), a_shape=shape, b_shape=shape, rebind=True,
            **statics))
    else:
        mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
        fn = shard_program("run_values", mesh=mesh, axis="shard", backend=backend,
                           interpret=False, group=2, a_max=shape[0], p_max=2,
                           a_shape=shape, b_shape=shape)
        found = _scopes_in(fn.lower(
            jnp.ones((1, 10)), jnp.ones(10), scatter[None], scatter,
            (tuple(x[None] for x in piece) + (jnp.zeros(6, jnp.int32)[None],),),
            jnp.zeros((1, 20), jnp.int32)))
    assert found == set(SCOPES)
