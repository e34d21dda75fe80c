"""The schedule split: a schedule longer than one kernel call's SMEM holds
runs as several calls into one panel array.

On PETSc ex56's elasticity operator (``bench/matrices/ex56.py``) at 3
elements a side and tile 8, with the per-call budget
(:data:`repro.core.perfmodel.SCHEDULE_TRIPLES_PER_CALL`) set so that the
3,068-triple schedule takes four calls: the cut itself, and every path
that reaches the kernel — ``execute`` on ``[nnz]`` values,
``execute_stream`` at depth 2, ``execute_batch``, a sharded plan and a
two-stage chain — on ``pallas_interpret`` and ``jnp``, each bitwise equal
to the unsplit plan and within the benchmark's error bound of scipy's
float64 product.
"""
import os
import sys

import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import perfmodel
from repro.kernels.gustavson_spgemm import schedule_cuts
from repro.sparse.formats import COO
from repro.spgemm import PlanCache, spgemm_plan

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

from bench import patterns  # noqa: E402

GRID, TILE, GROUP = [3, 3, 3], 8, 4
BUDGET = 1_000  # 3,068 triples in 4 calls; the longest panel holds 51
BACKENDS = ["pallas_interpret", "jnp"]
# The benchmark's limit on max |C - R| / (|A| |B|), float32 against float64.
VALUE_ERR_LIMIT = 1e-4


def _bits(x):
    return np.ascontiguousarray(np.asarray(x, np.float32)).view(np.uint32)


@pytest.fixture(scope="module")
def ex56():
    p = patterns.matrix_pattern({"matrix": "ex56", "grid": GRID})
    return COO(p.row, p.col, np.ones(p.nnz, np.float32), p.shape)


def _values(a, seed, sets=None):
    rng = np.random.default_rng(seed)
    shape = (a.nnz,) if sets is None else (sets, a.nnz)
    return rng.standard_normal(shape, dtype=np.float32)


def _plan(a, backend, budget=None, **kw):
    with pytest.MonkeyPatch.context() as mp:
        if budget is not None:
            mp.setattr(perfmodel, "SCHEDULE_TRIPLES_PER_CALL", budget)
        return spgemm_plan(a, a, tile=TILE, group=GROUP, backend=backend,
                           output="compact", cache=PlanCache(), **kw)


@pytest.fixture(scope="module", params=BACKENDS)
def plans(request, ex56):
    """(split, unsplit) plans of A @ A on one backend."""
    return (_plan(ex56, request.param, BUDGET),
            _plan(ex56, request.param))


def _csr(a, vals):
    return sp.csr_matrix((vals.astype(np.float64), (a.row, a.col)),
                         shape=a.shape)


def _value_err(c, *factors):
    """max |C - R| / M over C's entries, R the float64 product of the
    factors and M that of their absolute values (bench/reference.py)."""
    exact, bound = factors[0], abs(factors[0])
    for f in factors[1:]:
        exact, bound = exact @ f, bound @ abs(f)
    exact, bound = exact.tocsr(), bound.tocsr()
    exact.sort_indices()
    bound.sort_indices()
    assert np.array_equal(c.indptr, bound.indptr)
    assert np.array_equal(c.indices, bound.indices)
    r = np.asarray(exact[bound.nonzero()]).ravel()  # bound's entry order
    return float(np.max(np.abs(c.data - r) / bound.data))


def _same(split, whole, a, vals):
    """Bitwise the unsplit result, and within the benchmark's bound of
    the float64 product."""
    assert np.array_equal(split.indptr, whole.indptr)
    assert np.array_equal(split.indices, whole.indices)
    assert np.array_equal(_bits(split.data), _bits(whole.data))
    m = _csr(a, vals)
    assert _value_err(split, m, m) < VALUE_ERR_LIMIT


# -- the cut ----------------------------------------------------------------


@pytest.mark.parametrize("budget", [51, 200, BUDGET, 3_068, 49_152])
def test_cuts_start_panels_fit_the_budget_and_cover_the_schedule(ex56, budget):
    start = _plan(ex56, "jnp").schedule.start
    cuts = schedule_cuts(start, budget)
    lo, hi = cuts[:-1], cuts[1:]
    assert cuts[0] == 0 and cuts[-1] == start.shape[0]
    assert np.all(hi > lo)  # together the slices cover it exactly once
    assert np.all(hi - lo <= budget)
    assert np.all(start[lo] == 1)  # every slice starts a panel
    # The fewest slices: no slice could take the next slice's first panel.
    panel_end = np.append(np.flatnonzero(start), start.shape[0])
    nxt = panel_end[np.searchsorted(panel_end, hi[:-1], side="right")]
    assert np.all(nxt - lo[:-1] > budget)
    assert (len(cuts) == 2) == (budget >= start.shape[0])


def test_a_panel_longer_than_the_budget_is_refused(ex56):
    start = _plan(ex56, "jnp").schedule.start
    with pytest.raises(ValueError, match="more than 50 triples"):
        schedule_cuts(start, 50)
    assert list(schedule_cuts(np.zeros(0, np.int32), 50)) == [0, 0]


def test_the_split_plan_stages_its_calls(plans):
    split, whole = plans
    t = split.schedule.num_triples
    assert (split.report.kernel_calls, whole.report.kernel_calls) == (4, 1)
    assert split.report.as_dict()["kernel_calls"] == 4
    pieces = split._executor._sched
    assert len(pieces) == 4
    lens = [piece[0].shape[0] for piece in pieces]
    assert sum(lens) == t and max(lens) <= BUDGET
    for piece, lo in zip(pieces, np.cumsum([0] + lens[:-1])):
        assert np.array_equal(np.asarray(piece[0]),
                              split.schedule.a_slot[lo: lo + piece[0].shape[0]])
        if split.backend != "jnp":  # the start flags: a panel starts each call
            assert int(piece[4][0]) == 1


# -- every path, split against unsplit ----------------------------------------


def test_execute_on_values(plans, ex56):
    split, whole = plans
    v = _values(ex56, 1)
    _same(split.execute(v, v), whole.execute(v, v), ex56, v)


def test_execute_stream_at_depth_2(plans, ex56):
    split, whole = plans
    sets = [(v, v) for v in _values(ex56, 2, sets=3)]
    got = list(split.execute_stream(iter(sets), depth=2))
    want = list(whole.execute_stream(iter(sets), depth=2))
    assert len(got) == len(want) == 3
    for c, c0, (v, _) in zip(got, want, sets):
        _same(c, c0, ex56, v)


def test_execute_batch_of_3(plans, ex56):
    split, whole = plans
    vals = _values(ex56, 3, sets=3)
    for c, c0, v in zip(split.execute_batch(vals, vals),
                        whole.execute_batch(vals, vals), vals):
        _same(c, c0, ex56, v)


def test_two_stage_chain(plans, ex56):
    """A @ A @ A, device-resident between the stages: the second stage's
    plan takes the first's budget too."""
    split, whole = plans
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(perfmodel, "SCHEDULE_TRIPLES_PER_CALL", BUDGET)
        chain = split.then(ex56, cache=PlanCache())
    chain0 = whole.then(ex56, cache=PlanCache())
    assert chain.plans[1].report.kernel_calls > 1
    assert chain0.plans[1].report.kernel_calls == 1
    v = _values(ex56, 4)
    c, c0 = chain.execute(v, v), chain0.execute(v, v)
    assert np.array_equal(c.indptr, c0.indptr)
    assert np.array_equal(_bits(c.data), _bits(c0.data))
    m, ones = _csr(ex56, v), _csr(ex56, np.ones(ex56.nnz, np.float32))
    assert _value_err(c, m, m, ones) < VALUE_ERR_LIMIT


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_split_plan_passes_deep_validation(ex56, backend):
    """The static verifier and the kernel lint (``kernel.smem-schedule``
    judges each staged call against the budget) accept a split plan."""
    plan = _plan(ex56, backend, BUDGET, validate="deep")
    assert plan.report.kernel_calls == 4


def test_a_schedule_within_the_budget_is_one_call(ex56):
    plan = _plan(ex56, "jnp", budget=3_068)
    assert plan.report.kernel_calls == 1
    assert len(plan._executor._sched) == 1


SHARDED = """
import sys
import numpy as np
import jax
sys.path.insert(0, {root!r})
from bench import patterns
from repro.core import perfmodel
from repro.launch.mesh import make_shard_mesh
from repro.sparse.formats import COO
from repro.spgemm import PlanCache, spgemm_plan

assert len(jax.devices()) == 4
p = patterns.matrix_pattern({{"matrix": "ex56", "grid": {grid!r}}})
a = COO(p.row, p.col, np.ones(p.nnz, np.float32), p.shape)
bits = lambda x: np.ascontiguousarray(np.asarray(x, np.float32)).view(np.uint32)
v = np.random.default_rng(5).standard_normal((2, p.nnz), dtype=np.float32)
for backend in ("pallas_interpret", "jnp"):
    def plan(budget, mesh):
        perfmodel.SCHEDULE_TRIPLES_PER_CALL = budget
        return spgemm_plan(a, a, tile={tile}, group={group}, backend=backend,
                           output="compact", cache=PlanCache(), mesh=mesh)
    whole = plan(49_152, make_shard_mesh(4))
    split = plan({budget}, make_shard_mesh(4))
    single = plan(49_152, None)
    per_shard = [sh.num_triples for sh in split._shards]
    assert split.report.kernel_calls >= 3 and whole.report.kernel_calls == 1
    assert all(x[0].shape[-1] <= {budget} for x in split._executor._sched)
    c, c0, c1 = (q.execute(v[0], v[0]) for q in (split, whole, single))
    assert np.array_equal(bits(c.data), bits(c0.data))
    assert np.array_equal(c.indptr, c1.indptr)
    assert np.max(np.abs(c.data - c1.data)) <= 1e-5 * np.max(np.abs(c1.data))
    cb, cb0 = split.execute_batch(v, v), whole.execute_batch(v, v)
    for x, y in zip(cb, cb0):
        assert np.array_equal(bits(x.data), bits(y.data))
    print("SHARDED_SPLIT_OK", backend, split.report.kernel_calls, per_shard)
"""


def test_sharded_plan(forced_devices):
    """Each device of a 4-shard plan runs its shard's schedule in calls
    cut at its own panel starts, padded to the widest shard's slice."""
    out = forced_devices(SHARDED.format(root=ROOT, grid=GRID, tile=TILE,
                                        group=GROUP, budget=300), devices=4)
    assert out.count("SHARDED_SPLIT_OK") == 2, out
