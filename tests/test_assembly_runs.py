"""Run-wise output assembly (``repro.kernels.assemble_runs``): the run
table rebuilt from a plan's gather map places every C value exactly once
from where the gather reads it, and the kernel (interpret mode) returns
the element gather's bits, on the benchmark's own patterns and on random
ones with the edge cases, through ``execute``, ``execute_stream``,
``execute_batch`` and a chain."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core.schedule import (
    LANES,
    build_assembly_runs,
    decode_assembly_runs,
)
from repro.kernels import assemble_runs as ar
from repro.sparse.formats import COO
from repro.spgemm import PlanCache, execute_chain, spgemm_plan
from repro.spgemm import executor as executor_mod

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

from bench import patterns  # noqa: E402

BACKEND = "pallas_interpret"


def _bench_csr(matrix, grid):
    p = patterns.matrix_pattern({"matrix": matrix, "grid": grid})
    vals = np.random.default_rng(1).standard_normal(p.nnz)
    return COO(p.row, p.col, vals.astype(np.float32), p.shape)


def _random_csr(m, k, density, seed, empty_rows=()):
    a = sp.random(m, k, density=density, format="coo", dtype=np.float32,
                  random_state=np.random.default_rng(seed))
    keep = ~np.isin(a.row, empty_rows)
    a = sp.coo_matrix((a.data[keep] + 1, (a.row[keep], a.col[keep])),
                      shape=a.shape).tocsr().tocoo()
    return COO(a.row, a.col, a.data, a.shape)


# name -> (A, B, tile, group): tile 16 and group 2 give panels of four
# 128-lane rows; the random shapes are no multiple of the tile (edge
# blocks are trimmed), hold empty rows, and make C long enough to cross
# output blocks of 256 x 128 values.
CASES = {
    "hpcg27": lambda: (_bench_csr("hpcg27", [8, 8, 8]),) * 2 + (16, 2),
    "ex56": lambda: (_bench_csr("ex56", [3, 3, 3]),) * 2 + (16, 2),
    "random": lambda: (_random_csr(301, 203, 0.05, 3, empty_rows=(0, 7, 150)),
                       _random_csr(203, 299, 0.05, 4, empty_rows=(5,)), 16, 2),
    "random-tile32": lambda: (_random_csr(263, 263, 0.08, 5, empty_rows=(9,)),
                              _random_csr(263, 263, 0.08, 6), 32, 4),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    a, b, tile, group = CASES[request.param]()
    plan = spgemm_plan(a, b, tile=tile, group=group, backend=BACKEND,
                       cache=PlanCache(), output="compact")
    return request.param, a, b, plan


def _expand(runs):
    """Every value the run table places: (output position, source index)."""
    d = decode_assembly_runs(runs)
    step = (np.arange(int(d["length"].sum()))
            - np.repeat(np.cumsum(d["length"]) - d["length"], d["length"]))
    return (np.repeat(d["dest"], d["length"]) + step,
            np.repeat(d["src"], d["length"]) + step, d)


def test_run_table_places_every_value_once_from_its_gather_slot(case):
    name, _, _, plan = case
    gather = np.asarray(plan.compact.gather)
    runs = plan._executor.assembly_runs
    assert runs is not None and runs.nnz == gather.shape[0]
    dest, src, d = _expand(runs)
    assert np.array_equal(np.sort(dest), np.arange(gather.shape[0]))
    order = np.argsort(dest)
    assert np.array_equal(src[order], gather)
    # Never the dummy panel, never past a real panel row.
    assert (d["panel"] < plan.schedule.n_panels).all()
    assert (d["row"] < runs.lane_rows).all()
    assert (d["lane"] + d["length"] <= LANES).all()
    assert runs.n_runs < gather.shape[0]
    if name.startswith("random"):
        assert gather.shape[0] % LANES
        assert runs.n_blocks > 1


def _panels(plan, bsz=None, seed=0):
    """Random panels holding -0.0 and NaN, with the executor's shape."""
    n = plan.schedule.n_panels
    shape = (n * (bsz or 1), plan.schedule.group * plan._bm, plan._bn)
    x = np.random.default_rng(seed).standard_normal(shape).astype(np.float32)
    flat = x.reshape(-1)
    flat[::7] = -0.0
    flat[3::11] = np.nan
    return x


def _assemble(plan, panels, bsz):
    ex = plan._executor
    table, items = ex._asm
    return np.asarray(ar.assemble_runs_impl(
        jnp.asarray(panels), table, items, bsz=bsz, nnz=plan.compact.nnz,
        interpret=True))


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


@pytest.mark.parametrize("bsz", [1, 2])
def test_kernel_is_bitwise_the_gather(case, bsz):
    _, _, _, plan = case
    gather = np.asarray(plan.compact.gather)
    panels = _panels(plan, bsz)
    want = panels.reshape(bsz, -1)[:, gather]
    assert np.isnan(want).any() and np.signbit(want[want == 0]).any()
    assert np.array_equal(_bits(_assemble(plan, panels, bsz)), _bits(want))


@pytest.mark.parametrize("name", ["random", "random-tile32"])
def test_kernel_split_into_calls_is_bitwise_the_gather(name, monkeypatch):
    """An item table longer than one call's budget runs as calls cut at
    block starts, each writing into the output of the one before."""
    a, b, tile, group = CASES[name]()
    kw = dict(tile=tile, group=group, backend=BACKEND, output="compact")
    runs = spgemm_plan(a, b, cache=PlanCache(), **kw)._executor.assembly_runs
    assert runs.n_blocks > 1
    # The most items one block holds: a call per block or two.
    budget = int(np.bincount(runs.item_block).max())
    monkeypatch.setattr(ar.perfmodel, "assembly_items_per_call",
                        lambda width: budget)
    plan = spgemm_plan(a, b, cache=PlanCache(), **kw)
    assert len(plan._executor._asm[1]) > 1
    panels = _panels(plan, 1, seed=2)
    want = panels.reshape(1, -1)[:, np.asarray(plan.compact.gather)]
    assert np.array_equal(_bits(_assemble(plan, panels, 1)), _bits(want))


def test_empty_map_has_no_runs_and_assembles_nothing():
    runs = build_assembly_runs(np.zeros(0, np.int32), n_panels=3,
                               lane_rows=4, tile_panels=3, tile_rows=4,
                               block_rows=ar.BLOCK_ROWS)
    assert runs.n_runs == 0 and runs.item_block.shape == (0,)
    assert ar.plan_assembly_runs(np.zeros(0, np.int32), n_panels=3,
                                 group=2, bm=16, bn=16) is None
    out = ar.assemble_runs_impl(jnp.zeros((6, 32, 16)),
                                jnp.zeros(1024, jnp.int32), (), bsz=2, nnz=0)
    assert out.shape == (2, 0)


def test_runs_cut_at_lane_rows_and_blocks():
    """A stretch of consecutive indices is cut where it crosses a lane row
    and where the output crosses a block."""
    per_block = ar.BLOCK_ROWS * LANES
    gather = np.arange(120, 120 + per_block + 10, dtype=np.int64)
    runs = build_assembly_runs(gather, n_panels=1 + gather[-1] // 512,
                               lane_rows=4, tile_panels=4, tile_rows=4,
                               block_rows=ar.BLOCK_ROWS)
    d = decode_assembly_runs(runs)
    # 8 lanes to the first lane row's end, then whole rows; the block's
    # end cuts the run that crosses it.
    assert d["length"][0] == 8 and (d["lane"] + d["length"] <= LANES).all()
    assert d["dest"].tolist().count(per_block) == 1
    assert runs.n_blocks == 2


def test_panels_without_a_lane_view_keep_the_gather():
    """Tile 4, group 2: a panel is 32 values, no whole 128-lane row."""
    a = _random_csr(40, 40, 0.1, 8)
    plan = spgemm_plan(a, a, tile=4, group=2, backend=BACKEND,
                       cache=PlanCache(), output="compact")
    ex = plan._executor
    assert ex.assembly_runs is None
    assert ex.assemble_runs == ex.assemble_values == plan.compact.nnz


@pytest.fixture(scope="module")
def gather_plan(case):
    """The same plan with the element gather, for the bits of before."""
    name, a, b, _ = case
    _, _, tile, group = CASES[name]()
    mp = pytest.MonkeyPatch()
    mp.setattr(executor_mod, "plan_assembly_runs", lambda *a, **k: None)
    try:
        plan = spgemm_plan(a, b, tile=tile, group=group, backend=BACKEND,
                           cache=PlanCache(), output="compact")
    finally:
        mp.undo()
    assert plan._executor.assembly_runs is None
    return plan


def _values(plan, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(plan.report.nnz_a).astype(np.float32),
            rng.standard_normal(plan.report.nnz_b).astype(np.float32))


def test_served_paths_give_the_gathers_bits(case, gather_plan):
    _, _, _, plan = case
    sets = [_values(plan, s) for s in range(3)]
    want = [_bits(gather_plan.execute(*v).data) for v in sets]
    assert plan._executor.assemble_runs < plan._executor.assemble_values
    for v, w in zip(sets, want):
        assert np.array_equal(_bits(plan.execute(*v).data), w)
    for c, w in zip(plan.execute_stream(iter(sets), depth=2), want):
        assert np.array_equal(_bits(c.data), w)
    batch = plan.execute_batch(np.stack([v[0] for v in sets]),
                               np.stack([v[1] for v in sets]))
    for c, w in zip(batch, want):
        assert np.array_equal(_bits(c.data), w)


@pytest.mark.parametrize("name", ["hpcg27", "ex56", "random-tile32"])
def test_chain_gives_the_gathers_bits(name):
    """A @ B @ B, two stages, C device-resident between them."""
    a, b, tile, group = CASES[name]()
    kw = dict(tile=tile, group=group, backend=BACKEND, output="compact")
    mp = pytest.MonkeyPatch()
    mp.setattr(executor_mod, "plan_assembly_runs", lambda *a, **k: None)
    try:
        old = spgemm_plan(a, b, cache=PlanCache(), **kw).then(
            b, cache=PlanCache())
    finally:
        mp.undo()
    new = spgemm_plan(a, b, cache=PlanCache(), **kw).then(
        b, cache=PlanCache())
    assert all(p._executor.assembly_runs is not None for p in new.plans)
    assert all(p._executor.assembly_runs is None for p in old.plans)
    v = _values(new.plans[0], 7)
    assert np.array_equal(_bits(execute_chain(new, *v).data),
                          _bits(execute_chain(old, *v).data))
