"""Mesh-aware sharded SpGEMM plans.

Two layers of coverage:

* the panel-schedule partitioner (pure numpy) is tested in-process:
  slice/rebase reconstruction, triple-count balance on the paper
  matrices, ragged and empty shards, validation;
* sharded ``execute``/``execute_batch`` are tested against the
  single-device plan under 8 forced host devices via the subprocess-safe
  ``forced_devices`` fixture (XLA device count must be set before jax
  import — see tests/conftest.py).
"""
import numpy as np
import pytest

from repro.core.schedule import (
    build_spgemm_schedule,
    partition_spgemm_schedule,
)
from repro.sparse.convert import bcsr_from_coo, bcsv_from_coo
from repro.sparse.formats import COO
from repro.sparse.random import random_coo, suite_matrix


def _paper_schedule(name, scale, tile=16, group=2):
    a = suite_matrix(name, scale=scale).to_coo().sum_duplicates()
    b = COO(a.col, a.row, a.val, (a.shape[1], a.shape[0])).sum_duplicates()
    a_bcsv, _ = bcsv_from_coo(a, (tile, tile), group)
    b_bcsr, _ = bcsr_from_coo(b, (tile, tile))
    return build_spgemm_schedule(a_bcsv, b_bcsr)


class TestPartitioner:
    @pytest.mark.parametrize("n_shards", [1, 2, 3, 5, 8])
    def test_slices_reconstruct_parent(self, n_shards):
        """Every shard is a contiguous rebased slice: concatenating the
        shards (with offsets restored) reproduces the parent schedule."""
        coo = random_coo(200, 160, 0.05, "uniform", seed=3)
        b = COO(coo.col, coo.row, coo.val, (160, 200))
        a_bcsv, _ = bcsv_from_coo(coo, (8, 8), 2)
        b_bcsr, _ = bcsr_from_coo(b, (8, 8))
        sch = build_spgemm_schedule(a_bcsv, b_bcsr)
        shards = partition_spgemm_schedule(sch, n_shards)
        assert len(shards) == n_shards
        assert np.array_equal(
            np.concatenate([s.schedule.a_slot + s.a_lo for s in shards]),
            sch.a_slot)
        assert np.array_equal(
            np.concatenate([s.schedule.b_slot for s in shards]), sch.b_slot)
        assert np.array_equal(
            np.concatenate([s.schedule.panel + s.panel_lo for s in shards]),
            sch.panel)
        assert np.array_equal(
            np.concatenate([s.schedule.sub_row for s in shards]),
            sch.sub_row)
        assert np.array_equal(
            np.concatenate(
                [s.schedule.c_brow + s.group_lo * sch.group for s in shards]),
            sch.c_brow)
        assert np.array_equal(
            np.concatenate([s.schedule.c_bcol for s in shards]), sch.c_bcol)
        # Ranges tile the parent contiguously.
        for prev, cur in zip(shards, shards[1:]):
            assert prev.group_hi == cur.group_lo
            assert prev.triple_hi == cur.triple_lo
            assert prev.panel_hi == cur.panel_lo
        assert shards[0].triple_lo == 0
        assert shards[-1].triple_hi == sch.num_triples

    @pytest.mark.parametrize(
        "name,scale",
        [("poisson3Da", 0.05), ("2cubes_sphere", 0.01), ("cage12", 0.01),
         ("offshore", 0.005)],
    )
    def test_triple_balance_on_paper_matrices(self, name, scale):
        """Acceptance: max/mean triple-count imbalance <= 1.25 at 2/4/8
        shards on the (scaled) paper patterns."""
        sch = _paper_schedule(name, scale)
        for n in (2, 4, 8):
            t = np.array([
                s.num_triples for s in partition_spgemm_schedule(sch, n)
            ])
            assert t.sum() == sch.num_triples
            imbalance = t.max() / t.mean()
            assert imbalance <= 1.25, (name, n, imbalance, t.tolist())

    def test_more_shards_than_groups_yields_empty_shards(self):
        sch = _paper_schedule("poisson3Da", 0.004)
        n_groups = -(-sch.grid_m // sch.group)
        shards = partition_spgemm_schedule(sch, n_groups + 5)
        empty = [s for s in shards if s.num_triples == 0]
        assert empty, "expected empty shards"
        for s in empty:
            assert s.n_panels == 0
            assert s.schedule.nnzb_c == 0
            assert s.a_lo == s.a_hi
        assert sum(s.num_triples for s in shards) == sch.num_triples

    def test_validation(self):
        sch = _paper_schedule("poisson3Da", 0.004)
        with pytest.raises(ValueError, match="n_shards"):
            partition_spgemm_schedule(sch, 0)


SHARDED_VS_SINGLE = """
import numpy as np
import jax
from repro.sparse.random import suite_matrix
from repro.sparse.formats import COO
from repro.launch.mesh import make_shard_mesh
from repro.spgemm import PlanCache, ShardedSpGEMMPlan, spgemm_plan

assert len(jax.devices()) == 8, jax.devices()
rng = np.random.default_rng(0)
for name, scale in (("poisson3Da", 0.004), ("scircuit", 0.004),
                    ("cage12", 0.004)):
    a = suite_matrix(name, scale=scale).to_coo().sum_duplicates()
    # Small-integer values: exact in float32 under any accumulation
    # order, so single- vs multi-device results must be bitwise equal.
    v = rng.integers(-4, 5, a.nnz).astype(np.float32)
    a.val = np.where(v == 0, np.float32(1.0), v)
    b = COO(a.col, a.row, a.val, (a.shape[1], a.shape[0]))
    single = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                         cache=PlanCache())
    c0 = single.execute()
    # Canonical (row-major pattern order) value vectors — b.val is in
    # A^T coordinate order, which is NOT B's canonical order.
    av0 = single.a_pattern.val
    bv0 = single.b_pattern.val
    av = rng.integers(-3, 4, (3, a.nnz)).astype(np.float32)
    bv = rng.integers(-3, 4, (3, b.nnz)).astype(np.float32)
    cb0 = single.execute_batch(av, bv)
    for n in (1, 2, 4, 8):
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=PlanCache(), mesh=make_shard_mesh(n))
        assert isinstance(plan, ShardedSpGEMMPlan)
        stats = plan.shard_stats()
        assert stats["n_shards"] == n
        # jnp path acceptance: bitwise-equal CSR pattern AND data.
        c = plan.execute()
        assert np.array_equal(c.indptr, c0.indptr), (name, n)
        assert np.array_equal(c.indices, c0.indices), (name, n)
        assert np.array_equal(c.data, c0.data), (name, n)
        # Fused fresh-values path (A row-sharded, B replicated).
        c1 = plan.execute(av0 * 2.0, bv0)
        c1s = single.execute(av0 * 2.0, bv0)
        assert np.array_equal(c1.data, c1s.data), (name, n, "values")
        # Batched path: one shard_map call, chunked like the single plan.
        cb = plan.execute_batch(av, bv)
        for i in range(3):
            assert np.array_equal(cb[i].data, cb0[i].data), (name, n, i)
            assert np.array_equal(cb[i].indptr, cb0[i].indptr)
        # execute_batch never reads staged values: works after release.
        plan.release_values()
        cr = plan.execute_batch(av0[None], bv0[None])
        assert np.array_equal(cr[0].data, c0.data), (name, n, "released")
    print(name, "OK")
print("SHARDED_MATCH_OK")
"""


RAGGED_EMPTY_BLOCK = """
import numpy as np
import jax
from repro.sparse.convert import to_bcsr, to_bcsv
from repro.sparse.random import random_block_sparse, random_coo
from repro.sparse.formats import COO
from repro.launch.mesh import make_shard_mesh
from repro.spgemm import PlanCache, spgemm_plan

assert len(jax.devices()) == 8

# Ragged: 5 block-row groups over 2/4 shards (panel counts indivisible),
# and empty shards: 8 shards over 3 groups.
rng = np.random.default_rng(1)
coo = random_coo(77, 63, 0.09, "uniform", seed=11)  # 10 brows @8 / g2 -> 5
v = rng.integers(-4, 5, coo.nnz).astype(np.float32)
coo.val = np.where(v == 0, np.float32(1.0), v)
b = COO(coo.col, coo.row, coo.val, (63, 77))
single = spgemm_plan(coo, b, tile=8, group=2, backend="jnp",
                     cache=PlanCache())
c0 = single.execute()
for n in (2, 4, 8):
    plan = spgemm_plan(coo, b, tile=8, group=2, backend="jnp",
                       cache=PlanCache(), mesh=make_shard_mesh(n))
    if n == 8:
        assert 0 in plan.shard_stats()["triples"], "expected an empty shard"
    c = plan.execute()
    assert np.array_equal(c.indptr, c0.indptr), n
    assert np.array_equal(c.indices, c0.indices), n
    assert np.array_equal(c.data, c0.data), n

# Block (BCSV/BCSR) plans shard over packed block slices.
ad = random_block_sparse(96, 96, (16, 16), 0.4, seed=21)
bd = random_block_sparse(96, 96, (16, 16), 0.4, seed=22)
ab, bb = to_bcsv(ad, (16, 16), 2), to_bcsr(bd, (16, 16))
sb = spgemm_plan(ab, bb, backend="jnp", cache=PlanCache())
c0 = sb.execute()
for n in (2, 8):
    plan = spgemm_plan(ab, bb, backend="jnp", cache=PlanCache(),
                       mesh=make_shard_mesh(n))
    c = plan.execute()
    assert np.array_equal(c.data, c0.data), n
    av = np.stack([ab.blocks, ab.blocks * 2.0])
    bv = np.stack([bb.blocks, bb.blocks])
    cb = plan.execute_batch(av, bv)
    cbs = sb.execute_batch(av, bv)
    assert np.array_equal(cb[0].data, cbs[0].data)
    assert np.array_equal(cb[1].data, cbs[1].data)

# Cache key includes the mesh axis: same pattern, different shard counts
# and the single-device plan coexist; pattern-equal sharded calls hit.
cache = PlanCache()
m4 = make_shard_mesh(4)
p1 = spgemm_plan(ab, bb, backend="jnp", cache=cache, mesh=m4)
p2 = spgemm_plan(ab, bb, backend="jnp", cache=cache, mesh=m4)
p3 = spgemm_plan(ab, bb, backend="jnp", cache=cache)
p4 = spgemm_plan(ab, bb, backend="jnp", cache=cache,
                 mesh=make_shard_mesh(2))
assert p1 is p2 and p1 is not p3 and p1 is not p4
assert cache.stats.hits == 1 and cache.stats.misses == 3
s = cache.stats()
assert s["resident_plans"] == 3 and s["resident_bytes"] > 0
print("RAGGED_EMPTY_BLOCK_OK")
"""


DROPPED_ELEMENTS = """
import numpy as np
import jax
from repro.sparse.random import random_coo
from repro.sparse.formats import COO
from repro.launch.mesh import make_shard_mesh
from repro.spgemm import PlanCache, spgemm_plan

assert len(jax.devices()) == 4
# B's first block row (tile 8) is empty, so A's block-column-0 blocks feed
# no triple: a shard whose slot range starts past them drops their
# elements in the device bind.
a = random_coo(120, 64, 0.08, "uniform", seed=31)
b = random_coo(64, 48, 0.1, "uniform", seed=32)
keep = b.row >= 8
b = COO(b.row[keep], b.col[keep], b.val[keep], b.shape)
single = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=PlanCache())
plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=PlanCache(),
                   mesh=make_shard_mesh(4))
ex = plan._executor
# Nonzero small integers: exact in float32 under any accumulation order,
# so the sharded C must equal the single-device C bit for bit.
rng = np.random.default_rng(33)
av = rng.choice([-3, -2, -1, 1, 2, 3], plan.report.nnz_a).astype(np.float32)
bv = rng.choice([-3, -2, -1, 1, 2, 3], plan.report.nnz_b).astype(np.float32)
a_map = np.asarray(ex._a_scatter)
flat_a = ex._a_max * 8 * 8
dropped = 0
for i in range(4):
    e_lo, e_hi = int(ex._e_bounds[i]), int(ex._e_bounds[i + 1])
    dropped += int((a_map[i, : e_hi - e_lo] >= flat_a).sum())
assert dropped > 0, "expected elements outside a shard's slot range"
assert all(len(np.unique(row)) == row.size for row in a_map)
ha = plan._rebind(av, None, plan._a_scatter, plan.report.nnz_a, "a",
                  plan._a_shape, plan._a_dtype)
hb = plan._rebind(bv, None, plan._b_scatter, plan.report.nnz_b, "b",
                  plan._b_shape, plan._b_dtype)
bits = lambda x: np.ascontiguousarray(np.asarray(x, np.float32)).view(np.uint32)
ga, gb = ex.pipe_stage(av, bv, mode="values")
assert np.array_equal(bits(ga), bits(ex._stack_a(ha)))
assert np.array_equal(bits(gb), bits(hb))
gba, gbb = ex.pipe_stage(np.stack([av, -av]), np.stack([bv, bv]),
                         mode="batch_values")
assert np.array_equal(bits(np.asarray(gba)[:, 0]), bits(ex._stack_a(ha)))
ha2 = plan._rebind(-av, None, plan._a_scatter, plan.report.nnz_a, "a",
                   plan._a_shape, plan._a_dtype)
assert np.array_equal(bits(np.asarray(gba)[:, 1]), bits(ex._stack_a(ha2)))
assert np.array_equal(bits(gbb), bits(np.stack([hb, hb])))
c, c0 = plan.execute(av, bv), single.execute(av, bv)
assert np.array_equal(c.indptr, c0.indptr)
assert np.array_equal(bits(c.data), bits(c0.data))
cb = plan.execute_batch(np.stack([av, -av]), np.stack([bv, bv]))
cb0 = single.execute_batch(np.stack([av, -av]), np.stack([bv, bv]))
for x, y in zip(cb, cb0):
    assert np.array_equal(bits(x.data), bits(y.data))
print("DROPPED_ELEMENTS_OK", dropped)
"""


class TestShardedExecution:
    def test_matches_single_device_on_paper_matrices(self, forced_devices):
        """Acceptance: sharded execute/execute_batch bitwise-equal (jnp
        path) to the single-device plan at 1/2/4/8 shards."""
        out = forced_devices(SHARDED_VS_SINGLE, devices=8)
        assert "SHARDED_MATCH_OK" in out

    def test_ragged_empty_and_block_paths(self, forced_devices):
        out = forced_devices(RAGGED_EMPTY_BLOCK, devices=8)
        assert "RAGGED_EMPTY_BLOCK_OK" in out

    def test_bind_drops_elements_outside_shard_slots(self, forced_devices):
        """Ragged 4-shard bind: elements of A blocks outside a shard's slot
        range are dropped; the rest equals the host rebind bit for bit,
        single and batched, and C equals the single-device plan's."""
        out = forced_devices(DROPPED_ELEMENTS, devices=4)
        assert "DROPPED_ELEMENTS_OK" in out

    def test_single_device_mesh_works_without_forced_devices(self):
        """A 1-device mesh shards trivially in the normal test process."""
        from repro.launch.mesh import make_shard_mesh
        from repro.spgemm import PlanCache, ShardedSpGEMMPlan, spgemm_plan

        coo = random_coo(60, 50, 0.1, "uniform", seed=5)
        rng = np.random.default_rng(6)
        v = rng.integers(-4, 5, coo.nnz).astype(np.float32)
        coo.val = np.where(v == 0, np.float32(1.0), v)
        b = COO(coo.col, coo.row, coo.val, (50, 60))
        single = spgemm_plan(coo, b, tile=8, group=2, backend="jnp",
                             cache=PlanCache())
        plan = spgemm_plan(coo, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache(), mesh=make_shard_mesh(1))
        assert isinstance(plan, ShardedSpGEMMPlan)
        assert np.array_equal(
            plan.execute().todense(), single.execute().todense())
        assert plan.shard_stats()["imbalance"] == 1.0
