"""Pallas dispatch on every numeric path (batch, pipeline, sharded).

The batch-folded grid (``spgemm_scheduled_batch_impl``) and the per-shard
Pallas programs inside ``shard_map`` must be *bitwise*-equal to the
single-set kernel — the fold iterates the triple dimension innermost so
each element sees its schedule in the exact single-grid order, and each
shard pads its stacked schedule to a dummy panel no gather reads. These
tests pin that contract, plus the dispatch itself: a pallas plan's batch
path must never silently fall back to the jnp reference kernel.

Sharded coverage runs under forced host devices in a subprocess (XLA
device count is fixed at first jax import — see tests/conftest.py).
"""
import numpy as np
import pytest

from repro.data.pipeline import SpGEMMValueStream
from repro.kernels import ref
from repro.sparse.convert import to_bcsr, to_bcsv
from repro.sparse.random import random_block_sparse, random_coo
from repro.spgemm import PlanCache, spgemm_plan


def _int_coo(m, n, density, seed):
    """Small-integer float32 values: exact under any accumulation order,
    so cross-path comparisons are bit-for-bit."""
    coo = random_coo(m, n, density, "uniform", seed=seed)
    rng = np.random.default_rng(seed + 999)
    vals = rng.integers(-4, 5, coo.nnz).astype(np.float32)
    coo.val = np.where(vals == 0, np.float32(1.0), vals)
    return coo


def _element_plan(seed=0, m=96, k=72, n=80, density=0.06,
                  backend="pallas_interpret"):
    a = _int_coo(m, k, density, seed).sum_duplicates()
    b = _int_coo(k, n, density, seed + 10).sum_duplicates()
    return spgemm_plan(a, b, tile=8, group=2, backend=backend,
                       cache=PlanCache())


def _block_plan(backend="pallas_interpret", size=128, bs=32, seed=3):
    ad = random_block_sparse(size, size, (bs, bs), 0.3, seed=seed)
    bd = random_block_sparse(size, size, (bs, bs), 0.3, seed=seed + 1)
    return spgemm_plan(to_bcsv(ad, (bs, bs), 2), to_bcsr(bd, (bs, bs)),
                       backend=backend, cache=PlanCache())


def _assert_same_csr(x, y):
    assert np.array_equal(x.indptr, y.indptr)
    assert np.array_equal(x.indices, y.indices)
    assert np.array_equal(x.data, y.data)


class TestBatchedPallasDispatch:
    def test_element_batch_matches_looped_execute(self):
        """pallas_interpret execute_batch == a loop of single Pallas
        executes, bitwise (element plan)."""
        plan = _element_plan(seed=1)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=7)
        av, bv = stream.values_batch_at(0, batch=5)
        looped = [plan.execute(av[i], bv[i]) for i in range(5)]
        batched = plan.execute_batch(av, bv)
        assert len(batched) == 5
        for w, g in zip(looped, batched):
            _assert_same_csr(w, g)

    def test_block_batch_matches_looped_execute(self):
        """Same bitwise contract on packed-block operands."""
        plan = _block_plan()
        rng = np.random.default_rng(2)
        ab = rng.standard_normal((3,) + plan._a_shape).astype(np.float32)
        bb = rng.standard_normal((3,) + plan._b_shape).astype(np.float32)
        looped = [plan.execute(ab[i], bb[i]) for i in range(3)]
        batched = plan.execute_batch(ab, bb)
        for w, g in zip(looped, batched):
            _assert_same_csr(w, g)

    def test_batch_matches_jnp_backend(self):
        """Both batch folds (Pallas grid, jnp scatter-add) agree bitwise
        on integer values — same plan, backends swapped."""
        pp = _element_plan(seed=3, backend="pallas_interpret")
        jp = _element_plan(seed=3, backend="jnp")
        stream = SpGEMMValueStream(pp.a_pattern, pp.b_pattern, seed=11)
        av, bv = stream.values_batch_at(0, batch=4)
        for w, g in zip(jp.execute_batch(av, bv), pp.execute_batch(av, bv)):
            _assert_same_csr(w, g)

    def test_pallas_batch_does_not_call_jnp_ref(self, monkeypatch):
        """Dispatch guard: the batch path of a pallas plan must not trace
        the jnp reference kernel (fresh plan shapes force a re-trace, so
        a fallback would hit the patched symbol)."""
        plan = _element_plan(seed=5, m=88, k=64, n=104, density=0.07)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=13)
        av, bv = stream.values_batch_at(0, batch=3)

        def boom(*a, **k):
            raise AssertionError(
                "pallas batch path fell back to ref.spgemm_scheduled_ref")

        monkeypatch.setattr(ref, "spgemm_scheduled_ref", boom)
        out = plan.execute_batch(av, bv)
        assert len(out) == 3

    @pytest.mark.parametrize("depth", [1, 2])
    def test_pipeline_batch_stage_matches_execute_batch(self, depth):
        """The pipeline's batched kernel stage runs the same Pallas fold:
        a batched submit == execute_batch, bitwise."""
        plan = _element_plan(seed=7)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=17)
        av, bv = stream.values_batch_at(0, batch=4)
        want = plan.execute_batch(av, bv)
        with plan.pipeline(depth=depth) as pipe:
            got = pipe.submit(av, bv).result()
        assert len(got) == len(want) == 4
        for w, g in zip(want, got):
            _assert_same_csr(w, g)

    def test_pipeline_stream_matches_sequential(self):
        """Single-set pipeline stages on a pallas plan stay bitwise-equal
        to sequential executes."""
        plan = _element_plan(seed=9)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=19)
        seq = [plan.execute(*stream.values_at(s)) for s in range(4)]
        with plan.pipeline(depth=2) as pipe:
            out = list(pipe.stream(stream.values_at(s) for s in range(4)))
        for w, g in zip(seq, out):
            _assert_same_csr(w, g)


# Child-process body for the sharded tests: builds the same integer-valued
# problem, compares a sharded pallas_interpret plan (execute, execute_batch,
# and a depth-2 pipeline stream) against the single-device jnp plan.
_SHARDED_CODE = """
import numpy as np

from repro.data.pipeline import SpGEMMValueStream
from repro.launch.mesh import make_shard_mesh
from repro.sparse.random import random_coo
from repro.spgemm import PlanCache, spgemm_plan

n_shards = {n_shards}

coo = random_coo(144, 112, 0.06, "uniform", seed=4)
rng = np.random.default_rng(1003)
vals = rng.integers(-4, 5, coo.nnz).astype(np.float32)
coo.val = np.where(vals == 0, np.float32(1.0), vals)
a = coo.sum_duplicates()
coo2 = random_coo(112, 128, 0.06, "uniform", seed=14)
vals = rng.integers(-4, 5, coo2.nnz).astype(np.float32)
coo2.val = np.where(vals == 0, np.float32(1.0), vals)
b = coo2.sum_duplicates()

single = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                     cache=PlanCache())
sharded = spgemm_plan(a, b, tile=8, group=2, backend="pallas_interpret",
                      cache=PlanCache(), mesh=make_shard_mesh(n_shards))

stream = SpGEMMValueStream(single.a_pattern, single.b_pattern, seed=23)

def same(x, y):
    assert np.array_equal(x.indptr, y.indptr)
    assert np.array_equal(x.indices, y.indices)
    assert np.array_equal(x.data, y.data)

# execute
av, bv = stream.values_at(0)
same(single.execute(av, bv), sharded.execute(av, bv))

# execute_batch vs looped single-device executes
ab, bb = stream.values_batch_at(1, batch=4)
want = [single.execute(ab[i], bb[i]) for i in range(4)]
got = sharded.execute_batch(ab, bb)
for w, g in zip(want, got):
    same(w, g)

# pipeline stream through the sharded pallas stage jits
seq = [single.execute(*stream.values_at(s)) for s in range(3)]
with sharded.pipeline(depth=2) as pipe:
    out = list(pipe.stream(stream.values_at(s) for s in range(3)))
for w, g in zip(seq, out):
    same(w, g)

print("SHARDED_PALLAS_OK", n_shards)
"""


class TestShardedPallasDispatch:
    @pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
    def test_sharded_pallas_matches_single_device(self, forced_devices,
                                                  n_shards):
        out = forced_devices(_SHARDED_CODE.format(n_shards=n_shards),
                             devices=8)
        assert f"SHARDED_PALLAS_OK {n_shards}" in out


class TestCompiledPallasNeedsTpu:
    """``backend="pallas"`` compiles the real kernel: off a TPU it raises
    in every executor and kernel entry point — only ``"pallas_interpret"``
    interprets."""

    def test_single_device_executor_raises(self):
        with pytest.raises(RuntimeError, match="pallas_interpret"):
            _element_plan(backend="pallas")

    def test_sharded_executor_raises(self):
        from repro.launch.mesh import make_shard_mesh

        a = _int_coo(96, 72, 0.06, 0).sum_duplicates()
        b = _int_coo(72, 80, 0.06, 10).sum_duplicates()
        with pytest.raises(RuntimeError, match="pallas_interpret"):
            spgemm_plan(a, b, tile=8, group=2, backend="pallas",
                        cache=PlanCache(), mesh=make_shard_mesh(1))

    def test_seed_kernel_entry_points_raise(self):
        from repro.kernels import ops

        x = np.ones((8, 32), np.float32)
        w = to_bcsv(random_block_sparse(32, 32, (8, 8), 0.5, seed=0),
                    (8, 8), 1)
        with pytest.raises(RuntimeError, match="pallas_interpret"):
            ops.sparse_dense_matmul(x, w, backend="pallas", tm=8)
