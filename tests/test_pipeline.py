"""Async serving pipeline: stage-split executor + submit/collect API.

The load-bearing invariant is *bitwise equality*: a pipelined stream of N
steps must reproduce N sequential ``execute`` calls exactly — same
``indptr``/``indices``/``data`` — on element, block, batched, and sharded
plans, at every depth. The stage jits run the same ops as the fused cores,
so this is a property of the refactor, not a tolerance.

Sharded coverage runs under 8 forced host devices via the subprocess-safe
``forced_devices`` fixture (see tests/conftest.py).
"""
import numpy as np
import pytest

from repro.data.pipeline import SpGEMMValueStream
from repro.sparse.convert import to_bcsr, to_bcsv
from repro.sparse.formats import COO
from repro.sparse.random import random_block_sparse, random_coo
from repro.spgemm import (
    PipelineFullError,
    PlanCache,
    SpGEMMPipeline,
    spgemm_plan,
)


def _element_plan(seed=0, m=96, n=80, k=72, density=0.06, backend="jnp",
                  cache=None):
    a = random_coo(m, k, density, "uniform", seed=seed).sum_duplicates()
    b = random_coo(k, n, density, "uniform", seed=seed + 1).sum_duplicates()
    return spgemm_plan(a, b, tile=8, group=2, backend=backend,
                       cache=cache if cache is not None else PlanCache())


def _block_plan(backend="pallas_interpret"):
    ad = random_block_sparse(128, 128, (32, 32), 0.3, seed=3)
    bd = random_block_sparse(128, 128, (32, 32), 0.3, seed=4)
    return spgemm_plan(to_bcsv(ad, (32, 32), 2), to_bcsr(bd, (32, 32)),
                       backend=backend, cache=PlanCache())


def _assert_same_csr(x, y):
    assert np.array_equal(x.indptr, y.indptr)
    assert np.array_equal(x.indices, y.indices)
    assert np.array_equal(x.data, y.data)


class TestBitwiseEquality:
    @pytest.mark.parametrize("depth", [1, 2, 4])
    def test_element_stream_matches_sequential(self, depth):
        plan = _element_plan()
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=7)
        n = 6
        seq = [plan.execute(*stream.values_at(s)) for s in range(n)]
        with plan.pipeline(depth=depth) as pipe:
            out = list(pipe.stream(stream.values_at(s) for s in range(n)))
        assert len(out) == n
        for c_seq, c_pipe in zip(seq, out):
            _assert_same_csr(c_seq, c_pipe)

    @pytest.mark.parametrize("depth", [1, 2, 4])
    def test_block_plan_matches_sequential(self, depth):
        """Packed-block operands (and the pallas_interpret kernel path)."""
        plan = _block_plan()
        rng = np.random.default_rng(0)
        sets = [
            (
                rng.standard_normal(plan._a_shape).astype(np.float32),
                rng.standard_normal(plan._b_shape).astype(np.float32),
            )
            for _ in range(3)
        ]
        seq = [plan.execute(a, b) for a, b in sets]
        with plan.pipeline(depth=depth) as pipe:
            out = list(pipe.stream(iter(sets)))
        for c_seq, c_pipe in zip(seq, out):
            _assert_same_csr(c_seq, c_pipe)

    @pytest.mark.parametrize("depth", [1, 2])
    def test_batched_submit_matches_execute_batch(self, depth):
        """A submit with a leading batch axis == execute_batch, element
        and block plans."""
        plan = _element_plan(seed=11)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=5)
        av, bv = stream.values_batch_at(0, batch=5)
        want = plan.execute_batch(av, bv)
        with plan.pipeline(depth=depth) as pipe:
            got = pipe.submit(av, bv).result()
        assert len(got) == len(want) == 5
        for w, g in zip(want, got):
            _assert_same_csr(w, g)

        bp = _block_plan(backend="jnp")
        rng = np.random.default_rng(1)
        ab = rng.standard_normal((3,) + bp._a_shape).astype(np.float32)
        bb = rng.standard_normal((3,) + bp._b_shape).astype(np.float32)
        want = bp.execute_batch(ab, bb)
        got = bp.execute_async(ab, bb).result()
        for w, g in zip(want, got):
            _assert_same_csr(w, g)

    def test_noarg_submit_uses_staged_values(self):
        plan = _element_plan(seed=21)
        want = plan.execute()
        got = plan.execute_async().result()
        _assert_same_csr(want, got)

    def test_execute_stream_matches_sequential(self):
        plan = _element_plan(seed=31)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=9)
        n = 5
        seq = [plan.execute(*stream.values_at(s)) for s in range(n)]
        out = list(plan.execute_stream(stream.value_iter(steps=n), depth=2))
        assert len(out) == n
        for c_seq, c_pipe in zip(seq, out):
            _assert_same_csr(c_seq, c_pipe)

    def test_empty_plan_pipeline(self):
        """Disjoint patterns (no products): pipelined results are the
        same empty CSR the synchronous path returns."""
        a = COO(np.array([0], np.int32), np.array([0], np.int32),
                np.ones(1, np.float32), (16, 16))
        b = COO(np.array([8], np.int32), np.array([0], np.int32),
                np.ones(1, np.float32), (16, 16))
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache())
        want = plan.execute(np.ones(1, np.float32), np.ones(1, np.float32))
        got = plan.execute_async(
            np.ones(1, np.float32), np.ones(1, np.float32)).result()
        _assert_same_csr(want, got)
        got_b = plan.execute_async(
            np.ones((2, 1), np.float32), np.ones((2, 1), np.float32)
        ).result()
        assert len(got_b) == 2
        for g in got_b:
            _assert_same_csr(want, g)


class TestPipelineSemantics:
    def test_kernel_and_assembly_run_as_one_program(self, monkeypatch):
        """After the bind, a step runs the fused core from the bound
        blocks: the kernel and the assembly in one program, so the panel
        array is never a buffer passed between two programs."""
        from repro.spgemm import executor

        plan = _element_plan(seed=61)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=3)
        vals = [stream.values_at(s) for s in range(4)]
        seq = [plan.execute(*v) for v in vals[:2]]
        batch = plan.execute_batch(np.stack([v[0] for v in vals[2:]]),
                                   np.stack([v[1] for v in vals[2:]]))
        calls = []
        for name in ("numeric_core", "numeric_core_batch"):
            real = getattr(executor, name)

            def spy(*args, _real=real, _name=name, **kwargs):
                calls.append(_name)
                return _real(*args, **kwargs)

            monkeypatch.setattr(executor, name, spy)
        with plan.pipeline(depth=2) as pipe:
            out = list(pipe.stream(vals[:2]))
            got = pipe.submit(np.stack([v[0] for v in vals[2:]]),
                              np.stack([v[1] for v in vals[2:]])).result()
        assert calls == ["numeric_core", "numeric_core", "numeric_core_batch"]
        for c_seq, c_pipe in zip(seq + batch, out + got):
            _assert_same_csr(c_seq, c_pipe)

    def test_out_of_order_collect(self):
        plan = _element_plan(seed=41)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        seq = [plan.execute(*stream.values_at(s)) for s in range(3)]
        with plan.pipeline(depth=3) as pipe:
            tickets = [pipe.submit(*stream.values_at(s)) for s in range(3)]
            c2 = pipe.collect(tickets[2])
            c0 = pipe.collect(tickets[0])
            c1 = tickets[1].result()
        _assert_same_csr(seq[0], c0)
        _assert_same_csr(seq[1], c1)
        _assert_same_csr(seq[2], c2)

    def test_depth_exhaustion_and_refill(self):
        plan = _element_plan(seed=51)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        pipe = plan.pipeline(depth=2)
        t0 = pipe.submit(*stream.values_at(0))
        pipe.submit(*stream.values_at(1))
        assert pipe.in_flight == 2
        with pytest.raises(PipelineFullError, match="depth 2 exhausted"):
            pipe.submit(*stream.values_at(2))
        pipe.collect(t0)  # frees a slot
        pipe.submit(*stream.values_at(2))
        assert pipe.in_flight == 2
        list(pipe)  # drain
        assert pipe.in_flight == 0
        assert plan.in_flight == 0

    def test_default_collect_is_oldest(self):
        plan = _element_plan(seed=61)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        seq = [plan.execute(*stream.values_at(s)) for s in range(2)]
        pipe = plan.pipeline(depth=2)
        pipe.submit(*stream.values_at(0))
        pipe.submit(*stream.values_at(1))
        _assert_same_csr(seq[0], pipe.collect())
        _assert_same_csr(seq[1], pipe.collect())
        with pytest.raises(ValueError, match="nothing in flight"):
            pipe.collect()

    def test_double_collect_raises(self):
        plan = _element_plan(seed=71)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        pipe = plan.pipeline(depth=1)
        t = pipe.submit(*stream.values_at(0))
        t.result()
        with pytest.raises(ValueError, match="already collected"):
            t.result()

    def test_foreign_ticket_rejected(self):
        plan = _element_plan(seed=81)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        p1 = plan.pipeline(depth=1)
        p2 = plan.pipeline(depth=1)
        t = p1.submit(*stream.values_at(0))
        with pytest.raises(ValueError, match="different pipeline"):
            p2.collect(t)
        t.result()

    def test_invalid_submit_holds_no_slot(self):
        plan = _element_plan(seed=91)
        pipe = plan.pipeline(depth=1)
        with pytest.raises(ValueError, match="expected a_vals"):
            pipe.submit(np.ones(3, np.float32), np.ones(3, np.float32))
        with pytest.raises(ValueError, match="both a_vals and b_vals"):
            pipe.submit(np.ones(3, np.float32), None)
        assert pipe.in_flight == 0
        assert plan.in_flight == 0

    def test_poisoned_step_propagates_at_collect(self, monkeypatch):
        """A step whose device dispatch fails re-raises at *its* collect;
        neighbors stay collectable and the pipeline stays usable."""
        plan = _element_plan(seed=101)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        seq = [plan.execute(*stream.values_at(s)) for s in range(3)]
        ex = plan._executor
        real = ex.pipe_kernel
        calls = {"n": 0}

        def flaky(staged, *, mode):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("boom at step 1")
            return real(staged, mode=mode)

        monkeypatch.setattr(ex, "pipe_kernel", flaky)
        pipe = plan.pipeline(depth=3)
        tickets = [pipe.submit(*stream.values_at(s)) for s in range(3)]
        _assert_same_csr(seq[0], tickets[0].result())
        with pytest.raises(RuntimeError, match="boom at step 1"):
            tickets[1].result()
        _assert_same_csr(seq[2], tickets[2].result())
        assert plan.in_flight == 0  # the poisoned slot was freed too
        monkeypatch.setattr(ex, "pipe_kernel", real)
        _assert_same_csr(seq[0], pipe.submit(*stream.values_at(0)).result())

    def test_closed_pipeline_rejects_submit(self):
        plan = _element_plan(seed=111)
        pipe = plan.pipeline(depth=1)
        pipe.close()
        with pytest.raises(RuntimeError, match="closed"):
            pipe.submit()


class TestReleaseGuards:
    def test_release_while_in_flight_raises(self):
        plan = _element_plan(seed=121)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        pipe = plan.pipeline(depth=2)
        t = pipe.submit(*stream.values_at(0))
        assert plan.in_flight == 1
        for fn in (plan.release_values, plan.release_device_values,
                   plan.release):
            with pytest.raises(RuntimeError, match="in-flight pipeline"):
                fn()
        t.result()
        assert plan.in_flight == 0
        plan.release_values()  # legal again once drained

    def test_close_unpins_the_plan(self):
        plan = _element_plan(seed=131)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        pipe = plan.pipeline(depth=2)
        pipe.submit(*stream.values_at(0))
        pipe.submit(*stream.values_at(1))
        with pytest.raises(RuntimeError):
            plan.release_values()
        pipe.close()
        assert plan.in_flight == 0
        plan.release_values()

    def test_released_plan_refuses_work(self):
        plan = _element_plan(seed=141)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        plan.release()
        with pytest.raises(RuntimeError, match="released"):
            plan.execute(*stream.values_at(0))
        with pytest.raises(RuntimeError, match="released"):
            plan.execute_batch(*stream.values_batch_at(0, batch=2))
        with pytest.raises(RuntimeError, match="released"):
            plan.pipeline().submit(*stream.values_at(0))

    def test_cache_evict_guard(self):
        cache = PlanCache()
        plan = _element_plan(seed=151, cache=cache)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        (key,) = list(cache._plans)
        t = plan.pipeline(depth=1).submit(*stream.values_at(0))
        with pytest.raises(RuntimeError, match="in-flight pipeline"):
            cache.evict(key)
        assert key in cache  # still resident
        t.result()
        assert cache.evict(key)
        assert key not in cache
        assert not cache.evict(key)  # already gone: False, no error

    def test_lru_eviction_skips_in_flight_plans(self):
        """Automatic capacity eviction never tears down a plan with
        outstanding tickets — it skips to the next LRU candidate."""
        cache = PlanCache(capacity=2)
        p1 = _element_plan(seed=161, cache=cache)
        stream = SpGEMMValueStream(p1.a_pattern, p1.b_pattern, seed=2)
        t = p1.pipeline(depth=1).submit(*stream.values_at(0))
        p2 = _element_plan(seed=162, cache=cache)  # fills capacity
        _element_plan(seed=163, cache=cache)  # would evict p1 (LRU)
        keys = list(cache._plans)
        assert any(cache._plans[k] is p1 for k in keys)  # p1 survived
        assert all(cache._plans[k] is not p2 for k in keys)  # p2 evicted
        t.result()


class TestShardedPipeline:
    @pytest.mark.parametrize("shards", [1, 2, 4, 8])
    def test_sharded_stream_matches_sequential(self, forced_devices,
                                               shards):
        forced_devices(f"""
            import numpy as np
            from repro.data.pipeline import SpGEMMValueStream
            from repro.launch.mesh import make_shard_mesh
            from repro.sparse.formats import COO
            from repro.sparse.random import suite_matrix
            from repro.spgemm import PlanCache, spgemm_plan

            a = suite_matrix("poisson3Da", scale=0.02).to_coo()
            a = a.sum_duplicates()
            b = COO(a.col, a.row, a.val, (a.shape[1], a.shape[0]))
            plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                               cache=PlanCache(),
                               mesh=make_shard_mesh({shards}))
            stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern,
                                       seed=3)
            n = 4
            seq = [plan.execute(*stream.values_at(s)) for s in range(n)]
            for depth in (1, 2, 4):
                with plan.pipeline(depth=depth) as pipe:
                    out = list(pipe.stream(
                        stream.values_at(s) for s in range(n)))
                for c_seq, c_pipe in zip(seq, out):
                    assert np.array_equal(c_seq.indptr, c_pipe.indptr)
                    assert np.array_equal(c_seq.indices, c_pipe.indices)
                    assert np.array_equal(c_seq.data, c_pipe.data)
            # batched submit == execute_batch
            av, bv = stream.values_batch_at(0, batch=3)
            want = plan.execute_batch(av, bv)
            got = plan.execute_async(av, bv).result()
            for w, g in zip(want, got):
                assert np.array_equal(w.data, g.data)
            print("ok")
        """)


class TestAbandonment:
    def test_abandoned_ticket_does_not_pin_the_plan(self):
        """Dropping an uncollected execute_async ticket (and its hidden
        pipeline) must release the plan's in-flight count at GC, so
        teardown does not stay blocked forever."""
        import gc

        plan = _element_plan(seed=171)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=2)
        t = plan.execute_async(*stream.values_at(0))
        assert plan.in_flight == 1
        del t
        gc.collect()
        assert plan.in_flight == 0
        plan.release_values()  # legal: nothing pins the plan anymore
