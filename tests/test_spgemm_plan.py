"""Plan/execute SpGEMM API tests: correctness, reuse, caching, and the
sparse-native conversions (no dense round-trip)."""
import threading
import tracemalloc

import numpy as np
import pytest

from repro.core.gustavson import spgemm_gustavson
from repro.data.pipeline import SpGEMMValueStream
from repro.kernels import ops
from repro.sparse.convert import (
    bcsr_from_coo,
    bcsv_from_coo,
    pad_to_blocks,
    to_bcsr,
    to_bcsv,
    to_csr,
)
from repro.sparse.formats import BCSR, BCSV, COO
from repro.sparse.random import random_block_sparse, random_coo, suite_matrix
from repro.spgemm import (
    PlanCache,
    schedule_build_count,
    spgemm_plan,
)


def _int_coo(m, n, density, seed):
    """Sparse matrix with small-integer float32 values: exact in float32
    under any accumulation order, so oracle comparisons are bit-for-bit."""
    coo = random_coo(m, n, density, "uniform", seed=seed)
    rng = np.random.default_rng(seed + 999)
    vals = rng.integers(-4, 5, coo.nnz).astype(np.float32)
    coo.val = np.where(vals == 0, np.float32(1.0), vals)
    return coo


class TestPlanCorrectness:
    @pytest.mark.parametrize("backend", ["pallas_interpret", "jnp"])
    @pytest.mark.parametrize("seed", [0, 1])
    def test_execute_matches_gustavson_bitwise(self, backend, seed):
        a = _int_coo(90, 70, 0.08, seed)
        b = _int_coo(70, 110, 0.1, seed + 10)
        plan = spgemm_plan(a, b, tile=16, group=2, backend=backend,
                           cache=PlanCache())
        c = plan.execute()
        ref = spgemm_gustavson(to_csr(a), to_csr(b))
        assert np.array_equal(c.todense(), ref.todense())

    @pytest.mark.parametrize("name", ["poisson3Da", "scircuit", "cage12"])
    def test_paper_suite_matches_gustavson(self, name):
        """Acceptance: plan/execute vs spgemm_gustavson on (scaled) paper
        matrices."""
        a = suite_matrix(name, scale=0.004)
        coo = a.to_coo()
        b = COO(coo.col, coo.row, coo.val, (a.shape[1], a.shape[0]))  # A^T
        plan = spgemm_plan(a, b, tile=32, group=4,
                           backend="pallas_interpret", cache=PlanCache())
        c = plan.execute()
        ref = spgemm_gustavson(a, to_csr(b))
        np.testing.assert_allclose(c.todense(), ref.todense(),
                                   rtol=1e-4, atol=1e-4)

    def test_empty_inputs(self):
        a = COO(np.zeros(0, np.int32), np.zeros(0, np.int32),
                np.zeros(0, np.float32), (32, 16))
        b = _int_coo(16, 24, 0.2, 3)
        plan = spgemm_plan(a, b, tile=8, group=2,
                           backend="pallas_interpret", cache=PlanCache())
        c = plan.execute()
        assert c.nnz == 0 and c.shape == (32, 24)


class TestPlanReuse:
    def test_two_value_sets_match_gustavson_bitwise(self):
        """One plan, two value sets: both executes match the Gustavson
        oracle bit-for-bit, with zero extra symbolic work."""
        a = _int_coo(80, 60, 0.1, 11)
        b = _int_coo(60, 80, 0.12, 12)
        plan = spgemm_plan(a, b, tile=16, group=2,
                           backend="pallas_interpret", cache=PlanCache())
        builds_after_plan = schedule_build_count()

        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=5,
                                   integer_values=True)
        for step in range(2):
            a_vals, b_vals = stream.values_at(step)
            c = plan.execute(a_vals, b_vals)
            ref = spgemm_gustavson(
                to_csr(COO(plan.a_pattern.row, plan.a_pattern.col, a_vals,
                           a.shape)),
                to_csr(COO(plan.b_pattern.row, plan.b_pattern.col, b_vals,
                           b.shape)),
            )
            assert np.array_equal(c.todense(), ref.todense())
        # Acceptance: re-execution did zero schedule-construction work.
        assert schedule_build_count() == builds_after_plan
        assert plan.report.schedule_builds == 1
        assert plan.report.executes == 2

    def test_cache_returns_identical_plan_object(self):
        a = _int_coo(64, 48, 0.1, 21)
        b = _int_coo(48, 64, 0.1, 22)
        cache = PlanCache()
        p1 = spgemm_plan(a, b, tile=16, group=2, backend="jnp", cache=cache)
        # Pattern-equal input with different values: same plan object.
        a2 = COO(a.row, a.col, a.val * 2.0, a.shape)
        p2 = spgemm_plan(a2, b, tile=16, group=2, backend="jnp", cache=cache)
        assert p2 is p1
        assert cache.stats.hits == 1 and cache.stats.misses == 1
        assert p1.report.cache_hits == 1
        # The hit rebinds the caller's values: execute() uses a2's values.
        c = p2.execute()
        ref = spgemm_gustavson(to_csr(a2), to_csr(b))
        assert np.array_equal(c.todense(), ref.todense())

    def test_cache_misses_on_different_pattern_or_params(self):
        a = _int_coo(64, 48, 0.1, 31)
        b = _int_coo(48, 64, 0.1, 32)
        cache = PlanCache()
        p1 = spgemm_plan(a, b, tile=16, group=2, backend="jnp", cache=cache)
        p2 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache)
        p3 = spgemm_plan(a, b, tile=16, group=4, backend="jnp", cache=cache)
        assert p1 is not p2 and p1 is not p3 and p2 is not p3
        assert cache.stats.misses == 3 and cache.stats.hits == 0

    def test_concurrent_executes_on_shared_plan(self):
        """Cached plans are shared objects: concurrent executes with
        different value sets must each return their own C (no torn
        A/B pairs, no aliased staging buffers)."""
        a = _int_coo(40, 30, 0.12, 81)
        b = _int_coo(30, 40, 0.12, 82)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache())
        mismatches = []

        def worker(seed):
            rng = np.random.default_rng(seed)
            av = rng.integers(-3, 4, a.nnz).astype(np.float32)
            bv = rng.integers(-3, 4, b.nnz).astype(np.float32)
            c = plan.execute(av, bv)
            ref = spgemm_gustavson(
                to_csr(COO(plan.a_pattern.row, plan.a_pattern.col, av,
                           a.shape)),
                to_csr(COO(plan.b_pattern.row, plan.b_pattern.col, bv,
                           b.shape)),
            )
            if not np.array_equal(c.todense(), ref.todense()):
                mismatches.append(seed)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not mismatches
        assert plan.report.executes == 8

    def test_value_stream_iter_does_not_leak_producer(self):
        """Dropping a prefetching iterator must stop its producer thread
        even when the producer is blocked on a full queue."""
        a = _int_coo(20, 20, 0.1, 91)
        b = _int_coo(20, 20, 0.1, 92)
        stream = SpGEMMValueStream(a, b, seed=0)
        before = set(threading.enumerate())
        it = stream.iter(prefetch=1)
        assert set(next(it)) == {"a_vals", "b_vals"}
        producers = [t for t in threading.enumerate() if t not in before]
        assert producers, "expected a producer thread"
        it.close()
        for t in producers:
            t.join(timeout=2.0)
            assert not t.is_alive(), "producer thread leaked"

    def test_shim_does_not_break_direct_plan_holders(self):
        """ops.spgemm releases device copies of the shared cached plan,
        but a direct spgemm_plan holder's no-arg execute() must keep
        working (host values stay staged)."""
        ad = random_block_sparse(96, 96, (32, 32), 0.5, seed=101)
        bd = random_block_sparse(96, 96, (32, 32), 0.5, seed=102)
        a, b = to_bcsv(ad, (32, 32), 2), to_bcsr(bd, (32, 32))
        p = spgemm_plan(a, b, backend="jnp")  # default (shared) cache
        ops.spgemm(a, b, backend="jnp")
        c = p.execute()  # restages from host on demand
        np.testing.assert_allclose(
            c.todense(), ad.astype(np.float64) @ bd.astype(np.float64),
            rtol=1e-4, atol=1e-4)

    def test_ops_spgemm_shim_uses_cache_and_fresh_values(self):
        ad = random_block_sparse(128, 128, (32, 32), 0.4, seed=41)
        bd = random_block_sparse(128, 128, (32, 32), 0.4, seed=42)
        c1 = ops.spgemm(to_bcsv(ad, (32, 32), 2), to_bcsr(bd, (32, 32)),
                        backend="pallas_interpret")
        np.testing.assert_allclose(
            c1.todense(), ad.astype(np.float64) @ bd.astype(np.float64),
            rtol=1e-4, atol=1e-4)
        # Same pattern, new values — must NOT serve stale numerics.
        ad2 = (ad * 3.0).astype(np.float32)
        c2 = ops.spgemm(to_bcsv(ad2, (32, 32), 2), to_bcsr(bd, (32, 32)),
                        backend="pallas_interpret")
        np.testing.assert_allclose(
            c2.todense(), ad2.astype(np.float64) @ bd.astype(np.float64),
            rtol=1e-4, atol=1e-4)


class TestSparseNativeConversion:
    def test_matches_dense_path(self):
        rng = np.random.default_rng(7)
        for _ in range(5):
            m, n = rng.integers(10, 100, 2)
            bm, bn = rng.choice([4, 8, 16], 2)
            g = int(rng.integers(1, 4))
            d = rng.standard_normal((m, n)).astype(np.float32)
            d[rng.random((m, n)) >= 0.1] = 0.0
            ref_r = BCSR.fromdense(pad_to_blocks(d, (bm, bn)), (bm, bn))
            got_r = to_bcsr(d, (bm, bn))
            assert np.array_equal(got_r.indptr, ref_r.indptr)
            assert np.array_equal(got_r.indices, ref_r.indices)
            assert np.array_equal(got_r.blocks, ref_r.blocks)
            ref_v = BCSV.fromdense(pad_to_blocks(d, (bm, bn)), (bm, bn), g)
            got_v = to_bcsv(d, (bm, bn), g)
            got_v.validate()
            assert np.array_equal(got_v.brow, ref_v.brow)
            assert np.array_equal(got_v.bcol, ref_v.bcol)
            assert np.array_equal(got_v.group_ptr, ref_v.group_ptr)
            assert np.array_equal(got_v.blocks, ref_v.blocks)

    def test_scatter_rebinds_values(self):
        coo = _int_coo(60, 44, 0.1, 51)
        fmt, scatter = bcsv_from_coo(coo, (8, 8), 2)
        v2 = np.arange(coo.nnz, dtype=np.float32) + 1.0
        fmt.blocks.reshape(-1)[scatter] = v2
        want = np.zeros(fmt.shape, np.float32)
        want[coo.row, coo.col] = v2
        assert np.array_equal(fmt.todense(), want)

    def test_large_sparse_never_densifies(self):
        """50k x 50k with nnz ~= 100k: the old dense round-trip needed
        ~10 GB; the sparse-native path must stay orders of magnitude
        below that."""
        n = 50_000
        nnz = 100_000
        rng = np.random.default_rng(0)
        row = rng.integers(0, n, nnz).astype(np.int32)
        col = rng.integers(0, n, nnz).astype(np.int32)
        val = rng.standard_normal(nnz).astype(np.float32)
        coo = COO(row, col, val, (n, n)).sum_duplicates()
        tracemalloc.start()
        bcsv = to_bcsv(coo, (8, 8), 4)
        bcsr = to_bcsr(coo, (8, 8))
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert peak < 500 * 1024 * 1024, f"peak {peak / 1e6:.0f} MB"
        assert bcsv.nnzb <= coo.nnz and bcsr.nnzb <= coo.nnz
        # Spot-check structural fidelity without densifying.
        back = bcsv.to_coo().sum_duplicates().sort_rowmajor()
        assert back.nnz == coo.nnz
        s = coo.sort_rowmajor()
        assert np.array_equal(back.row, s.row)
        assert np.array_equal(back.col, s.col)
        np.testing.assert_array_equal(back.val, s.val)

    def test_block_to_coo_roundtrip(self):
        d = random_block_sparse(64, 96, (16, 16), 0.3, seed=61)
        for fmt in (to_bcsr(d, (16, 16)), to_bcsv(d, (16, 16), 2)):
            assert np.array_equal(fmt.to_coo().todense(), fmt.todense())


class TestPlanReport:
    def test_report_fields(self):
        a = _int_coo(64, 64, 0.1, 71)
        b = _int_coo(64, 64, 0.1, 72)
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=PlanCache())
        rep = plan.report
        assert rep.nnz_a == a.nnz and rep.nnz_b == b.nnz
        assert rep.num_triples >= rep.b_fetches >= 1
        assert 0.0 <= rep.block_omar < 100.0
        assert rep.tile == (16, 16, 16) and rep.group == 2
        assert rep.shape == (64, 64)
        d = rep.as_dict()
        assert d["pattern_key"] == rep.pattern_key
        assert d["schedule_builds"] == 1


class TestPatternToken:
    """spgemm_plan(..., pattern_token=): the serving warm path's fast
    cache key — resident lookups skip to_coo + the pattern digest."""

    def test_token_hit_skips_digest_and_returns_same_plan(self, monkeypatch):
        cache = PlanCache()
        a = _int_coo(64, 48, 0.1, 11)
        b = _int_coo(48, 64, 0.1, 12)
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=cache, pattern_token="layer0")
        assert plan.report.pattern_token == "layer0"
        assert plan.report.as_dict()["pattern_token"] == "layer0"
        # A token hit must never touch the digest path.
        from repro.spgemm import plan as plan_mod

        def boom(*a, **k):
            raise AssertionError("token hit paid the pattern digest")

        monkeypatch.setattr(plan_mod, "pattern_digest", boom)
        p2 = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                         cache=cache, pattern_token="layer0")
        assert p2 is plan
        assert cache.stats.token_hits == 1
        assert plan.report.cache_hits == 1

    def test_token_hit_rebinds_canonical_coo_values(self):
        cache = PlanCache()
        a = _int_coo(64, 48, 0.1, 21)
        b = _int_coo(48, 64, 0.1, 22)
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=cache, pattern_token="t")
        a2 = COO(a.row, a.col, a.val * 3.0, a.shape)
        b2 = COO(b.row, b.col, b.val * 0.5, b.shape)
        p2 = spgemm_plan(a2, b2, tile=16, group=2, backend="jnp",
                         cache=cache, pattern_token="t")
        assert p2 is plan
        want = spgemm_gustavson(to_csr(a2), to_csr(b2))
        got = p2.execute()  # staged values must be this call's
        assert np.allclose(got.todense(), want.todense())

    def test_pure_lookup_without_operands(self):
        cache = PlanCache()
        a = _int_coo(32, 32, 0.15, 31)
        b = _int_coo(32, 32, 0.15, 32)
        with pytest.raises(KeyError, match="not resident"):
            spgemm_plan(None, None, tile=16, group=2, backend="jnp",
                        cache=cache, pattern_token="missing")
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=cache, pattern_token="tok")
        p2 = spgemm_plan(None, None, tile=16, group=2, backend="jnp",
                         cache=cache, pattern_token="tok")
        assert p2 is plan

    def test_token_digest_conflict_raises(self):
        """Binding one token to two different patterns is the caller lie
        the digest validation catches — whenever the digest path runs
        (here: the aliased plan was evicted, so the token lookup misses
        and the full path computes the conflicting digest)."""
        cache = PlanCache(capacity=1)
        a = _int_coo(32, 32, 0.15, 41)
        b = _int_coo(32, 32, 0.15, 42)
        spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                    cache=cache, pattern_token="tok")
        a2 = _int_coo(32, 32, 0.2, 43)  # different pattern
        b2 = _int_coo(32, 32, 0.2, 44)
        spgemm_plan(a2, b2, tile=16, group=2, backend="jnp",
                    cache=cache)  # evicts the aliased plan
        with pytest.raises(ValueError, match="already bound"):
            spgemm_plan(a2, b2, tile=16, group=2, backend="jnp",
                        cache=cache, pattern_token="tok")

    def test_token_scopes_by_config(self):
        """The same token under a different tile/group/backend resolves
        independently (the token names a pattern *per config*)."""
        cache = PlanCache()
        a = _int_coo(64, 48, 0.1, 51)
        b = _int_coo(48, 64, 0.1, 52)
        p16 = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                          cache=cache, pattern_token="tok")
        p8 = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                         cache=cache, pattern_token="tok")
        assert p8 is not p16
        assert spgemm_plan(None, None, tile=16, group=2, backend="jnp",
                           cache=cache, pattern_token="tok") is p16
        assert spgemm_plan(None, None, tile=8, group=2, backend="jnp",
                           cache=cache, pattern_token="tok") is p8

    def test_evicted_plan_falls_back_to_full_path(self):
        cache = PlanCache(capacity=1)
        a = _int_coo(32, 32, 0.15, 61)
        b = _int_coo(32, 32, 0.15, 62)
        spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                    cache=cache, pattern_token="tok")
        # Evict by inserting a different pattern.
        a2 = _int_coo(32, 32, 0.2, 63)
        b2 = _int_coo(32, 32, 0.2, 64)
        spgemm_plan(a2, b2, tile=16, group=2, backend="jnp", cache=cache)
        # Token lookup misses (plan evicted) and the full digest path
        # rebuilds + re-binds the alias.
        p = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                        cache=cache, pattern_token="tok")
        assert p.report.pattern_token == "tok"
        assert spgemm_plan(None, None, tile=16, group=2, backend="jnp",
                           cache=cache, pattern_token="tok") is p

    def test_token_hit_canonicalizes_unsorted_coo(self):
        """A token hit with a permuted (non-canonical) COO must produce
        the same results as the digest path — the hit verifies canonical
        order and sorts only when needed (review regression)."""
        cache = PlanCache()
        a = _int_coo(48, 40, 0.12, 71)
        b = _int_coo(40, 48, 0.12, 72)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=cache, pattern_token="tok")
        rng = np.random.default_rng(0)
        pa = rng.permutation(a.nnz)
        pb = rng.permutation(b.nnz)
        a_shuf = COO(a.row[pa], a.col[pa], (a.val * 2.0)[pa], a.shape)
        b_shuf = COO(b.row[pb], b.col[pb], (b.val * 3.0)[pb], b.shape)
        p2 = spgemm_plan(a_shuf, b_shuf, tile=8, group=2, backend="jnp",
                         cache=cache, pattern_token="tok")
        assert p2 is plan
        got = p2.execute()
        want = spgemm_gustavson(to_csr(a_shuf.sum_duplicates()),
                                to_csr(b_shuf.sum_duplicates()))
        assert np.array_equal(got.todense(), want.todense())

    def test_token_hit_rejects_wrong_nnz(self):
        cache = PlanCache()
        a = _int_coo(48, 40, 0.12, 81)
        b = _int_coo(40, 48, 0.12, 82)
        spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                    cache=cache, pattern_token="tok")
        a_less = COO(a.row[:-1], a.col[:-1], a.val[:-1], a.shape)
        with pytest.raises(ValueError, match="does not match the token"):
            spgemm_plan(a_less, b, tile=8, group=2, backend="jnp",
                        cache=cache, pattern_token="tok")

    def test_token_never_serves_across_value_dtypes(self):
        """A float64 request must not be served (and silently downcast)
        by a float32-built plan through the token fast path — it falls
        to the digest path, which raises the token conflict."""
        cache = PlanCache()
        a = _int_coo(48, 40, 0.12, 91)
        b = _int_coo(40, 48, 0.12, 92)
        p32 = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                          cache=cache, pattern_token="tok")
        a64 = COO(a.row, a.col, a.val.astype(np.float64), a.shape)
        b64 = COO(b.row, b.col, b.val.astype(np.float64), b.shape)
        with pytest.raises(ValueError, match="already bound"):
            spgemm_plan(a64, b64, tile=8, group=2, backend="jnp",
                        cache=cache, pattern_token="tok")
        # ... and without the token the float64 plan is simply distinct.
        p64 = spgemm_plan(a64, b64, tile=8, group=2, backend="jnp",
                          cache=cache)
        assert p64 is not p32

    def test_release_evicts_dead_plan_from_cache(self):
        """release() must not leave the dead plan resident — the next
        spgemm_plan for the pattern rebuilds instead of hitting a plan
        that can only raise (review regression)."""
        cache = PlanCache()
        a = _int_coo(48, 40, 0.12, 95)
        b = _int_coo(40, 48, 0.12, 96)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=cache)
        plan.release()
        assert len(cache) == 0
        p2 = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                         cache=cache)
        assert p2 is not plan
        p2.execute()  # alive and serving

    def test_token_hit_rebinds_block_inputs(self):
        """Block-plan token hits must rebind this call's packed blocks —
        never serve the previous caller's staged values silently."""
        cache = PlanCache()
        d_a = random_block_sparse(64, 64, (16, 16), 0.4, seed=71)
        d_b = random_block_sparse(64, 64, (16, 16), 0.4, seed=72)
        a1, b1 = to_bcsv(d_a, (16, 16), 2), to_bcsr(d_b, (16, 16))
        plan = spgemm_plan(a1, b1, backend="jnp", cache=cache,
                           pattern_token="blk")
        a2 = BCSV(a1.blocks * 2.0, a1.brow, a1.bcol, a1.group_ptr,
                  a1.shape, a1.group)
        b2 = BCSR(b1.indptr, b1.indices, b1.blocks * 0.5, b1.shape)
        p2 = spgemm_plan(a2, b2, backend="jnp", cache=cache,
                         pattern_token="blk")
        assert p2 is plan
        got = p2.execute()
        assert np.allclose(got.todense(), (d_a * 2.0) @ (d_b * 0.5),
                           atol=1e-4)

    def test_token_hit_rejects_unrebindable_input_type(self):
        """CSR (or any other) inputs on a token hit would keep stale
        staged values — the fast path refuses them instead."""
        cache = PlanCache()
        a = _int_coo(48, 40, 0.12, 75)
        b = _int_coo(40, 48, 0.12, 76)
        spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache,
                    pattern_token="tok")
        with pytest.raises(ValueError, match="token fast path"):
            spgemm_plan(to_csr(a), to_csr(b), tile=8, group=2,
                        backend="jnp", cache=cache, pattern_token="tok")

    def test_stale_release_leaves_rebuilt_plan_alone(self):
        """release() on a plan whose cache slot was evicted and rebuilt
        must not evict (or complain about) the new live plan."""
        cache = PlanCache(capacity=1)
        a = _int_coo(48, 40, 0.12, 85)
        b = _int_coo(40, 48, 0.12, 86)
        old = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                          cache=cache)
        a2 = _int_coo(48, 40, 0.2, 87)
        b2 = _int_coo(40, 48, 0.2, 88)
        spgemm_plan(a2, b2, tile=8, group=2, backend="jnp", cache=cache)
        fresh = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                            cache=cache)  # rebuilt under old's key
        assert fresh is not old
        old.release()
        assert len(cache) == 1  # fresh survived
        assert spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=cache) is fresh


def _bits(x) -> np.ndarray:
    """Bit pattern of a float32 array: equality here is bitwise."""
    return np.ascontiguousarray(np.asarray(x, np.float32)).view(np.uint32)


def _canonical_pair(seed):
    """A (40 x 32) and B (32 x 24) in canonical order, so the value
    vectors built here line up with the plan's pattern; tile 8 divides
    every side, so a block plan of the pattern pads nothing."""
    return (_int_coo(40, 32, 0.12, seed).sum_duplicates(),
            _int_coo(32, 24, 0.12, seed + 1).sum_duplicates())


class TestHeldValues:
    """An element plan holds its operand values once, as its own copy of
    the ``[nnz]`` vectors, and every numeric call binds them on the
    device: single and sharded plans, on both CPU backends."""

    @staticmethod
    def _plan(a, b, backend, sharded, cache, **kw):
        from repro.launch.mesh import make_shard_mesh

        return spgemm_plan(a, b, tile=8, group=2, backend=backend,
                           cache=cache,
                           mesh=make_shard_mesh(1) if sharded else None, **kw)

    @pytest.mark.parametrize(
        "after", ["values", "a_only", "cache_hit", "store_load",
                  "release_device"])
    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["single", "sharded"])
    @pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
    def test_no_arg_call_equals_the_explicit_call(self, backend, sharded,
                                                  after, tmp_path):
        """A no-argument ``execute()`` or ``submit()`` runs the values the
        last call, cache hit or store load left, bit for bit as the
        explicit call with them."""
        a, b = _canonical_pair(3)
        rng = np.random.default_rng(5)
        av = rng.standard_normal(a.nnz).astype(np.float32)
        bv = rng.standard_normal(b.nnz).astype(np.float32)
        disk = {"disk_dir": str(tmp_path)} if after == "store_load" else {}
        cache = PlanCache(**disk)
        plan = self._plan(a, b, backend, sharded, cache)
        if after == "values":
            plan.execute(av, bv)
        elif after == "a_only":
            plan.execute(av)
            bv = b.val
        elif after == "release_device":
            plan.execute(av, bv)
            plan.release_device_values()
        else:  # the same pattern, with this call's values
            if after == "store_load":
                cache = PlanCache(**disk)
            plan = self._plan(COO(a.row, a.col, av, a.shape),
                              COO(b.row, b.col, bv, b.shape),
                              backend, sharded, cache)
            assert (plan.report.loads, plan.report.cache_hits) == (
                (1, 0) if after == "store_load" else (0, 1))
        got = [plan.execute(), plan.execute_async().result()]
        want = plan.execute(av, bv)
        for c in got:
            assert np.array_equal(c.indptr, want.indptr)
            assert np.array_equal(_bits(c.data), _bits(want.data))

    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["single", "sharded"])
    @pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
    def test_caller_buffers_do_not_reach_the_held_values(self, backend,
                                                         sharded):
        """A caller that reuses its value buffers after ``execute``, or
        after a token hit that took its canonical COO as it is, changes
        no later no-argument call."""
        a, b = _canonical_pair(7)
        cache = PlanCache()
        plan = self._plan(a, b, backend, sharded, cache, pattern_token="t")
        av, bv = a.val * np.float32(2), b.val * np.float32(3)
        want = plan.execute(av, bv)
        av[:] = 0
        bv[:] = 0
        assert np.array_equal(_bits(plan.execute().data), _bits(want.data))
        a2 = COO(a.row, a.col, a.val * np.float32(5), a.shape)
        b2 = COO(b.row, b.col, b.val * np.float32(7), b.shape)
        assert self._plan(a2, b2, backend, sharded, cache,
                          pattern_token="t") is plan
        want = plan.execute(a2.val.copy(), b2.val.copy())
        a2.val[:] = 0
        b2.val[:] = 0
        assert np.array_equal(_bits(plan.execute().data), _bits(want.data))

    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["single", "sharded"])
    @pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
    def test_element_plan_holds_no_block_arrays(self, backend, sharded):
        """Against a block plan of the same pattern, an element plan's
        ``host_nbytes()`` is less the packed blocks and more only its
        vectors, scatter maps and patterns."""
        a, b = _canonical_pair(11)
        elem = self._plan(a, b, backend, sharded, PlanCache())
        blk = self._plan(to_bcsv(a, (8, 8), 2), to_bcsr(b, (8, 8)),
                         backend, sharded, PlanCache())
        assert elem._a_blocks is None and elem._b_blocks is None
        assert blk._a_vals is None and blk._b_vals is None
        blocks = blk._a_blocks.nbytes + blk._b_blocks.nbytes
        held = [elem._a_vals, elem._b_vals, elem._a_scatter, elem._b_scatter]
        held += [x for p in (elem.a_pattern, elem.b_pattern)
                 for x in (p.row, p.col, p.val)]
        assert elem.host_nbytes() == (
            blk.host_nbytes() - blocks + sum(x.nbytes for x in held))
        assert blocks > sum(x.nbytes for x in held[:2])
