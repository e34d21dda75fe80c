"""Compacted nnz-exact output (``output="compact"``) and structural plan
composition (``plan_from_structural_pattern`` / ``SpGEMMChain`` /
``execute_chain``).

Coverage layers:

* compact-vs-block agreement is **bitwise** (dense expansion) on every
  dispatch path — element, block-kind, batched, sharded at 1–8 forced
  devices, pipelined — with the compact result holding exactly the
  structural-product nnz (no block-padding zeros);
* edge cases: empty output rows, a single-nnz product inside a padded
  block, the all-empty product;
* compact plans persist and rehydrate through the disk tier with the
  compact map intact, under cache keys distinct from block plans;
* ``verify_plan`` catches hand-corrupted compact gather maps
  (fault-injection via ``dataclasses.replace``);
* chains are bitwise-equal to independent per-stage executes with a host
  round trip between them, while keeping intermediates device-resident.
"""
import dataclasses

import numpy as np
import pytest

from repro.analysis.verify import verify_plan
from repro.sparse.convert import bcsr_from_coo, bcsv_from_coo
from repro.sparse.formats import COO
from repro.sparse.random import random_coo
from repro.spgemm.cache import PlanCache
from repro.spgemm.plan import (
    SpGEMMChain,
    SpGEMMPlan,
    StructuralPattern,
    chain_plans,
    execute_chain,
    plan_from_structural_pattern,
    spgemm_plan,
)


def _int_coo(m, n, density, seed):
    """Small-integer float32 values — exact in f32, so compact-vs-block
    and chain-vs-round-trip comparisons can demand bitwise equality."""
    coo = random_coo(m, n, density, "uniform", seed=seed)
    rng = np.random.default_rng(seed + 999)
    vals = rng.integers(-4, 5, coo.nnz).astype(np.float32)
    coo.val = np.where(vals == 0, np.float32(1.0), vals)
    return coo.sum_duplicates()


def _mats(seed=0, m=96, n=80, k=72, density=0.06):
    a = _int_coo(m, n, density, seed)
    b = _int_coo(n, k, density, seed + 50)
    return a, b


def _pair(seed=0, **kw):
    a, b = _mats(seed, **kw)
    cache = PlanCache()
    blk = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache)
    cmp_ = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache,
                       output="compact")
    return a, b, blk, cmp_


def _structural_nnz(a: COO, b: COO) -> int:
    da = np.zeros(a.shape, bool)
    da[a.row, a.col] = True
    db = np.zeros(b.shape, bool)
    db[b.row, b.col] = True
    return int(np.count_nonzero(da.astype(np.int64) @ db.astype(np.int64)))


class TestCompactOutput:
    def test_element_bitwise_vs_block(self):
        a, b, blk, cmp_ = _pair(1)
        rb, rc = blk.execute(), cmp_.execute()
        assert np.array_equal(rb.todense(), rc.todense())  # bitwise
        assert rc.data.size == _structural_nnz(a, b)
        assert rc.data.size < rb.data.size  # padding zeros dropped

    def test_compact_is_subset_with_own_csr(self):
        _, _, blk, cmp_ = _pair(2)
        asm, comp = blk.assembly, cmp_.compact
        assert comp.nnz <= asm.nnz
        assert np.isin(np.asarray(comp.gather),
                       np.asarray(asm.gather)).all()
        # Block plan keeps its block-structural CSR untouched.
        assert blk.compact is None and blk.output == "block"
        assert cmp_.assembly.nnz == asm.nnz

    def test_block_kind_plan_degenerates_to_block_map(self):
        """Block-input plans have no element pattern: stored blocks are
        dense by contract, so compact degenerates to the block map and
        results stay identical."""
        a, b = _mats(3)
        a_bcsv, _ = bcsv_from_coo(a, (8, 8), 2)
        b_bcsr, _ = bcsr_from_coo(b, (8, 8))
        cache = PlanCache()
        blk = spgemm_plan(a_bcsv, b_bcsr, backend="jnp", cache=cache)
        cmp_ = spgemm_plan(a_bcsv, b_bcsr, backend="jnp", cache=cache,
                           output="compact")
        assert cmp_.compact is cmp_.assembly
        assert np.array_equal(blk.execute().todense(),
                              cmp_.execute().todense())

    def test_batched_bitwise(self):
        a, b, blk, cmp_ = _pair(4)
        rng = np.random.default_rng(0)
        av = rng.integers(-3, 4, (3, a.nnz)).astype(np.float32)
        bv = rng.integers(-3, 4, (3, b.nnz)).astype(np.float32)
        outs_b = blk.execute_batch(av, bv)
        outs_c = cmp_.execute_batch(av, bv)
        for ob, oc in zip(outs_b, outs_c):
            assert np.array_equal(ob.todense(), oc.todense())
            assert oc.data.size == cmp_.compact.nnz

    def test_pipelined_bitwise(self):
        a, b, blk, cmp_ = _pair(5)
        rng = np.random.default_rng(1)
        sets = [
            (rng.integers(-3, 4, a.nnz).astype(np.float32),
             rng.integers(-3, 4, b.nnz).astype(np.float32))
            for _ in range(4)
        ]
        outs_c = list(cmp_.execute_stream(iter(sets), depth=2))
        for (av, bv), oc in zip(sets, outs_c):
            ob = blk.execute(a_vals=av, b_vals=bv)
            assert np.array_equal(ob.todense(), oc.todense())

    @pytest.mark.parametrize("n_shards", [1, 2, 3, 5, 8])
    def test_sharded_bitwise(self, forced_devices, n_shards):
        forced_devices(f"""
            import numpy as np
            from repro.analysis.verify import verify_plan
            from repro.launch.mesh import make_shard_mesh
            from repro.sparse.random import random_coo
            from repro.spgemm.cache import PlanCache
            from repro.spgemm.plan import spgemm_plan

            a = random_coo(96, 80, 0.06, "uniform", seed=0).sum_duplicates()
            b = random_coo(80, 72, 0.06, "uniform", seed=50).sum_duplicates()
            rng = np.random.default_rng(1)
            a.val = rng.integers(-4, 5, a.nnz).astype(np.float32)
            b.val = rng.integers(-4, 5, b.nnz).astype(np.float32)
            cache = PlanCache()
            mesh = make_shard_mesh({n_shards})
            blk = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                              cache=cache, mesh=mesh)
            cmp_ = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                               cache=cache, mesh=mesh, output="compact")
            rb, rc = blk.execute(), cmp_.execute()
            assert np.array_equal(rb.todense(), rc.todense())
            assert rc.data.size == cmp_.compact.nnz < rb.data.size
            # Single-device reference, same operands.
            ref = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                              cache=PlanCache(), output="compact")
            assert np.array_equal(rc.todense(), ref.execute().todense())
            rep = verify_plan(cmp_)
            assert rep.ok, rep.summary()
            assert "compact" in rep.checks_run
            print("ok", {n_shards})
        """, devices=8)

    def test_empty_rows_and_cols(self):
        """Rows of A with no entries produce empty compact rows (indptr
        plateaus), still bitwise-equal to the block result."""
        a = COO(np.array([2, 2, 17]), np.array([1, 30, 4]),
                np.array([2.0, -1.0, 3.0], np.float32), (24, 40))
        b = _int_coo(40, 32, 0.08, 9)
        cache = PlanCache()
        blk = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache)
        cmp_ = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=cache, output="compact")
        assert np.array_equal(blk.execute().todense(),
                              cmp_.execute().todense())
        indptr = np.asarray(cmp_.compact.indptr)
        assert indptr.shape == (25,)
        assert indptr[0] == 0 and indptr[2] == 0  # rows 0-1 empty

    def test_single_nnz_in_padded_block(self):
        """One product element inside an 8x8 block: block output stores
        the 64 padded entries, compact stores exactly one."""
        a = COO(np.array([3]), np.array([5]),
                np.array([2.0], np.float32), (16, 16))
        b = COO(np.array([5]), np.array([7]),
                np.array([-3.0], np.float32), (16, 16))
        cache = PlanCache()
        blk = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache)
        cmp_ = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=cache, output="compact")
        rc = cmp_.execute()
        assert rc.data.size == 1
        assert blk.execute().data.size == 64
        dense = rc.todense()
        assert dense[3, 7] == np.float32(-6.0)
        assert np.count_nonzero(dense) == 1

    def test_empty_product(self):
        """Disjoint patterns: the product is structurally empty on both
        output formats."""
        a = COO(np.array([0]), np.array([0]),
                np.array([1.0], np.float32), (16, 16))
        b = COO(np.array([9]), np.array([0]),
                np.array([1.0], np.float32), (16, 16))
        cmp_ = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache(), output="compact")
        out = cmp_.execute()
        assert out.data.size == 0
        assert np.asarray(out.indptr).shape == (17,)

    def test_device_indptr_matches_host(self):
        _, _, blk, cmp_ = _pair(6)
        for plan in (blk, cmp_):
            want = np.asarray(plan._active().indptr)
            got = np.asarray(plan.device_indptr())
            assert np.array_equal(got.astype(np.int64), want.astype(np.int64))

    def test_persist_rehydrate_roundtrip(self, tmp_path):
        a, b = _mats(7)
        c1 = PlanCache(disk_dir=str(tmp_path))
        p1 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=c1,
                         output="compact")
        r1 = p1.execute()
        # Warm restart: fresh memory tier, same disk.
        c2 = PlanCache(disk_dir=str(tmp_path))
        p2 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=c2,
                         output="compact")
        assert c2.stats.loads == 1  # rehydrated, not rebuilt
        assert p2.output == "compact" and p2.compact is not None
        for f in ("gather", "indptr", "indices"):
            assert np.array_equal(np.asarray(getattr(p1.compact, f)),
                                  np.asarray(getattr(p2.compact, f)))
        assert np.array_equal(r1.todense(), p2.execute().todense())
        assert verify_plan(p2).ok

    def test_block_and_compact_keys_are_distinct(self, tmp_path):
        a, b = _mats(8)
        cache = PlanCache(disk_dir=str(tmp_path))
        p_blk = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                            cache=cache)
        p_cmp = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                            cache=cache, output="compact")
        assert p_blk is not p_cmp
        assert cache.stats.misses == 2  # two builds, no cross-serving
        # Requesting the same output again hits.
        again = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                            cache=cache, output="compact")
        assert again is p_cmp

    def test_autotune_rejects_compact(self):
        a, b = _mats(9)
        with pytest.raises(ValueError, match="autotune"):
            spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                        cache=PlanCache(), output="compact", autotune=True)


class TestCompactVerify:
    def test_clean_plan_passes(self):
        _, _, _, cmp_ = _pair(10)
        rep = verify_plan(cmp_)
        assert rep.ok and "compact" in rep.checks_run

    def test_fault_duplicate_gather(self):
        _, _, _, cmp_ = _pair(11)
        good = cmp_.compact
        g = np.asarray(good.gather).copy()
        g[1] = g[0]  # two C elements read one slot
        cmp_.compact = dataclasses.replace(good, gather=g)
        rep = verify_plan(cmp_)
        assert not rep.ok
        assert any(f.check == "compact.gather-duplicate"
                   for f in rep.errors)

    def test_fault_out_of_subset_gather(self):
        _, _, _, cmp_ = _pair(12)
        good = cmp_.compact
        g = np.asarray(good.gather).copy()
        outside = np.setdiff1d(
            np.arange(int(np.asarray(cmp_.assembly.gather).max()) + 2),
            np.asarray(cmp_.assembly.gather),
        )
        g[0] = outside[0]
        cmp_.compact = dataclasses.replace(good, gather=g)
        rep = verify_plan(cmp_)
        assert not rep.ok
        assert any(f.check == "compact.subset" for f in rep.errors)

    def test_fault_permuted_gather_caught_by_rebuild(self):
        _, _, _, cmp_ = _pair(13)
        good = cmp_.compact
        g = np.flip(np.asarray(good.gather)).copy()
        cmp_.compact = dataclasses.replace(good, gather=g)
        rep = verify_plan(cmp_)
        assert not rep.ok
        assert any(f.check == "compact.rebuild" for f in rep.errors)

    def test_fault_unsorted_columns(self):
        _, _, _, cmp_ = _pair(14)
        good = cmp_.compact
        idx = np.asarray(good.indices).copy()
        r0, r1 = int(good.indptr[0]), None
        # Find a row with >= 2 entries and swap its first two columns.
        counts = np.diff(np.asarray(good.indptr))
        row = int(np.argmax(counts >= 2))
        lo = int(good.indptr[row])
        idx[lo], idx[lo + 1] = idx[lo + 1], idx[lo]
        cmp_.compact = dataclasses.replace(good, indices=idx)
        rep = verify_plan(cmp_)
        assert not rep.ok
        assert any(f.check == "compact.column-order" for f in rep.errors)


class TestChain:
    def _abc(self, seed=20):
        a = _int_coo(64, 56, 0.07, seed)
        b = _int_coo(56, 48, 0.07, seed + 1)
        c = _int_coo(48, 40, 0.07, seed + 2)
        return a, b, c

    def test_then_bitwise_vs_host_round_trip(self):
        a, b, c = self._abc()
        cache = PlanCache()
        p1 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache,
                         output="compact")
        chain = p1.then(c, cache=cache)
        assert isinstance(chain, SpGEMMChain)
        out = chain.execute()
        # Independent executes with a host round trip in between.
        r1 = p1.execute()
        p2 = chain.plans[1]
        rt = p2.execute(a_vals=np.asarray(r1.data))
        assert np.array_equal(np.asarray(out.data), np.asarray(rt.data))
        assert np.array_equal(out.todense(), rt.todense())

    def test_intermediate_stays_on_device(self):
        import jax

        a, b, c = self._abc(24)
        cache = PlanCache()
        p1 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache,
                         output="compact")
        chain = p1.then(c, cache=cache)
        packed, _ = chain.plans[0]._run_packed(None, None)
        assert isinstance(packed, jax.Array)  # never left the device
        packed2 = chain.plans[1]._run_packed_chained(packed)
        assert isinstance(packed2, jax.Array)

    def test_three_stage_chain(self):
        a, b, c = self._abc(28)
        d = _int_coo(40, 32, 0.07, 31)
        cache = PlanCache()
        p1 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache,
                         output="compact")
        chain = p1.then(c, cache=cache).then(d, cache=cache)
        assert len(chain.plans) == 3
        out = chain.execute()
        ref = (_dense(a) @ _dense(b) @ _dense(c) @ _dense(d))
        np.testing.assert_allclose(out.todense(), ref, rtol=1e-4, atol=1e-4)

    def test_execute_chain_accepts_raw_lists_and_validates(self):
        a, b, c = self._abc(32)
        cache = PlanCache()
        p1 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache,
                         output="compact")
        p2 = plan_from_structural_pattern(
            p1.output_pattern(), c, tile=8, group=2, backend="jnp",
            cache=cache, output="compact",
        )
        out1 = execute_chain([p1, p2])
        out2 = chain_plans([p1, p2]).execute()
        assert np.array_equal(np.asarray(out1.data), np.asarray(out2.data))
        # A plan that was not built from p1's output pattern is rejected.
        stranger = spgemm_plan(
            _int_coo(64, 48, 0.07, 40), c, tile=8, group=2, backend="jnp",
            cache=cache,
        )
        with pytest.raises(ValueError, match="output pattern|A shape"):
            chain_plans([p1, stranger])

    def test_chain_block_output_works_too(self):
        a, b, c = self._abc(36)
        cache = PlanCache()
        p1 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache)
        chain = p1.then(c, cache=cache)
        out = chain.execute()
        ref = _dense(a) @ _dense(b) @ _dense(c)
        np.testing.assert_allclose(out.todense(), ref, rtol=1e-4, atol=1e-4)

    def test_chained_plan_cache_hit_and_counter(self):
        a, b, c = self._abc(44)
        cache = PlanCache()
        p1 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache,
                         output="compact")
        pat = p1.output_pattern()
        q1 = plan_from_structural_pattern(pat, c, tile=8, group=2,
                                          backend="jnp", cache=cache,
                                          output="compact")
        q2 = plan_from_structural_pattern(pat, c, tile=8, group=2,
                                          backend="jnp", cache=cache,
                                          output="compact")
        assert q2 is q1  # memory hit under the chain key
        assert cache.stats.chain_lookups == 2
        assert cache.stats()["chain_lookups"] == 2

    def test_chained_plan_persists(self, tmp_path):
        a, b, c = self._abc(48)
        c1 = PlanCache(disk_dir=str(tmp_path))
        p1 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=c1,
                         output="compact")
        q1 = p1.then(c, cache=c1)
        out1 = q1.execute()
        # Warm restart.
        c2 = PlanCache(disk_dir=str(tmp_path))
        p2 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=c2,
                         output="compact")
        q2 = p2.then(c, cache=c2)
        assert c2.stats.loads == 2  # both stages rehydrated from disk
        out2 = q2.execute()
        assert np.array_equal(np.asarray(out1.data), np.asarray(out2.data))

    def test_empty_intermediate_product(self):
        """A structurally empty intermediate flows zeros through the rest
        of the chain instead of erroring."""
        a = COO(np.array([0]), np.array([0]),
                np.array([1.0], np.float32), (16, 16))
        b = COO(np.array([9]), np.array([0]),
                np.array([1.0], np.float32), (16, 16))
        c = _int_coo(16, 16, 0.2, 52)
        cache = PlanCache()
        p1 = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache,
                         output="compact")
        chain = p1.then(c, cache=cache)
        out = chain.execute()
        assert out.data.size == 0
        assert np.count_nonzero(out.todense()) == 0

    def test_structural_pattern_round_trip(self):
        _, _, _, cmp_ = _pair(60)
        pat = cmp_.output_pattern()
        assert isinstance(pat, StructuralPattern)
        assert pat.nnz == cmp_.compact.nnz
        coo = pat.to_coo()
        # Canonical by construction: strictly ascending (row, col).
        key = coo.row.astype(np.int64) * pat.shape[1] + coo.col
        assert (np.diff(key) > 0).all()


def _dense(coo: COO) -> np.ndarray:
    out = np.zeros(coo.shape, np.float32)
    np.add.at(out, (coo.row, coo.col), coo.val)
    return out
