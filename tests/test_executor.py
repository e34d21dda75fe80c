"""Device-resident numeric executor tests: jittable output assembly,
vmap-batched execute_batch, and the supporting cache/report satellites."""
import functools

import numpy as np
import pytest
from _compat_hypothesis import given, settings, st

from repro.core.gustavson import spgemm_gustavson
from repro.data.pipeline import SpGEMMValueStream
from repro.kernels import ref
from repro.sparse.convert import to_bcsr, to_bcsv, to_csr
from repro.sparse.formats import COO, CSR
from repro.sparse.random import random_block_sparse, random_coo
from repro.spgemm import (
    PlanCache,
    SpGEMMPlan,
    schedule_build_count,
    spgemm_plan,
)


def _int_coo(m, n, density, seed):
    """Small-integer float32 values: exact in float32 under any accumulation
    order, so oracle comparisons are bit-for-bit."""
    coo = random_coo(m, n, density, "uniform", seed=seed)
    rng = np.random.default_rng(seed + 999)
    vals = rng.integers(-4, 5, coo.nnz).astype(np.float32)
    coo.val = np.where(vals == 0, np.float32(1.0), vals)
    return coo


def _host_assemble(plan, panels: np.ndarray) -> CSR:
    """The pre-executor host assembly (PR 1's SpGEMMPlan._assemble): scan
    each output panel with np.nonzero and scatter into CSR. Kept here as
    the reference the device-side gather assembly must reproduce."""
    sch = plan.schedule
    m, n = plan.assembly.shape
    bm, bn = plan._bm, plan._bn
    rows_l, cols_l, vals_l = [], [], []
    span = sch.group * bm
    for p in range(sch.n_panels):
        g = int(sch.panel_group[p])
        j = int(sch.panel_bcol[p])
        r0 = g * span
        sub = panels[p][: min(span, m - r0)]
        rr, cc = np.nonzero(sub)
        if rr.size == 0:
            continue
        rows_l.append(rr + r0)
        cols_l.append(cc + j * bn)
        vals_l.append(sub[rr, cc])
    if not rows_l:
        return CSR(np.zeros(m + 1, np.int64), np.zeros(0, np.int32),
                   np.zeros(0, np.float32), (m, n))
    coo = COO(
        np.concatenate(rows_l).astype(np.int32),
        np.concatenate(cols_l).astype(np.int32),
        np.concatenate(vals_l), (m, n),
    )
    return CSR.from_coo(coo)


def _kernel_panels(plan) -> np.ndarray:
    """Run only the scheduled kernel (jnp path) on the plan's staged
    blocks, bypassing the executor's fused assembly."""
    sch = plan.schedule
    return np.asarray(ref.spgemm_scheduled_ref(
        plan._a_blocks, plan._b_blocks,
        sch.a_slot, sch.b_slot, sch.panel, sch.sub_row,
        sch.n_panels, sch.group,
    ))


class TestDeviceAssembly:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 500), group=st.integers(1, 3))
    def test_matches_old_host_assemble(self, seed, group):
        """Device gather assembly == the old np.nonzero host assembly on
        random patterns (todense; the structural CSR additionally keeps
        exact-zero elements of nonzero blocks)."""
        rng = np.random.default_rng(seed)
        m, k, n = rng.integers(20, 90, 3)
        a = _int_coo(int(m), int(k), 0.1, seed)
        b = _int_coo(int(k), int(n), 0.12, seed + 7)
        plan = spgemm_plan(a, b, tile=8, group=group, backend="jnp",
                           cache=PlanCache())
        c_dev = plan.execute()
        c_host = _host_assemble(plan, _kernel_panels(plan))
        assert np.array_equal(c_dev.todense(), c_host.todense())
        # Structural pattern: value-independent, includes the host-
        # assembled (value-dependent) support.
        assert c_dev.nnz == plan.assembly.nnz >= c_host.nnz

    def test_execute_numeric_phase_has_no_host_nonzero(self, monkeypatch):
        """Acceptance guard: after warmup, the numeric phase never calls
        np.nonzero on host (assembly runs inside the jitted executor)."""
        a = _int_coo(64, 48, 0.1, 3)
        b = _int_coo(48, 64, 0.1, 4)
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=PlanCache())
        # Warm both executor jits (blocks path and fused values path):
        # tracing itself may touch np.nonzero inside jax.
        plan.execute()
        plan.execute(a.val, b.val)

        def _forbidden(*args, **kwargs):
            raise AssertionError("np.nonzero called in the numeric phase")

        monkeypatch.setattr(np, "nonzero", _forbidden)
        c = plan.execute(a.val * 2.0, b.val)
        monkeypatch.undo()
        ref_c = spgemm_gustavson(
            to_csr(COO(a.row, a.col, a.val * 2.0, a.shape)), to_csr(b))
        assert np.array_equal(c.todense(), ref_c.todense())

    def test_results_share_precomputed_structure(self):
        a = _int_coo(50, 40, 0.15, 11)
        b = _int_coo(40, 50, 0.15, 12)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache())
        c1, c2 = plan.execute(), plan.execute(a.val, b.val)
        assert c1.indptr is plan.assembly.indptr
        assert c1.indices is c2.indices


def _bits(x) -> np.ndarray:
    """Bit pattern of a float32 array: equality here is bitwise."""
    return np.ascontiguousarray(np.asarray(x, np.float32)).view(np.uint32)


def _host_blocks(plan, a_vals, b_vals):
    """The host ``_rebind`` of fresh values into fresh block arrays: the
    reference the device bind must reproduce bit for bit."""
    return (
        plan._rebind(a_vals, None, plan._a_scatter, plan.report.nnz_a, "a",
                     plan._a_shape, plan._a_dtype),
        plan._rebind(b_vals, None, plan._b_scatter, plan.report.nnz_b, "b",
                     plan._b_shape, plan._b_dtype),
    )


def _bind_index_shapes(jaxpr):
    """Shapes of the index operand of every scatter and gather in a traced
    program, sub-programs (jit, shard_map) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scatter", "gather"):
            found.append((eqn.primitive.name, eqn.invars[1].aval.shape))
        for param in eqn.params.values():
            sub = getattr(param, "jaxpr", param)  # ClosedJaxpr or Jaxpr
            if hasattr(sub, "eqns"):
                found += _bind_index_shapes(sub)
    return found


def _dropped_b_row_pattern(seed):
    """A (40 x 36) and B (36 x 30) with B's first block row (tile 8) empty:
    A's blocks in block column 0 feed no triple, so a shard's slot range
    can leave their elements out."""
    a = _int_coo(40, 36, 0.15, seed)
    b = _int_coo(36, 30, 0.15, seed + 1)
    keep = b.row >= 8
    b = COO(b.row[keep], b.col[keep], b.val[keep], b.shape)
    return a, b


class TestDeviceBind:
    """The device bind scatters each operand's [nnz] values into zeroed
    block arrays; it must equal the host rebind bit for bit."""

    @pytest.mark.parametrize("form", ["single", "batch"])
    def test_matches_host_rebind(self, form):
        a, b = _int_coo(40, 36, 0.12, 3), _int_coo(36, 30, 0.12, 4)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache())
        ex = plan._executor
        rng = np.random.default_rng(5)
        av = rng.standard_normal((3, plan.report.nnz_a)).astype(np.float32)
        bv = rng.standard_normal((3, plan.report.nnz_b)).astype(np.float32)
        if form == "single":
            got = [ex.pipe_stage(av[0], bv[0], mode="values")]
        else:
            ga, gb = ex.pipe_stage(av, bv, mode="batch_values")
            got = zip(np.asarray(ga).reshape((3,) + plan._a_shape),
                      np.asarray(gb).reshape((3,) + plan._b_shape))
        for i, (da, db) in enumerate(got):
            ha, hb = _host_blocks(plan, av[i], bv[i])
            assert np.array_equal(_bits(da), _bits(ha)), (form, i)
            assert np.array_equal(_bits(db), _bits(hb)), (form, i)

    @pytest.mark.parametrize("form", ["values", "batch_values"])
    def test_sharded_matches_host_rebind_and_drops_outside(self, form):
        """One shard whose slot range leaves out A's block-column-0 blocks
        (no triple reads them): their elements get indices past the
        shard's array and are dropped; the rest equals the host rebind."""
        from repro.launch.mesh import make_shard_mesh

        a, b = _dropped_b_row_pattern(7)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache(), mesh=make_shard_mesh(1))
        ex = plan._executor
        flat_a = ex._a_max * 8 * 8
        a_map = np.asarray(ex._a_scatter)
        assert a_map.shape == (1, plan.report.nnz_a)
        assert (a_map >= flat_a).any(), "expected elements to drop"
        assert len(np.unique(a_map)) == a_map.size
        rng = np.random.default_rng(8)
        av = rng.standard_normal((2, plan.report.nnz_a)).astype(np.float32)
        bv = rng.standard_normal((2, plan.report.nnz_b)).astype(np.float32)
        if form == "values":
            ga, gb = ex.pipe_stage(av[0], bv[0], mode="values")
            got = [(np.asarray(ga)[0], np.asarray(gb))]
        else:
            ga, gb = ex.pipe_stage(av, bv, mode="batch_values")
            got = zip(np.asarray(ga)[0], np.asarray(gb))
        for i, (da, db) in enumerate(got):
            ha, hb = _host_blocks(plan, av[i], bv[i])
            assert np.array_equal(_bits(da), _bits(ex._stack_a(ha)[0])), i
            assert np.array_equal(_bits(db), _bits(hb)), i
        # And end to end against the single-device plan.
        single = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                             cache=PlanCache())
        c, c0 = plan.execute(av[0], bv[0]), single.execute(av[0], bv[0])
        assert np.array_equal(_bits(c.data), _bits(c0.data))

    @pytest.mark.parametrize("form", ["single", "batch", "sharded"])
    def test_bind_indexes_nnz_elements_not_slots(self, form):
        """The bind's only indexed op is a scatter whose index operand has
        one entry per value: no slot-wide gather map comes back."""
        import jax
        from repro.launch.mesh import make_shard_mesh
        from repro.spgemm.executor import bind_batch_core, bind_core

        a, b = _int_coo(40, 36, 0.12, 3), _int_coo(36, 30, 0.12, 4)
        mesh = make_shard_mesh(1) if form == "sharded" else None
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache(), mesh=mesh)
        ex = plan._executor
        nnz_a, nnz_b = plan.report.nnz_a, plan.report.nnz_b
        av = np.ones((2, nnz_a), np.float32)
        bv = np.ones((2, nnz_b), np.float32)
        if form == "single":
            traced = [jax.make_jaxpr(functools.partial(
                bind_core, shape=shp))(v[0], sc) for v, sc, shp in (
                    (av, ex._a_scatter, ex.a_shape),
                    (bv, ex._b_scatter, ex.b_shape))]
        elif form == "batch":
            traced = [jax.make_jaxpr(functools.partial(
                bind_batch_core, shape=shp))(v, sc) for v, sc, shp in (
                    (av, ex._a_scatter, ex.a_shape),
                    (bv, ex._b_scatter, ex.b_shape))]
        else:
            traced = [jax.make_jaxpr(ex._fn("bind"))(
                av[:1], bv[0], ex._a_scatter, ex._b_scatter)]
        found = [f for t in traced for f in _bind_index_shapes(t.jaxpr)]
        assert found and {name for name, _ in found} == {"scatter"}
        assert sorted(shape[0] for _, shape in found) == sorted([nnz_a, nnz_b])
        assert tuple(ex._b_scatter.shape) == (nnz_b,)


class TestExecuteBatch:
    @pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
    def test_equals_loop_of_executes(self, backend):
        """execute_batch == a loop of single executes, elementwise and
        bitwise (integer values), on both backends."""
        a = _int_coo(80, 60, 0.1, 21)
        b = _int_coo(60, 70, 0.12, 22)
        plan = spgemm_plan(a, b, tile=16, group=2, backend=backend,
                           cache=PlanCache())
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=5,
                                   integer_values=True, batch=5)
        av, bv = stream.values_batch_at(0)
        cs = plan.execute_batch(av, bv)
        assert len(cs) == 5
        for i, c in enumerate(cs):
            single = plan.execute(av[i], bv[i])
            assert np.array_equal(c.todense(), single.todense()), i

    def test_batch_consumes_single_stream_sequence(self):
        a = _int_coo(30, 30, 0.2, 31)
        b = _int_coo(30, 30, 0.2, 32)
        single = SpGEMMValueStream(a, b, seed=9)
        batched = SpGEMMValueStream(a, b, seed=9, batch=3)
        av, bv = batched.values_batch_at(1)  # steps 3, 4, 5
        for i in range(3):
            sa, sb = single.values_at(3 + i)
            assert np.array_equal(av[i], sa) and np.array_equal(bv[i], sb)
        d = batched.batch_at(0)
        assert d["a_vals"].shape == (3, a.nnz)
        with pytest.raises(ValueError):
            single.values_batch_at(0)  # no batch size anywhere

    def test_schedule_builds_flat_across_batched_executes(self):
        a = _int_coo(60, 60, 0.1, 41)
        b = _int_coo(60, 60, 0.1, 42)
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=PlanCache())
        builds = schedule_build_count()
        executes = plan.report.executes
        rng = np.random.default_rng(0)
        for bsz in (1, 4, 9):
            av = rng.integers(-3, 4, (bsz, a.nnz)).astype(np.float32)
            bv = rng.integers(-3, 4, (bsz, b.nnz)).astype(np.float32)
            plan.execute_batch(av, bv)
        assert schedule_build_count() == builds
        assert plan.report.schedule_builds == 1
        assert plan.report.executes == executes + 14

    def test_empty_pattern(self):
        a = COO(np.zeros(0, np.int32), np.zeros(0, np.int32),
                np.zeros(0, np.float32), (32, 16))
        b = _int_coo(16, 24, 0.2, 3)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache())
        cs = plan.execute_batch(np.zeros((3, 0), np.float32),
                                np.tile(b.val, (3, 1)))
        assert len(cs) == 3
        assert all(c.nnz == 0 and c.shape == (32, 24) for c in cs)
        assert plan.execute_batch(np.zeros((0, 0), np.float32),
                                  np.zeros((0, b.nnz), np.float32)) == []

    def test_after_release_values(self):
        """execute_batch never reads staged values: it works after
        release_values(), while no-arg execute raises."""
        a = _int_coo(40, 30, 0.15, 51)
        b = _int_coo(30, 40, 0.15, 52)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache())
        want = plan.execute().todense()
        plan.release_values()
        with pytest.raises(ValueError, match="released"):
            plan.execute()
        cs = plan.execute_batch(a.val[None], b.val[None])
        assert np.array_equal(cs[0].todense(), want)

    def test_block_plan_batch(self):
        """Block plans batch over packed block arrays."""
        ad = random_block_sparse(64, 64, (16, 16), 0.4, seed=61)
        bd = random_block_sparse(64, 64, (16, 16), 0.4, seed=62)
        a, b = to_bcsv(ad, (16, 16), 2), to_bcsr(bd, (16, 16))
        plan = spgemm_plan(a, b, backend="jnp", cache=PlanCache())
        av = np.stack([a.blocks, a.blocks * 2.0])
        bv = np.stack([b.blocks, b.blocks])
        cs = plan.execute_batch(av, bv)
        ref64 = ad.astype(np.float64) @ bd.astype(np.float64)
        np.testing.assert_allclose(cs[0].todense(), ref64, rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(cs[1].todense(), 2.0 * ref64, rtol=1e-4,
                                   atol=1e-4)

    def test_shape_validation(self):
        a = _int_coo(40, 30, 0.15, 71)
        b = _int_coo(30, 40, 0.15, 72)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache())
        with pytest.raises(ValueError, match="a_vals"):
            plan.execute_batch(np.zeros((2, a.nnz + 1), np.float32),
                               np.zeros((2, b.nnz), np.float32))
        with pytest.raises(ValueError, match="b_vals"):
            plan.execute_batch(np.zeros((2, a.nnz), np.float32),
                               np.zeros((3, b.nnz), np.float32))


class TestLazyReport:
    def test_from_blocks_report_is_lazy(self):
        ad = random_block_sparse(64, 64, (16, 16), 0.4, seed=81)
        bd = random_block_sparse(64, 64, (16, 16), 0.4, seed=82)
        a, b = to_bcsv(ad, (16, 16), 2), to_bcsr(bd, (16, 16))
        plan = SpGEMMPlan.from_blocks(a, b, backend="jnp")
        rep = plan.report
        # Unresolved until read: the uncached shim path pays neither the
        # pattern digest nor the count_nonzero scans.
        assert callable(rep._pattern_key)
        assert callable(rep._nnz_a) and callable(rep._nnz_b)
        plan.execute()  # numeric phase must not force them
        plan.execute(a.blocks, b.blocks)  # nor the shim's value rebind
        assert callable(rep._nnz_a) and callable(rep._pattern_key)
        assert rep.nnz_a == int(np.count_nonzero(a.blocks))
        d = rep.as_dict()
        assert isinstance(d["pattern_key"], str) and len(d["pattern_key"])
        assert d["nnz_b"] == int(np.count_nonzero(b.blocks))

    def test_lazy_nnz_pins_no_memory_past_release(self):
        """Unread nnz thunks read the plan's staged blocks (no operand
        closure): resolving after release_values raises, while the
        pattern digest (index arrays only) still resolves."""
        ad = random_block_sparse(64, 64, (16, 16), 0.4, seed=83)
        bd = random_block_sparse(64, 64, (16, 16), 0.4, seed=84)
        plan = SpGEMMPlan.from_blocks(
            to_bcsv(ad, (16, 16), 2), to_bcsr(bd, (16, 16)), backend="jnp")
        plan.release_values()
        with pytest.raises(ValueError, match="released"):
            plan.report.nnz_a
        assert isinstance(plan.report.pattern_key, str)

    def test_element_plan_report_is_concrete(self):
        a = _int_coo(40, 30, 0.15, 91)
        b = _int_coo(30, 40, 0.15, 92)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache())
        assert plan.report.nnz_a == a.nnz and plan.report.nnz_b == b.nnz
        assert isinstance(plan.report.pattern_key, str)


class TestBatchChunkPolicy:
    def _executor(self, seed=0):
        a = _int_coo(48, 48, 0.15, seed)
        b = _int_coo(48, 48, 0.15, seed + 1)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache())
        return plan._executor

    def test_default_policy_is_backend_table(self):
        from repro.spgemm.executor import _CHUNK_POLICY, resolve_chunk_bytes
        import jax
        assert resolve_chunk_bytes() == _CHUNK_POLICY[jax.default_backend()]

    def test_backend_without_policy_row_raises(self, monkeypatch):
        import jax

        from repro.spgemm.executor import resolve_chunk_bytes
        monkeypatch.setattr(jax, "default_backend", lambda: "metal")
        with pytest.raises(ValueError, match="metal"):
            resolve_chunk_bytes()

    def test_constructor_arg_scales_chunk(self):
        from repro.spgemm.executor import SpGEMMExecutor
        a = _int_coo(48, 48, 0.15, 201)
        b = _int_coo(48, 48, 0.15, 202)
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache())
        ex = plan._executor
        per_set = 4 * ex._per_set_rows * ex._bn
        # Explicit knobs still work (back-compat call signature)...
        assert ex.batch_chunk(small_set_bytes=per_set - 1) == 1
        assert ex.batch_chunk(small_set_bytes=per_set,
                              cache_bytes=3 * per_set) == 3
        # ...and the constructor arg sets the same policy as default.
        tight = SpGEMMExecutor(
            schedule=plan.schedule, assembly=plan.assembly, backend="jnp",
            a_scatter=plan._a_scatter, b_scatter=plan._b_scatter,
            a_shape=plan._a_shape, b_shape=plan._b_shape,
            chunk_bytes=per_set - 1,
        )
        assert tight.batch_chunk() == 1

    def test_env_var_overrides_constructor(self, monkeypatch):
        from repro.spgemm.executor import CHUNK_BYTES_ENV, resolve_chunk_bytes
        monkeypatch.setenv(CHUNK_BYTES_ENV, "1024")
        per_set, cache_bytes = resolve_chunk_bytes(chunk_bytes=1 << 30)
        assert per_set == 1024  # env wins over the constructor arg
        assert cache_bytes >= per_set
        ex = self._executor(203)
        if 4 * ex._per_set_rows * ex._bn > 1024:
            assert ex.batch_chunk() == 1
        monkeypatch.setenv(CHUNK_BYTES_ENV, "0")
        with pytest.raises(ValueError, match="chunk bytes"):
            resolve_chunk_bytes()

    def test_env_var_changes_plan_batching(self, monkeypatch):
        """A tiny budget makes execute_batch run one set per device call
        without changing results."""
        from repro.spgemm.executor import CHUNK_BYTES_ENV
        a = _int_coo(60, 50, 0.12, 211)
        b = _int_coo(50, 60, 0.12, 212)
        want = None
        for env in (None, "1"):
            if env is None:
                monkeypatch.delenv(CHUNK_BYTES_ENV, raising=False)
            else:
                monkeypatch.setenv(CHUNK_BYTES_ENV, env)
            plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                               cache=PlanCache())
            if env is not None:
                assert plan._executor.batch_chunk() == 1
            av = np.stack([a.val, a.val * 2.0])
            bv = np.stack([b.val, b.val])
            got = [c.todense() for c in plan.execute_batch(av, bv)]
            if want is None:
                want = got
            else:
                assert all(np.array_equal(g, w) for g, w in zip(got, want))


class TestCacheStats:
    def test_stats_callable_snapshot(self):
        cache = PlanCache()
        a = _int_coo(40, 40, 0.15, 301)
        b = _int_coo(40, 40, 0.15, 302)
        p = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache)
        spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache)
        s = cache.stats()
        assert s["hits"] == 1 and s["misses"] == 1
        assert s["resident_plans"] == 1
        assert s["resident_bytes"] >= p.host_nbytes() > 0
        assert s["lookups"] == 2 and s["hit_rate"] == 0.5
        # Attribute access (the pre-existing surface) still works.
        assert cache.stats.hits == 1
        cache.clear()
        assert cache.stats()["resident_plans"] == 0

    def test_eviction_updates_residency(self):
        cache = PlanCache(capacity=1)
        for seed in (311, 322):
            a = _int_coo(40, 40, 0.15, seed)
            b = _int_coo(40, 40, 0.15, seed + 1)
            spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache)
        s = cache.stats()
        assert s["evictions"] == 1 and s["resident_plans"] == 1

    def test_report_surfaces_cache_stats(self):
        """The serving counters live on the cache alone: ``stats()``
        counts the miss and the hit, and a plan's report keeps no copy."""
        cache = PlanCache()
        a = _int_coo(40, 40, 0.15, 331)
        b = _int_coo(40, 40, 0.15, 332)
        p = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache)
        assert cache.stats()["misses"] == 1
        assert cache.stats()["hits"] == 0
        q = spgemm_plan(a, b, tile=8, group=2, backend="jnp", cache=cache)
        assert q is p
        assert cache.stats()["hits"] == 1 and cache.stats()["misses"] == 1
        assert p.report.cache_hits == 1
        assert "cache_stats" not in p.report.as_dict()


class TestPlanCacheBytes:
    def _plan(self, seed, cache):
        a = _int_coo(64, 64, 0.15, seed)
        b = _int_coo(64, 64, 0.15, seed + 1)
        return spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=cache)

    def test_host_nbytes_positive_and_shrinks_nothing(self):
        plan = self._plan(101, PlanCache())
        n = plan.host_nbytes()
        assert n > 0
        plan.release_values()
        assert 0 < plan.host_nbytes() < n

    def test_max_bytes_evicts_lru(self):
        probe = self._plan(111, PlanCache())
        budget = int(probe.host_nbytes() * 1.5)
        cache = PlanCache(max_bytes=budget)
        p1 = self._plan(111, cache)
        p2 = self._plan(222, cache)  # over budget -> evicts p1
        assert len(cache) == 1
        assert cache.stats.evictions == 1
        assert cache.total_bytes <= budget
        # p2 (most recent) survives even if it alone busts the budget.
        small = PlanCache(max_bytes=1)
        p3 = self._plan(333, small)
        assert len(small) == 1
        p3b = self._plan(333, small)
        assert p3b is p3

    def test_count_cap_still_applies(self):
        cache = PlanCache(capacity=2)
        plans = [self._plan(s, cache) for s in (211, 222, 233)]
        assert len(cache) == 2
        assert cache.stats.evictions == 1
        with pytest.raises(ValueError):
            PlanCache(max_bytes=0)
