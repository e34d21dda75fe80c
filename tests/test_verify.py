"""Static plan verifier: pristine plans pass, injected faults are caught.

Two halves, mirroring the verifier's contract:

* **Soundness** — every plan the builders produce (element, block,
  sharded at 1/2/4/8 shards, tuned, disk-rehydrated) verifies clean, and
  ``spgemm_plan(..., validate="deep")`` returns normally on all of them.
* **Completeness** — for each invariant family, a targeted mutation of a
  pristine plan's symbolic arrays must produce an error finding of the
  expected check class (hypothesis drives the mutation positions), and a
  corrupted-but-digest-valid disk artifact must fail verification inside
  the loader and fall back to a clean symbolic rebuild — never execute.
"""
import dataclasses
import glob
import json
import os

import numpy as np
import pytest
from _compat_hypothesis import given, settings, st

from repro.analysis.verify import (
    PlanVerificationError,
    check_assembly,
    check_assembly_runs,
    check_batch_races,
    check_schedule,
    check_shard_partition,
    verify_plan,
)
from repro.analysis.kernel_lint import lint_kernel_module, lint_plan_kernel_specs
from repro.sparse.convert import to_bcsr, to_bcsv
from repro.sparse.random import random_block_sparse, random_coo
from repro.spgemm import PlanCache, spgemm_plan


def _mats(seed=0, m=96, n=80, k=72, density=0.06):
    a = random_coo(m, k, density, "uniform", seed=seed).sum_duplicates()
    b = random_coo(k, n, density, "uniform", seed=seed + 1).sum_duplicates()
    return a, b


def _element_plan(**kw):
    a, b = _mats()
    return spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                       cache=PlanCache(), **kw)


def _block_plan(**kw):
    ad = random_block_sparse(128, 128, (32, 32), 0.3, seed=3)
    bd = random_block_sparse(128, 128, (32, 32), 0.3, seed=4)
    return spgemm_plan(to_bcsv(ad, (32, 32), 2), to_bcsr(bd, (32, 32)),
                       backend="jnp", cache=PlanCache(), **kw)


def _checks(findings):
    return {f.check for f in findings if f.severity == "error"}


class TestPristinePlansVerifyClean:
    def test_element_plan(self):
        plan = _element_plan()
        rep = verify_plan(plan)
        assert rep.ok, rep.summary()
        assert rep.plan_kind == "element" and not rep.sharded
        assert lint_plan_kernel_specs(plan) == []

    def test_block_plan(self):
        plan = _block_plan()
        rep = verify_plan(plan)
        assert rep.ok, rep.summary()
        assert rep.plan_kind == "block"
        assert lint_plan_kernel_specs(plan) == []

    def test_sharded_plan_single_device(self):
        from repro.launch.mesh import make_shard_mesh

        a, b = _mats(2, m=128)
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=PlanCache(), mesh=make_shard_mesh(1))
        rep = verify_plan(plan)
        assert rep.ok, rep.summary()
        assert rep.sharded

    @pytest.mark.parametrize("shards", [2, 4, 8])
    def test_sharded_plans_forced_devices(self, forced_devices, shards):
        out = forced_devices(f"""
from repro.launch.mesh import make_shard_mesh
from repro.sparse.random import random_coo
from repro.spgemm import PlanCache, spgemm_plan
from repro.analysis.verify import verify_plan

a = random_coo(160, 96, 0.05, "uniform", seed=0).sum_duplicates()
b = random_coo(96, 112, 0.05, "uniform", seed=1).sum_duplicates()
plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                   cache=PlanCache(), mesh=make_shard_mesh({shards}),
                   validate="deep")
rep = verify_plan(plan)
assert rep.ok, rep.summary()
assert rep.sharded and plan.n_shards == {shards}
print("SHARDED-VERIFY-OK")
""")
        assert "SHARDED-VERIFY-OK" in out

    def test_tuned_plan(self):
        from repro.spgemm.autotune import TunedConfig

        plan = _element_plan()
        plan.apply_tuned_config(TunedConfig(
            tile=(8, 8, 8), group=2, chunk_bytes=55555, pipeline_depth=3,
            values_per_s=10.0, default_values_per_s=9.0, model_rank=0,
            ranking_agreement=1.0, probes=6,
        ))
        rep = verify_plan(plan)
        assert rep.ok, rep.summary()

    def test_rehydrated_plan(self, tmp_path):
        a, b = _mats(7)
        spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                    cache=PlanCache(disk_dir=str(tmp_path)))
        warm = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=PlanCache(disk_dir=str(tmp_path)),
                           validate="deep")
        assert warm.report.load_hits >= 1
        assert verify_plan(warm).ok

    def test_kernel_module_lint_clean(self):
        assert lint_kernel_module() == []

    def test_deep_validate_all_return_paths(self, tmp_path):
        a, b = _mats(9)
        cache = PlanCache(disk_dir=str(tmp_path))
        fresh = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                            cache=cache, validate="deep",
                            pattern_token="t/deep")
        hit = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                          cache=cache, validate="deep",
                          pattern_token="t/deep")
        assert hit is fresh
        blk = _block_plan(validate="deep")
        assert blk.schedule.num_triples > 0
        with pytest.raises(ValueError, match="validate"):
            spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                        cache=PlanCache(), validate="shallow")


class TestKernelSmemBudget:
    """The five int32 scalar-prefetch schedule arrays must fit SMEM."""

    @staticmethod
    def _dense_plan(n):
        # Fully dense 8x8 blocks: (n/8)^3 triples.
        rng = np.random.default_rng(n)
        a = rng.standard_normal((n, n)).astype(np.float32)
        return spgemm_plan(a, a, tile=8, group=2, backend="jnp",
                           cache=PlanCache())

    def test_schedule_under_smem_is_clean(self):
        from repro.core.perfmodel import (
            SCHEDULE_SMEM_BYTES_PER_TRIPLE, TPU_SMEM_BYTES)

        plan = self._dense_plan(160)  # 8,000 triples
        t = plan.schedule.num_triples
        assert t * SCHEDULE_SMEM_BYTES_PER_TRIPLE < TPU_SMEM_BYTES
        assert "kernel.smem-schedule" not in _checks(
            lint_plan_kernel_specs(plan))

    def test_schedule_over_smem_is_an_error(self, monkeypatch):
        """A staged kernel call over the per-call budget is an error: the
        plan's one call of 8,000 triples, judged against a budget of
        5,000 set after it was built."""
        from repro.core import perfmodel

        plan = self._dense_plan(160)  # 8,000 triples, one call
        assert plan.report.kernel_calls == 1
        monkeypatch.setattr(perfmodel, "SCHEDULE_TRIPLES_PER_CALL", 5_000)
        smem = [f for f in lint_plan_kernel_specs(plan)
                if f.check == "kernel.smem-schedule"]
        assert len(smem) == 1 and smem[0].severity == "error"
        assert "8000 triples" in smem[0].message

    def test_schedule_over_one_call_is_split_and_clean(self):
        """64,000 triples are more than one call's SMEM holds: the
        executor runs them as two calls, each within the budget."""
        from repro.core.perfmodel import (
            SCHEDULE_SMEM_BYTES_PER_TRIPLE, SCHEDULE_TRIPLES_PER_CALL,
            TPU_SMEM_BYTES)

        plan = self._dense_plan(320)  # 64,000 triples
        t = plan.schedule.num_triples
        assert t * SCHEDULE_SMEM_BYTES_PER_TRIPLE > TPU_SMEM_BYTES
        assert plan.report.kernel_calls == 2
        calls = [piece[0].shape[0] for piece in plan._executor._sched]
        assert sum(calls) == t and max(calls) <= SCHEDULE_TRIPLES_PER_CALL
        assert "kernel.smem-schedule" not in _checks(
            lint_plan_kernel_specs(plan))


class TestScheduleFaultInjection:
    """Each mutation class must be detected by its check family."""

    def _plan(self):
        return _element_plan()

    def _nnzb(self, plan):
        return int(plan._a_shape[0]), int(plan._b_shape[0])

    def _run(self, plan, schedule):
        findings = []
        na, nb = self._nnzb(plan)
        check_schedule(schedule, na, nb, findings)
        return _checks(findings)

    @given(pos=st.integers(min_value=0, max_value=10 ** 9))
    @settings(max_examples=12, deadline=None)
    def test_out_of_bounds_a_slot(self, pos):
        plan = self._plan()
        s = plan.schedule
        na, _ = self._nnzb(plan)
        a_slot = s.a_slot.copy()
        a_slot[pos % len(a_slot)] = na  # one past the last real block
        got = self._run(plan, dataclasses.replace(s, a_slot=a_slot))
        assert "schedule.a-slot-bounds" in got

    @given(pos=st.integers(min_value=0, max_value=10 ** 9))
    @settings(max_examples=12, deadline=None)
    def test_out_of_bounds_panel(self, pos):
        plan = self._plan()
        s = plan.schedule
        panel = s.panel.copy()
        panel[pos % len(panel)] = s.n_panels  # the write-only dummy slot
        mut = dataclasses.replace(s, panel=panel)
        assert "schedule.panel-bounds" in self._run(plan, mut)

    @given(pos=st.integers(min_value=1, max_value=10 ** 9))
    @settings(max_examples=12, deadline=None)
    def test_start_flag_corruption(self, pos):
        plan = self._plan()
        s = plan.schedule
        start = s.start.copy()
        i = pos % len(start)
        start[i] = 1 - start[i]
        got = self._run(plan, dataclasses.replace(s, start=start))
        assert "schedule.start-flags" in got

    def test_split_panel_run(self):
        """A panel revisited in two separate runs (the revisit hazard the
        contiguity rule exists for) is caught."""
        plan = self._plan()
        s = plan.schedule
        if s.num_triples < 3 or s.n_panels < 2:
            pytest.skip("schedule too small to split a run")
        panel = s.panel.copy()
        start = s.start.copy()
        # Re-target the last triple at the first panel: panel 0 now has a
        # second, disjoint run at the end of the schedule.
        panel[-1] = panel[0]
        start[-1] = 1
        got = self._run(
            plan, dataclasses.replace(s, panel=panel, start=start)
        )
        assert "schedule.panel-contiguity" in got or \
            "schedule.panel-coverage" in got

    def test_unsorted_panel_keys(self):
        plan = self._plan()
        s = plan.schedule
        if s.n_panels < 2:
            pytest.skip("need two panels")
        pg = s.panel_group.copy()
        pb = s.panel_bcol.copy()
        pg[[0, -1]] = pg[[-1, 0]]
        pb[[0, -1]] = pb[[-1, 0]]
        got = self._run(
            plan, dataclasses.replace(s, panel_group=pg, panel_bcol=pb)
        )
        assert "schedule.panel-order" in got


class TestAssemblyFaultInjection:
    def _fixture(self):
        plan = _element_plan()
        return plan, plan.schedule, plan.assembly, (plan._bm, plan._bn)

    def _run(self, schedule, assembly, block_shape):
        findings = []
        check_assembly(schedule, assembly, block_shape, findings)
        return _checks(findings)

    @given(pos=st.integers(min_value=0, max_value=10 ** 9))
    @settings(max_examples=12, deadline=None)
    def test_duplicated_gather_index(self, pos):
        _, s, asm, bs = self._fixture()
        gather = np.asarray(asm.gather).copy()
        i = pos % (len(gather) - 1)
        gather[i] = gather[i + 1]
        mut = dataclasses.replace(asm, gather=gather)
        assert "assembly.gather-duplicate" in self._run(s, mut, bs)

    @given(pos=st.integers(min_value=0, max_value=10 ** 9))
    @settings(max_examples=12, deadline=None)
    def test_pad_panel_read(self, pos):
        """A gather index pointing into the dummy pad panel's flat range
        (>= n_panels*group*bm*bn) must be flagged as a pad read."""
        _, s, asm, bs = self._fixture()
        bm, bn = bs
        flat = s.n_panels * s.group * bm * bn
        gather = np.asarray(asm.gather).copy()
        gather[pos % len(gather)] = flat + pos % (s.group * bm * bn)
        mut = dataclasses.replace(asm, gather=gather)
        assert "assembly.pad-panel-read" in self._run(s, mut, bs)

    def test_indptr_corruption(self):
        _, s, asm, bs = self._fixture()
        indptr = np.asarray(asm.indptr).copy()
        indptr[len(indptr) // 2] += 1
        mut = dataclasses.replace(asm, indptr=indptr)
        got = self._run(s, mut, bs)
        assert got & {"assembly.indptr-monotone", "assembly.indptr-total",
                      "assembly.column-order"}

    def test_unsorted_columns(self):
        plan, s, asm, bs = self._fixture()
        indptr = np.asarray(asm.indptr)
        widths = np.diff(indptr)
        rows = np.nonzero(widths >= 2)[0]
        if not len(rows):
            pytest.skip("no row with 2+ nnz")
        lo = int(indptr[rows[0]])
        indices = np.asarray(asm.indices).copy()
        indices[[lo, lo + 1]] = indices[[lo + 1, lo]]
        mut = dataclasses.replace(asm, indices=indices)
        assert "assembly.column-order" in self._run(s, mut, bs)

    def test_batch_race_from_panel_aliasing(self):
        """A panel id beyond the dummy slot collides with the next batch
        element's slot range — the exact write-write race 'parallel'
        semantics would miscompile. check_batch_races must prove it."""
        plan = _element_plan()
        s = plan.schedule
        panel = s.panel.copy()
        panel[0] = s.n_panels + 1  # lands in element b+1's slot 0
        findings = []
        check_batch_races(
            dataclasses.replace(s, panel=panel), findings, bsz=2
        )
        assert _checks(findings) & {"races.batch.padded-panel-bounds",
                                    "races.batch.cross-element"}

    def test_verify_plan_catches_in_place_corruption(self):
        plan = _element_plan()
        gather = np.asarray(plan.assembly.gather).copy()
        gather[0] = gather[1]
        plan.assembly = dataclasses.replace(plan.assembly, gather=gather)
        rep = verify_plan(plan)
        assert not rep.ok
        with pytest.raises(PlanVerificationError):
            rep.raise_if_failed()


class TestRunTableFaultInjection:
    """Family 7: the run-wise assembly's table against the map it places."""

    def _plan(self):
        a, b = _mats(5, m=160, n=150, k=120, density=0.08)
        return spgemm_plan(a, b, tile=16, group=2, backend="pallas_interpret",
                           cache=PlanCache(), output="compact")

    def _run(self, plan, runs):
        findings = []
        check_assembly_runs(runs, plan.compact.gather, findings)
        return _checks(findings)

    def _last_item_of_a_block(self, runs):
        """An item whose runs end its block, with at least two runs."""
        blk, end = runs.item_block, runs.item_end
        last = np.flatnonzero(np.append(blk[1:] != blk[:-1], True))
        begin = np.where(np.append(False, blk[1:] == blk[:-1]),
                         np.append(0, end[:-1]), 0)
        return int(last[np.argmax(end[last] - begin[last] >= 2)])

    def test_pristine_plan_verifies_with_runs(self):
        plan = self._plan()
        assert plan._executor.assembly_runs is not None
        rep = verify_plan(plan)
        assert rep.ok, rep.summary()
        assert "runs" in rep.checks_run
        assert self._run(plan, plan._executor.assembly_runs) == set()

    def test_dropped_run(self):
        plan = self._plan()
        runs = plan._executor.assembly_runs
        i = self._last_item_of_a_block(runs)
        end = runs.item_end.copy()
        end[i] -= 1  # the block's last run is no item's any more
        got = self._run(plan, dataclasses.replace(runs, item_end=end))
        assert got == {"runs.dropped"}

    def test_duplicated_run(self):
        plan = self._plan()
        runs = plan._executor.assembly_runs
        i = self._last_item_of_a_block(runs)
        j = int(runs.item_end[i]) - 1
        table = runs.table.copy()
        b, w = int(runs.item_block[i]), runs.width
        table[b, [j, w + j]] = table[b, [j - 1, w + j - 1]]
        got = self._run(plan, dataclasses.replace(runs, table=table))
        assert "runs.duplicate" in got and "runs.dropped" in got

    def test_verify_plan_catches_a_planted_run(self):
        plan = self._plan()
        runs = plan._executor.assembly_runs
        end = runs.item_end.copy()
        end[self._last_item_of_a_block(runs)] -= 1
        plan._executor.assembly_runs = dataclasses.replace(runs, item_end=end)
        rep = verify_plan(plan)
        assert "runs.dropped" in _checks(rep.findings)
        with pytest.raises(PlanVerificationError):
            rep.raise_if_failed()


class TestShardFaultInjection:
    def _sharded_plan(self):
        from repro.launch.mesh import make_shard_mesh

        a, b = _mats(11, m=160)
        return spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=PlanCache(), mesh=make_shard_mesh(1))

    def test_overlapping_shard_bounds(self):
        plan = self._sharded_plan()
        shards = plan._shards
        if not shards:
            pytest.skip("empty sharded plan")
        sh = shards[0]
        # Stretch shard 0 one group past its end: with >1 shards the
        # ranges now overlap; with 1 shard the span exceeds n_groups.
        bad = dataclasses.replace(sh, group_hi=sh.group_hi + 1)
        object.__setattr__(plan, "_shards", [bad] + list(shards[1:]))
        findings = []
        check_shard_partition(plan, findings)
        got = _checks(findings)
        assert got & {"shards.contiguity", "shards.coverage",
                      "shards.bounds", "shards.rebase",
                      "shards.triple-span", "shards.panel-span"}


class TestCorruptedArtifactNeverExecutes:
    """validate="deep" + a digest-valid-but-corrupt disk artifact: the
    loader's verification must fail, count a load_failure, and fall back
    to a clean symbolic rebuild."""

    def _corrupt_artifact(self, store_dir):
        """Duplicate one assembly gather index inside the (single) stored
        artifact and re-sign the payload digest, so every integrity
        check in PlanStore.load still passes."""
        from repro.spgemm.persist import _META_KEY, _payload_digest

        [path] = glob.glob(os.path.join(store_dir, "*.plan.npz"))
        with np.load(path, allow_pickle=False) as npz:
            arrays = {n: npz[n].copy() for n in npz.files if n != _META_KEY}
            header = json.loads(bytes(np.asarray(npz[_META_KEY])).decode())
        gather = arrays["asm.gather"]
        assert len(gather) >= 2
        gather[0] = gather[1]
        header["digest"] = _payload_digest(arrays, header["meta"])
        payload = dict(arrays)
        payload[_META_KEY] = np.frombuffer(
            json.dumps(header).encode(), np.uint8
        )
        with open(path, "wb") as f:
            np.savez(f, **payload)

    def test_deep_validate_rejects_and_rebuilds(self, tmp_path):
        a, b = _mats(13)
        spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                    cache=PlanCache(disk_dir=str(tmp_path)))
        self._corrupt_artifact(str(tmp_path))
        cache = PlanCache(disk_dir=str(tmp_path))
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=cache, validate="deep")
        stats = cache.stats()
        assert stats["load_failures"] == 1, \
            "corrupted artifact should fail loader-side verification"
        assert plan.report.schedule_builds == 1, \
            "must fall back to a fresh symbolic build"
        assert verify_plan(plan).ok

    def test_without_deep_validate_corruption_loads(self, tmp_path):
        """Control: the store's digest alone cannot catch a re-signed
        corruption — that is exactly the gap validate='deep' closes."""
        a, b = _mats(13)
        spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                    cache=PlanCache(disk_dir=str(tmp_path)))
        self._corrupt_artifact(str(tmp_path))
        cache = PlanCache(disk_dir=str(tmp_path))
        plan = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                           cache=cache)
        assert cache.stats()["load_failures"] == 0
        assert plan.report.load_hits >= 1
        rep = verify_plan(plan)
        assert not rep.ok and "assembly.gather-duplicate" in _checks(
            rep.findings
        )


class TestStoreAudit:
    def test_orphaned_alias_reported_and_pruned(self, tmp_path):
        from repro.spgemm.persist import PlanStore

        store = PlanStore(str(tmp_path))
        k_live, k_dead = ("live", 1), ("dead", 2)
        arrays = {"x": np.arange(4, dtype=np.int32)}
        store.save(k_live, arrays, {"kind": "t"})
        store.save(k_dead, arrays, {"kind": "t"})
        store.alias_put("tok-live", repr(k_live))
        store.alias_put("tok-dead", repr(k_dead))
        os.unlink(store.path_for(k_dead))

        assert store.alias_get("tok-live") == repr(k_live)
        assert store.alias_get("tok-dead") is None, \
            "an alias whose target file is gone must be a miss"
        report = store.audit()
        assert report["orphaned"] == ["tok-dead"] and report["pruned"]
        assert report["files"] == 1
        clean = store.audit()
        assert clean["orphaned"] == [] and clean["aliases"] == 1

    def test_audit_clean_store(self, tmp_path):
        from repro.spgemm.persist import PlanStore

        store = PlanStore(str(tmp_path))
        report = store.audit()
        assert report == {"files": 0, "bytes": 0, "aliases": 0,
                          "orphaned": [], "pruned": False}
