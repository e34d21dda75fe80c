"""Per-pattern plan autotuner (repro.spgemm.autotune).

Coverage layers:

* the probe primitives are deterministic under an injected fake clock
  (exactly two timer calls per measurement, interleaved repeat order);
* the roofline ranking helpers order candidates by traffic/flops and the
  model-vs-measured agreement metric behaves at its extremes;
* the two-stage search is steered entirely by the fake timer: the model
  pruning always keeps the requested default config, the measured winner
  (tile/group/chunk) is applied to the returned plan, and the recorded
  values/s come from the scripted durations;
* tuned configs are durable: bitwise ``TunedConfig`` round-trips through
  the ``PlanStore`` sidecar and the plan artifact meta, warm restarts
  (fresh caches and a genuinely fresh process) apply the persisted
  config with **zero** probe executions;
* numerics are untouched: tuned plans are bitwise-equal to untuned plans
  built directly at the tuned (tile, group) on the execute /
  execute_batch / pipeline paths, on paper matrices;
* ``REPRO_SPGEMM_CHUNK_BYTES`` still beats a tuned config, and the
  gateway reports per-pattern config provenance.
"""
import os

import numpy as np
import pytest

from repro.core.perfmodel import (
    CPU_XEON_E5_2637,
    roofline_seconds,
    spgemm_schedule_traffic,
)
from repro.core.tuning import best_ms, interleaved_best_ms
from repro.sparse.formats import COO
from repro.sparse.random import random_coo, suite_matrix
from repro.spgemm import PlanCache, SpGEMMGateway, spgemm_plan
from repro.spgemm.autotune import (
    TunedConfig,
    _default_candidates,
    _ranking_agreement,
    autotune_plan,
    probe_run_count,
)
from repro.spgemm.executor import CHUNK_BYTES_ENV, resolve_chunk_bytes


def _int_coo(m, n, density, seed):
    """Small-integer float32 values — exact in f32, so tuned-vs-untuned
    comparisons can demand bitwise equality."""
    coo = random_coo(m, n, density, "uniform", seed=seed)
    rng = np.random.default_rng(seed + 999)
    vals = rng.integers(-4, 5, coo.nnz).astype(np.float32)
    coo.val = np.where(vals == 0, np.float32(1.0), vals)
    return coo


def _mats(seed=7, shape=(96, 96), density=0.06):
    a = _int_coo(shape[0], shape[1], density, seed)
    b = COO(a.col, a.row, a.val, (shape[1], shape[0]))
    return a, b


class FakeTimer:
    """A perf_counter stand-in scripted by per-measurement durations.

    The probe contract is exactly two timer calls per measurement
    (start, stop): every even call pops the next scripted duration and
    advances the clock by it, so measurement k reads ``durations[k]``
    seconds regardless of how long the probed code really ran."""

    def __init__(self, durations):
        self.durations = [float(d) for d in durations]
        self.t = 0.0
        self.calls = 0

    def __call__(self):
        self.calls += 1
        if self.calls % 2 == 0:
            self.t += self.durations.pop(0)
        return self.t


class TestProbePrimitives:
    def test_best_ms_fake_timer(self):
        timer = FakeTimer([0.004, 0.002, 0.003])
        assert best_ms(lambda: 0, 3, timer=timer) == pytest.approx(2.0)
        assert timer.calls == 6  # exactly two per repeat

    def test_interleaved_best_ms_fake_timer(self):
        # Interleaved order: repeat 0 runs fn0 then fn1, repeat 1 again —
        # so the scripted durations land [fn0, fn1, fn0, fn1].
        timer = FakeTimer([0.002, 0.003, 0.001, 0.005])
        got = interleaved_best_ms([lambda: 0, lambda: 0], 2, timer=timer)
        assert got == pytest.approx([1.0, 3.0])
        assert timer.calls == 8

    def test_ranking_agreement_extremes(self):
        assert _ranking_agreement([1.0, 2.0, 3.0], [10.0, 20.0, 30.0]) == 1.0
        assert _ranking_agreement([1.0, 2.0, 3.0], [30.0, 20.0, 10.0]) == 0.0
        # Model ties carry no information: half credit.
        assert _ranking_agreement([1.0, 1.0], [10.0, 20.0]) == 0.5


class TestModelRanking:
    def test_traffic_counts_scale_with_tile(self):
        base = dict(num_triples=100, nnzb_a=40, b_fetches=60, n_panels=10,
                    group=4)
        t8 = spgemm_schedule_traffic(tile=(8, 8, 8), **base)
        t16 = spgemm_schedule_traffic(tile=(16, 16, 16), **base)
        assert t16["flops"] == 8 * t8["flops"]  # 2*triples*bm*bk*bn
        assert t16["bytes"] == 4 * t8["bytes"]  # per-block area x4

    def test_roofline_takes_memory_floor(self):
        dev = CPU_XEON_E5_2637
        flops = dev.peak_flops  # 1s of compute
        tiny = roofline_seconds(flops, 0.0, dev)
        assert tiny == pytest.approx(1.0)
        heavy = roofline_seconds(flops, dev.mem_bandwidth * 10, dev)
        assert heavy == pytest.approx(10.0)  # memory-bound

    def test_default_candidates_include_request(self):
        grid = _default_candidates((16, 16, 16), 2)
        assert ((16, 16, 16), 2) in grid
        assert all(all(8 <= d <= 256 for d in t) for t, _ in grid)
        assert all(g >= 1 for _, g in grid)

    def test_model_device_by_backend_and_device_kind(self, monkeypatch):
        import jax

        from repro.core.perfmodel import TPU_V5E_CHIP
        from repro.spgemm.autotune import _model_device

        assert _model_device("jnp") is CPU_XEON_E5_2637
        assert _model_device("pallas_interpret") is CPU_XEON_E5_2637
        # "pallas" ranks against the attached chip: this host's CPU device
        # has no chip model, which is an error rather than a default.
        with pytest.raises(ValueError, match="device kind"):
            _model_device("pallas")

        class Chip:
            def __init__(self, kind):
                self.device_kind = kind

        monkeypatch.setattr(jax, "devices", lambda: [Chip("TPU v5 lite")])
        assert _model_device("pallas") is TPU_V5E_CHIP
        monkeypatch.setattr(jax, "devices", lambda: [Chip("TPU v4")])
        with pytest.raises(ValueError, match="TPU v4"):
            _model_device("pallas")


class TestSearch:
    """The fake timer steers the whole search deterministically."""

    def test_requested_config_always_survives_pruning(self):
        """model_top_k=1 with a grid where the request ranks last: the
        default must still be probed (it is the winner under a timer that
        makes everything else slow)."""
        a, b = _mats(1)
        cache = PlanCache()
        cands = [((8, 8, 8), 2), ((16, 16, 16), 2), ((32, 32, 32), 2)]
        # Entries = survivors x chunks; model_top_k=1 + forced default ->
        # at most 2 survivors, 1 chunk candidate -> <= 2 measurements per
        # repeat. Scripted durations cover the worst case; leftovers are
        # simply never popped.
        durations = []
        for _ in range(2):  # repeats
            durations += [1.0, 0.001]
        plan = autotune_plan(
            a, b, tile=8, group=2, backend="jnp", cache=cache,
            candidates=cands, chunk_candidates=[None],
            depth_candidates=(2,), model_top_k=1, probe_batch=2,
            repeats=2, timer=FakeTimer(durations),
        )
        cfg = plan.tuned_config
        # If the model's top pick was already (8,8,8), the scripted order
        # flips — accept either, but the requested config must have been
        # measured and the plan's config must be a member of the grid.
        assert (cfg.tile, cfg.group) in cands
        assert cfg.probes > 0
        assert cfg.default_values_per_s > 0  # the default WAS measured

    def test_measured_winner_and_chunk_applied(self):
        """One (tile, group) candidate, two chunk candidates: the faster
        scripted chunk wins and lands on the executor."""
        a, b = _mats(2)
        cache = PlanCache()
        plan = autotune_plan(
            a, b, tile=16, group=2, backend="jnp", cache=cache,
            candidates=[((16, 16, 16), 2)],
            chunk_candidates=[None, 123456],
            depth_candidates=(2,), model_top_k=1, probe_batch=2,
            repeats=1, timer=FakeTimer([0.010, 0.002]),
        )
        cfg = plan.tuned_config
        assert (cfg.tile, cfg.group) == ((16, 16, 16), 2)
        assert cfg.chunk_bytes == 123456
        assert plan._executor._chunk_policy == resolve_chunk_bytes(123456)
        assert plan.report.config_source == "tuned"
        assert plan.report.tuned == cfg.to_meta()
        # values/s computed from the scripted 2 ms winner / 10 ms default.
        assert cfg.values_per_s == pytest.approx(2 / 0.002)
        assert cfg.default_values_per_s == pytest.approx(2 / 0.010)
        assert cfg.speedup == pytest.approx(5.0)

    def test_tuned_depth_steers_pipeline_default(self):
        a, b = _mats(3)
        plan = autotune_plan(
            a, b, tile=16, group=2, backend="jnp", cache=PlanCache(),
            candidates=[((16, 16, 16), 2)], chunk_candidates=[None],
            depth_candidates=(1, 4), model_top_k=1, probe_batch=2,
            repeats=1,
            # chunk stage: 1 measurement; depth stage: depth 1 slow,
            # depth 4 fast.
            timer=FakeTimer([0.002, 0.050, 0.001]),
        )
        assert plan.tuned_config.pipeline_depth == 4
        pipe = plan.pipeline()  # depth=None -> tuned depth
        assert pipe.depth == 4
        pipe.close()

    def test_block_input_restricts_to_chunk_and_depth(self):
        from repro.sparse.convert import to_bcsr, to_bcsv
        from repro.sparse.random import random_block_sparse

        ad = random_block_sparse(64, 64, (16, 16), 0.4, seed=31)
        bd = random_block_sparse(64, 64, (16, 16), 0.4, seed=32)
        ab, bb = to_bcsv(ad, (16, 16), 2), to_bcsr(bd, (16, 16))
        plan = autotune_plan(
            ab, bb, backend="jnp", cache=PlanCache(),
            chunk_candidates=[None], depth_candidates=(2,),
            probe_batch=2, repeats=1, timer=FakeTimer([0.001]),
        )
        cfg = plan.tuned_config
        # Tile/group come from the block formats; only chunk/depth tuned.
        assert cfg.tile == (16, 16, 16) and cfg.group == 2


class TestPersistence:
    CFG = TunedConfig(
        tile=(16, 16, 16), group=2, chunk_bytes=789,
        pipeline_depth=4, values_per_s=1234.5678901234567,
        default_values_per_s=1000.0000000000001, model_rank=1,
        ranking_agreement=2.0 / 3.0, probes=12,
    )

    def test_meta_roundtrip_bitwise(self):
        back = TunedConfig.from_meta(self.CFG.to_meta())
        assert back == self.CFG  # f64 fields bitwise via dataclass eq

    def test_sidecar_roundtrip_bitwise(self, tmp_path):
        key = ("pat", (16, 16, 16), 2, "jnp", None)
        c1 = PlanCache(disk_dir=str(tmp_path))
        c1.tuned_put(key, self.CFG.to_meta())
        assert c1.stats.tuned_stores == 1
        # Fresh cache over the same dir: memory tier empty, disk serves.
        c2 = PlanCache(disk_dir=str(tmp_path))
        meta = c2.tuned_get(key)
        assert meta is not None and c2.stats.tuned_hits == 1
        back = TunedConfig.from_meta(meta, source="persisted")
        assert back == TunedConfig.from_meta(
            self.CFG.to_meta(), source="persisted"
        )
        # values/s floats survive the JSON header bitwise.
        assert back.values_per_s == self.CFG.values_per_s
        assert back.ranking_agreement == self.CFG.ranking_agreement

    def test_tuned_miss_counted(self):
        c = PlanCache()
        assert c.tuned_get(("nope",)) is None
        assert c.stats.tuned_misses == 1

    def test_plan_artifact_carries_tuned_config(self, tmp_path):
        """persist_artifacts/from_artifacts round-trip the tuned config:
        a copied artifact file rehydrates tuned on its own."""
        from repro.spgemm.plan import SpGEMMPlan

        a, b = _mats(4)
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=PlanCache())
        cfg = TunedConfig(
            tile=(16, 16, 16), group=2, chunk_bytes=55555,
            pipeline_depth=3, values_per_s=10.0,
            default_values_per_s=9.0, model_rank=0,
            ranking_agreement=1.0, probes=6,
        )
        plan.apply_tuned_config(cfg)
        arrays, meta = plan.persist_artifacts()
        assert meta["tuned_config"] == cfg.to_meta()
        back = SpGEMMPlan.from_artifacts(
            arrays, meta, backend="jnp",
            a_vals=a.val, b_vals=b.val,
        )
        assert back.tuned_config is not None
        assert back.tuned_config.source == "persisted"
        assert back.tuned_config.chunk_bytes == 55555
        assert back.report.config_source == "persisted"
        assert back._executor._chunk_policy == resolve_chunk_bytes(55555)
        assert back._default_depth() == 3

    def test_warm_restart_zero_probes(self, tmp_path):
        """Fresh cache over the tuned directory: the persisted config is
        applied without a single probe execution."""
        a, b = _mats(5)
        c1 = PlanCache(disk_dir=str(tmp_path))
        tuned = autotune_plan(
            a, b, tile=16, group=2, backend="jnp", cache=c1,
            candidates=[((16, 16, 16), 2), ((8, 8, 8), 2)],
            chunk_candidates=[None], depth_candidates=(2,),
            model_top_k=2, probe_batch=2, repeats=1,
            timer=FakeTimer([0.002, 0.004]),
        )
        cfg = tuned.tuned_config
        before = probe_run_count()
        c2 = PlanCache(disk_dir=str(tmp_path))
        warm = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=c2, autotune=True)
        assert probe_run_count() == before, "warm restart paid probes"
        assert warm.report.config_source == "persisted"
        assert warm.report.schedule_builds == 0
        assert warm.tuned_config == TunedConfig.from_meta(
            cfg.to_meta(), source="persisted"
        )


class TestPrecedence:
    def test_env_override_beats_tuned_config(self, monkeypatch):
        a, b = _mats(6)
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=PlanCache())
        cfg = TunedConfig(
            tile=(16, 16, 16), group=2, chunk_bytes=999999,
            pipeline_depth=2, values_per_s=1.0, default_values_per_s=1.0,
            model_rank=0, ranking_agreement=1.0, probes=2,
        )
        monkeypatch.setenv(CHUNK_BYTES_ENV, str(4096))
        plan.apply_tuned_config(cfg)
        # resolve_chunk_bytes re-reads the env inside set_chunk_bytes:
        # the operator override wins over the tuned value.
        assert plan._executor._chunk_policy[0] == 4096
        assert plan.report.config_source == "env-override"
        assert plan.report.tuned == cfg.to_meta()  # still auditable

    def test_mismatched_config_degrades_to_default(self):
        """A config tuned at a different (tile, group) is *stale*, not
        fatal: it is ignored, recorded as ``config_source="stale-tuned"``,
        and surfaced by the verifier as a ``tuned.stale-config`` warning
        — the plan keeps executing on policy defaults."""
        from repro.analysis.verify import verify_plan

        a, b = _mats(7)
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=PlanCache())
        cfg = TunedConfig(
            tile=(8, 8, 8), group=2, chunk_bytes=None, pipeline_depth=2,
            values_per_s=1.0, default_values_per_s=1.0, model_rank=0,
            ranking_agreement=1.0, probes=0,
        )
        plan.apply_tuned_config(cfg)  # must NOT raise
        assert plan.tuned_config is None
        assert plan.report.tuned is None
        assert plan.report.config_source == "stale-tuned"
        rep = verify_plan(plan)
        assert rep.ok  # a warning, not an error
        stale = [f for f in rep.findings if f.check == "tuned.stale-config"]
        assert len(stale) == 1 and stale[0].severity == "warning"
        # Numerics are untouched by the fallback.
        ref = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                          cache=PlanCache())
        assert np.array_equal(plan.execute().data, ref.execute().data)

    def test_drifted_sidecar_rehydrates_with_fallback(self, tmp_path):
        """Regression: a persisted artifact whose embedded tuned config
        was hand-drifted (tile no longer matching the symbolic facts)
        must rehydrate as a working plan on defaults — the old behavior
        raised out of ``from_artifacts`` and made the artifact
        unloadable."""
        from repro.spgemm.plan import SpGEMMPlan

        a, b = _mats(8)
        a, b = a.sum_duplicates(), b.sum_duplicates()  # canonical order
        plan = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                           cache=PlanCache())
        cfg = TunedConfig(
            tile=(16, 16, 16), group=2, chunk_bytes=4096, pipeline_depth=3,
            values_per_s=2.0, default_values_per_s=1.0, model_rank=0,
            ranking_agreement=1.0, probes=4,
        )
        plan.apply_tuned_config(cfg)
        arrays, meta = plan.persist_artifacts()
        # Hand-drift the sidecar record: claims a tile the plan was
        # never built at.
        meta = dict(meta)
        drifted = dict(meta["tuned_config"])
        drifted["tile"] = [8, 8, 8]
        meta["tuned_config"] = drifted
        back = SpGEMMPlan.from_artifacts(
            arrays, meta, backend="jnp", pattern_key=plan.report.pattern_key,
            a_vals=a.val, b_vals=b.val, a_pattern=a, b_pattern=b,
        )
        assert back.tuned_config is None
        assert back.report.config_source == "stale-tuned"
        assert back._stale_tuned is not None
        assert np.array_equal(back.execute().data, plan.execute().data)


class TestBitwise:
    """Tuned plans never change numerics: results are bitwise-equal to an
    untuned plan built directly at the tuned (tile, group)."""

    @pytest.mark.parametrize("name,scale", [
        ("poisson3Da", 0.004), ("2cubes_sphere", 0.002),
    ])
    def test_tuned_bitwise_on_paper_matrices(self, name, scale):
        a = suite_matrix(name, scale=scale).to_coo().sum_duplicates()
        rng = np.random.default_rng(17)
        v = rng.integers(-4, 5, a.nnz).astype(np.float32)
        a.val = np.where(v == 0, np.float32(1.0), v)
        b = COO(a.col, a.row, a.val, (a.shape[1], a.shape[0]))
        tuned = autotune_plan(
            a, b, tile=16, group=2, backend="jnp", cache=PlanCache(),
            model_top_k=2, probe_batch=2, repeats=1,
            depth_candidates=(2,),
        )
        cfg = tuned.tuned_config
        ref = spgemm_plan(a, b, tile=cfg.tile, group=cfg.group,
                          backend="jnp", cache=PlanCache())
        av = rng.integers(-3, 4, a.nnz).astype(np.float32)
        bv = rng.integers(-3, 4, b.nnz).astype(np.float32)
        c_t, c_r = tuned.execute(av, bv), ref.execute(av, bv)
        assert np.array_equal(c_t.indptr, c_r.indptr)
        assert np.array_equal(c_t.indices, c_r.indices)
        assert np.array_equal(c_t.data, c_r.data)
        # Batched path (the tuned chunk policy reshapes device calls,
        # never values).
        avb = rng.integers(-3, 4, (5, a.nnz)).astype(np.float32)
        bvb = rng.integers(-3, 4, (5, b.nnz)).astype(np.float32)
        for x, y in zip(tuned.execute_batch(avb, bvb),
                        ref.execute_batch(avb, bvb)):
            assert np.array_equal(x.data, y.data)
        # Pipelined path at the tuned depth.
        items = [(avb[i], bvb[i]) for i in range(5)]
        outs_t = list(tuned.execute_stream(iter(items)))
        outs_r = [ref.execute(x, y) for x, y in items]
        for x, y in zip(outs_t, outs_r):
            assert np.array_equal(x.data, y.data)
        # And the tuned result agrees with the dense product of the
        # rebound (av, bv) values — which align with the plan's
        # *canonical* patterns, not the raw input entry order.
        ap, bp = tuned.a_pattern, tuned.b_pattern
        ad = np.zeros(a.shape, np.float32)
        ad[ap.row, ap.col] = av
        bd = np.zeros(b.shape, np.float32)
        bd[bp.row, bp.col] = bv
        np.testing.assert_allclose(
            c_t.todense(), ad @ bd, rtol=1e-6, atol=1e-5)

    def test_sharded_tuned_bitwise(self):
        from repro.launch.mesh import make_shard_mesh

        a, b = _mats(9, shape=(120, 90), density=0.08)
        mesh = make_shard_mesh(1)
        tuned = autotune_plan(
            a, b, tile=8, group=2, backend="jnp", cache=PlanCache(),
            mesh=mesh, candidates=[((8, 8, 8), 2)],
            chunk_candidates=[None, 4096], depth_candidates=(2,),
            probe_batch=2, repeats=1, timer=FakeTimer([0.004, 0.001]),
        )
        assert tuned.tuned_config.chunk_bytes == 4096
        ref = spgemm_plan(a, b, tile=8, group=2, backend="jnp",
                          cache=PlanCache(), mesh=mesh)
        rng = np.random.default_rng(23)
        av = rng.integers(-3, 4, a.nnz).astype(np.float32)
        bv = rng.integers(-3, 4, b.nnz).astype(np.float32)
        assert np.array_equal(tuned.execute(av, bv).data,
                              ref.execute(av, bv).data)


class TestGatewayIntegration:
    def test_register_autotune_and_stats_provenance(self):
        a, b = _mats(10)
        gw = SpGEMMGateway(cache=PlanCache(), start=True, depth=2)
        try:
            plan = gw.register(
                "t0/l0", a, b, tile=16, group=2, backend="jnp",
                autotune={
                    "candidates": [((16, 16, 16), 2)],
                    "chunk_candidates": [None],
                    "depth_candidates": (4,),
                    "probe_batch": 2, "repeats": 1,
                    "timer": FakeTimer([0.001]),
                },
            )
            assert plan.tuned_config is not None
            av = np.asarray(a.val, np.float32)
            bv = np.asarray(b.val, np.float32)
            res = gw.submit("t0/l0", av, bv).wait()
            ref = spgemm_plan(a, b, tile=16, group=2, backend="jnp",
                              cache=PlanCache()).execute(av, bv)
            assert np.array_equal(res.value.data, ref.data)
            st = gw.stats()["patterns"]["t0/l0"]
            assert st["config_source"] == "tuned"
            assert st["tuned"] == plan.tuned_config.to_meta()
            assert st["pipeline_depth"] == 4  # tuned depth beats gateway's
        finally:
            gw.close()

    def test_untuned_pattern_reports_default(self):
        a, b = _mats(11)
        gw = SpGEMMGateway(cache=PlanCache(), start=False, depth=2)
        gw.register("t1/l0", a, b, tile=16, group=2, backend="jnp")
        st = gw.stats()["patterns"]["t1/l0"]
        assert st["config_source"] == "default"
        assert st["tuned"] is None
        assert st["pipeline_depth"] == 2
        gw.close()


AUTOTUNE_PROCESS = """
import os
import numpy as np
from repro.sparse.formats import COO
from repro.sparse.random import suite_matrix
from repro.spgemm import spgemm_plan
from repro.spgemm.autotune import probe_run_count

assert os.environ["REPRO_SPGEMM_PLAN_DIR"]
WARM = {warm}
a = suite_matrix("poisson3Da", scale=0.004).to_coo().sum_duplicates()
rng = np.random.default_rng(0)
v = rng.integers(-4, 5, a.nnz).astype(np.float32)
a.val = np.where(v == 0, np.float32(1.0), v)
b = COO(a.col, a.row, a.val, (a.shape[1], a.shape[0]))
plan = spgemm_plan(
    a, b, tile=16, group=2, backend="jnp",
    autotune={{"model_top_k": 2, "probe_batch": 2, "repeats": 1,
               "depth_candidates": (2,)}},
)
cfg = plan.tuned_config
assert cfg is not None
if WARM:
    assert probe_run_count() == 0, "warm process paid probes"
    assert plan.report.config_source == "persisted"
    assert cfg.source == "persisted"
else:
    assert probe_run_count() == cfg.probes > 0
    assert plan.report.config_source == "tuned"
import json
print("CFG " + json.dumps(cfg.to_meta(), sort_keys=True))
"""


class TestWarmRestartProcess:
    def test_second_process_zero_probes(self, tmp_path, forced_devices):
        """The acceptance scenario with real processes: process 1 searches
        and persists; process 2 — a fresh interpreter — applies the exact
        same TunedConfig with its probe counter still at zero."""
        os.environ["REPRO_SPGEMM_PLAN_DIR"] = str(tmp_path)
        try:
            cold = forced_devices(
                AUTOTUNE_PROCESS.format(warm=False), devices=1)
            warm = forced_devices(
                AUTOTUNE_PROCESS.format(warm=True), devices=1)
        finally:
            del os.environ["REPRO_SPGEMM_PLAN_DIR"]
        get = lambda out: [ln for ln in out.splitlines()
                           if ln.startswith("CFG ")][0]
        cold_cfg, warm_cfg = get(cold), get(warm)
        # Identical except provenance: the warm process loaded, not probed.
        assert cold_cfg.replace('"probed"', '"persisted"') == warm_cfg
