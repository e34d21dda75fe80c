"""Kernel microbenchmarks (interpret/jnp on CPU — correctness-scale only;
wall-times here are NOT TPU numbers, the roofline report covers those).

Reports the plan-level reuse metrics that determine TPU performance
(triples, B-fetch elision / block OMAR, arithmetic intensity) via the
plan/execute API, plus the amortization the API exists for: plan-build
time vs numeric-only execute time on the same pattern.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks.common import timeit
from repro.data.pipeline import SpGEMMValueStream
from repro.kernels import ops
from repro.sparse.convert import to_bcsr, to_bcsv
from repro.sparse.formats import COO
from repro.sparse.random import random_block_sparse, suite_matrix
from repro.spgemm import PlanCache, spgemm_plan
from repro.spgemm.persist import PlanStore

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# (matrix, scale, tile, group, batch) for the sharded section: sizes where
# the per-shard working set drops under the batch-fusion knee, so sharding
# buys both parallel shards and bigger fused chunks.
_SHARDED_CASES = (
    ("poisson3Da", 0.03, 16, 4, 32),
    ("2cubes_sphere", 0.008, 16, 4, 32),
)


def run(quiet: bool = False, devices: int = 0, pipeline_depths=(1, 2, 4)):
    data = {}
    print("kernels,case,triples,b_fetches,block_omar_pct,flops,"
          "bytes_streamed,arith_intensity,plan_ms,execute_ms")
    for (m, k, n, da, db, g) in [
        (512, 512, 512, 0.2, 0.2, 2),
        (1024, 512, 1024, 0.1, 0.15, 4),
        (512, 1024, 512, 0.3, 0.3, 8),
    ]:
        bm = bk = bn = 128
        ad = random_block_sparse(m, k, (bm, bk), da, seed=1)
        bd = random_block_sparse(k, n, (bk, bn), db, seed=2)
        cache = PlanCache()

        def build_plan():
            cache.clear()
            return spgemm_plan(ad, bd, tile=(bm, bk, bn), group=g,
                               backend="jnp", cache=cache)

        plan = build_plan()
        rep = plan.report
        flops = 2 * rep.num_triples * bm * bk * bn
        # HBM bytes: A streamed once; B fetched per elided schedule; C
        # panels written once.
        bytes_ = (rep.nnzb_a * bm * bk + rep.b_fetches * bk * bn
                  + rep.n_panels * g * bm * bn) * 4
        ai = flops / bytes_
        # Amortization: full plan build (conversion + symbolic + staging)
        # vs numeric-only execute with fresh values on the cached plan.
        plan_ms = timeit(build_plan, repeats=3, warmup=0) * 1e3
        a_vals = plan.a_pattern.val * 0.5
        b_vals = plan.b_pattern.val * 2.0
        exec_ms = timeit(lambda: plan.execute(a_vals, b_vals),
                         repeats=3, warmup=1) * 1e3
        print(f"kernels,spgemm_{m}x{k}x{n}_g{g},{rep.num_triples},"
              f"{rep.b_fetches},{rep.block_omar:.1f},{flops:.2e},"
              f"{bytes_:.2e},{ai:.1f},{plan_ms:.1f},{exec_ms:.1f}")

    # Plan reuse correctness: fresh values on a cached plan match a fresh
    # dense reference (the serving loop's invariant).
    ad = random_block_sparse(256, 256, (64, 64), 0.3, seed=3)
    bd = random_block_sparse(256, 256, (64, 64), 0.3, seed=4)
    plan = spgemm_plan(ad, bd, tile=64, group=2,
                       backend="pallas_interpret", cache=PlanCache())
    c = plan.execute()
    err = np.abs(c.todense() - ad @ bd).max()
    print(f"kernels,spgemm_plan_interpret_maxerr,{err:.2e}")
    a2 = np.zeros_like(ad)
    a2[plan.a_pattern.row, plan.a_pattern.col] = plan.a_pattern.val * 3.0
    c2 = plan.execute(plan.a_pattern.val * 3.0, None)
    err2 = np.abs(c2.todense() - a2 @ bd).max()
    print(f"kernels,spgemm_plan_reexec_maxerr,{err2:.2e}")

    # Compatibility shim spot-check (ops.spgemm -> cached plan).
    c3 = ops.spgemm(to_bcsv(ad, (64, 64), 2), to_bcsr(bd, (64, 64)),
                    backend="pallas_interpret")
    err3 = np.abs(c3.todense() - ad @ bd).max()
    print(f"kernels,spgemm_ops_shim_maxerr,{err3:.2e}")

    # Batched numeric phase: one vmapped execute_batch call vs a loop of
    # single executes over the same value sets (C = A @ A^T on scaled paper
    # patterns, jnp backend — the serving workload shape).
    print("kernels,batched_case,batch,nnz_per_set,loop_ms,batch_ms,"
          "values_per_s,speedup")
    for name, scale in (("poisson3Da", 0.02), ("2cubes_sphere", 0.003)):
        a_csr = suite_matrix(name, scale=scale)
        a_coo = a_csr.to_coo()
        b_coo = COO(a_coo.col, a_coo.row, a_coo.val,
                    (a_csr.shape[1], a_csr.shape[0]))  # A^T
        cache = PlanCache()
        plan = spgemm_plan(a_coo, b_coo, tile=32, group=4, backend="jnp",
                           cache=cache)
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=3)
        nnz_set = plan.report.nnz_a + plan.report.nnz_b
        for bsz in (1, 8, 32):
            av, bv = stream.values_batch_at(0, batch=bsz)

            def loop():
                return [plan.execute(av[i], bv[i]) for i in range(bsz)]

            def batched():
                return plan.execute_batch(av, bv)

            # Interleaved min-of-N: the two sides differ by tens of
            # percent, within scheduler noise for a lone 3-sample median —
            # alternating measurements and keeping the best of each side
            # compares like against like.
            loop(), batched()  # warm both jit caches
            loop_s, batch_s = float("inf"), float("inf")
            for _ in range(9):
                t0 = time.perf_counter()
                loop()
                loop_s = min(loop_s, time.perf_counter() - t0)
                t0 = time.perf_counter()
                batched()
                batch_s = min(batch_s, time.perf_counter() - t0)
            loop_ms, batch_ms = loop_s * 1e3, batch_s * 1e3
            vps = bsz * nnz_set / (batch_ms / 1e3)
            print(f"kernels,spgemm_batched_{name},{bsz},{nnz_set},"
                  f"{loop_ms:.1f},{batch_ms:.1f},{vps:.3e},"
                  f"{loop_ms / batch_ms:.2f}x")
        # Plan-cache observability (PlanCache.stats()).
        cs = cache.stats()
        print(f"kernels,plan_cache_{name},hits={cs['hits']},"
              f"misses={cs['misses']},evictions={cs['evictions']},"
              f"resident_plans={cs['resident_plans']},"
              f"resident_bytes={cs['resident_bytes']}")

    data["pallas_batch"] = _pallas_batch_section()

    _persistence_section()

    if pipeline_depths:
        _pipeline_section(pipeline_depths)

    if devices > 1:
        _sharded_section(devices)
    return data


def _pallas_batch_section() -> dict:
    """Batch-folded Pallas grid: ``execute_batch`` on a pallas_interpret
    plan vs a loop of single-set Pallas calls — bitwise equality plus the
    dispatch amortization the fold buys. CI gates on the returned ``ok``
    (BENCH_kernel_schedule_metrics.json ``data.pallas_batch.ok``)."""
    print("kernels,pallas_batch_case,batch,loop_ms,batch_ms,speedup,bitwise")
    ad = random_block_sparse(256, 256, (32, 32), 0.35, seed=7)
    bd = random_block_sparse(256, 256, (32, 32), 0.35, seed=8)
    plan = spgemm_plan(ad, bd, tile=32, group=4,
                       backend="pallas_interpret", cache=PlanCache())
    stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=5)
    bsz = 4
    av, bv = stream.values_batch_at(0, batch=bsz)

    def loop():
        return [plan.execute(av[i], bv[i]) for i in range(bsz)]

    def batched():
        return plan.execute_batch(av, bv)

    ref, out = loop(), batched()  # also warms both jit caches
    bitwise = all(
        np.array_equal(np.asarray(r.todense()), np.asarray(o.todense()))
        for r, o in zip(ref, out)
    )
    loop_ms = timeit(loop, repeats=3, warmup=0) * 1e3
    batch_ms = timeit(batched, repeats=3, warmup=0) * 1e3
    rec = {
        "ok": bool(bitwise),
        "backend": "pallas_interpret",
        "batch": bsz,
        "num_triples": plan.report.num_triples,
        "loop_ms": loop_ms,
        "batch_ms": batch_ms,
        "speedup": loop_ms / batch_ms,
        "bitwise_equal": bool(bitwise),
    }
    print(f"kernels,spgemm_pallas_batch_256,{bsz},{loop_ms:.1f},"
          f"{batch_ms:.1f},{loop_ms / batch_ms:.2f}x,{bitwise}")
    if not bitwise:
        raise RuntimeError(
            "pallas batch grid diverged bitwise from looped execute")
    return rec


def _persistence_section() -> None:
    """Cold plan build (full symbolic phase) vs warm restart (verified
    disk load through the PlanCache disk tier) on the same pattern — the
    amortization REPRO_SPGEMM_PLAN_DIR buys a restarted serving worker."""
    print("kernels,persist_case,plan_file_kb,cold_plan_ms,warm_plan_ms,"
          "warm_speedup,schedule_builds_warm")
    for name, scale, tile, group in (
        ("poisson3Da", 0.02, 32, 4),
        ("2cubes_sphere", 0.003, 32, 4),
    ):
        a = suite_matrix(name, scale=scale).to_coo().sum_duplicates()
        b = COO(a.col, a.row, a.val, (a.shape[1], a.shape[0]))  # A^T
        with tempfile.TemporaryDirectory() as d:
            store = PlanStore(d)

            def cold():
                store.clear()  # every repeat pays the full symbolic phase
                return spgemm_plan(a, b, tile=tile, group=group,
                                   backend="jnp",
                                   cache=PlanCache(disk_dir=d))

            def warm():
                # Fresh cache on the populated directory = a restarted
                # process; only conversion-to-COO/digest/rebind host work.
                return spgemm_plan(a, b, tile=tile, group=group,
                                   backend="jnp",
                                   cache=PlanCache(disk_dir=d))

            cold_ms = timeit(cold, repeats=3, warmup=0) * 1e3
            cold()  # leave the store populated for the warm side
            plan = warm()
            if plan.report.schedule_builds != 0:
                raise RuntimeError("warm restart re-ran the symbolic phase")
            warm_ms = timeit(warm, repeats=3, warmup=0) * 1e3
            kb = store.total_bytes() / 1024
            print(f"kernels,spgemm_persist_{name},{kb:.0f},{cold_ms:.1f},"
                  f"{warm_ms:.1f},{cold_ms / warm_ms:.2f}x,"
                  f"{plan.report.schedule_builds}")


def _pipeline_section(depths=(1, 2, 4), steps: int = 24) -> None:
    """Streaming throughput: N serving steps (fresh values generated per
    step, one execute each) run synchronously vs through
    ``SpGEMMPipeline`` at several depths. The pipelined side overlaps
    value generation + staging (H2D + rebind) of step s+1 with step s's
    kernel and defers every D2H to collect — the paper's double-buffered
    operand fetch (depth 2) measured end to end. Results are
    bitwise-equal by construction (tests/test_pipeline.py)."""
    print("kernels,pipeline_case,depth,steps,sync_steps_s,pipe_steps_s,"
          "speedup")
    for name, scale, tile, group in (
        ("poisson3Da", 0.02, 32, 4),
        ("2cubes_sphere", 0.003, 32, 4),
    ):
        a = suite_matrix(name, scale=scale).to_coo().sum_duplicates()
        b = COO(a.col, a.row, a.val, (a.shape[1], a.shape[0]))  # A^T
        plan = spgemm_plan(a, b, tile=tile, group=group, backend="jnp",
                           cache=PlanCache())
        stream = SpGEMMValueStream(plan.a_pattern, plan.b_pattern, seed=3)

        def sync():
            return [plan.execute(*stream.values_at(s)) for s in range(steps)]

        def piped(depth):
            with plan.pipeline(depth=depth) as pipe:
                return list(pipe.stream(
                    stream.values_at(s) for s in range(steps)))

        # Interleaved min-of-N (same rationale as the batched section).
        sync()
        for d in depths:
            piped(d)  # warm the stage jits
        best = {"sync": float("inf")}
        best.update({d: float("inf") for d in depths})
        for _ in range(7):
            t0 = time.perf_counter()
            sync()
            best["sync"] = min(best["sync"], time.perf_counter() - t0)
            for d in depths:
                t0 = time.perf_counter()
                piped(d)
                best[d] = min(best[d], time.perf_counter() - t0)
        sync_sps = steps / best["sync"]
        for d in depths:
            pipe_sps = steps / best[d]
            print(f"kernels,spgemm_pipeline_{name},{d},{steps},"
                  f"{sync_sps:.1f},{pipe_sps:.1f},"
                  f"{pipe_sps / sync_sps:.2f}x")


def _sharded_section(devices: int) -> None:
    """Run the sharded benchmark. On a TPU host it runs in this process
    over the attached chips (a chip belongs to one process, so a child
    could not reach them), and is skipped on a one-chip host. On CPU it
    runs in a subprocess with forced host devices (the XLA device count
    must be set before jax initializes — this process already did)."""
    import jax

    if jax.default_backend() != "cpu":
        n_dev = len(jax.devices())
        if n_dev < 2:
            print(f"kernels,sharded_section_skipped,{n_dev} "
                  f"{jax.default_backend()} device(s): a sharded plan "
                  f"needs at least 2")
            return
        _sharded_worker(min(devices, n_dev))
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"{env.get('XLA_FLAGS', '')} "
        f"--xla_force_host_platform_device_count={devices}"
    ).strip()
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_kernels",
         "--sharded-worker", "--devices", str(devices)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=1200,
    )
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded benchmark worker failed:\n{out.stderr[-3000:]}"
        )


def _sharded_worker(devices: int) -> None:
    """Child process body: per-shard triple imbalance + values/s scaling
    of sharded execute_batch vs the single-device plan."""
    import jax

    from repro.launch.mesh import make_shard_mesh

    n_dev = len(jax.devices())
    print("kernels,sharded_case,shards,triples_max,triples_mean,"
          "imbalance,batch_ms,values_per_s,scaling_vs_1")
    shard_counts = [n for n in (2, 4, 8, 16) if n <= min(devices, n_dev)]
    for name, scale, tile, group, batch in _SHARDED_CASES:
        a = suite_matrix(name, scale=scale).to_coo().sum_duplicates()
        b = COO(a.col, a.row, a.val, (a.shape[1], a.shape[0]))
        single = spgemm_plan(a, b, tile=tile, group=group, backend="jnp",
                             cache=PlanCache())
        stream = SpGEMMValueStream(single.a_pattern, single.b_pattern,
                                   seed=3)
        av, bv = stream.values_batch_at(0, batch=batch)
        nnz_set = single.report.nnz_a + single.report.nnz_b

        def best_of(plan, reps: int = 5) -> float:
            plan.execute_batch(av, bv)  # warm the jit
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                plan.execute_batch(av, bv)
                best = min(best, time.perf_counter() - t0)
            return best

        t1 = best_of(single)
        tmean = single.report.num_triples
        print(f"kernels,spgemm_sharded_{name},1,{tmean},{tmean:.1f},"
              f"1.00,{t1 * 1e3:.1f},{batch * nnz_set / t1:.3e},1.00x")
        for n in shard_counts:
            plan = spgemm_plan(a, b, tile=tile, group=group, backend="jnp",
                               cache=PlanCache(), mesh=make_shard_mesh(n))
            t = best_of(plan)
            st = plan.shard_stats()
            tmax = max(st["triples"])
            tmean = sum(st["triples"]) / n
            print(f"kernels,spgemm_sharded_{name},{n},{tmax},{tmean:.1f},"
                  f"{st['imbalance']:.2f},{t * 1e3:.1f},"
                  f"{batch * nnz_set / t:.3e},{t1 / t:.2f}x")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--devices", type=int, default=4,
                   help="forced host devices for the sharded section "
                        "(0/1 skips it)")
    p.add_argument("--pipeline-depth", type=str, default="1,2,4",
                   help="comma-separated SpGEMMPipeline depths for the "
                        "streaming-throughput section (empty/0 skips it)")
    p.add_argument("--sharded-worker", action="store_true",
                   help=argparse.SUPPRESS)  # internal: child process body
    args = p.parse_args(argv)
    depths = tuple(
        int(d) for d in args.pipeline_depth.split(",") if d.strip()
    )
    depths = tuple(d for d in depths if d > 0)
    if args.sharded_worker:
        _sharded_worker(args.devices)
        return None
    return run(devices=args.devices, pipeline_depths=depths)


if __name__ == "__main__":
    main()
