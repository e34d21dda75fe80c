"""Readings that the value limit of a cell is set from, on the chip.

    python3 bench/control.py --workload <name> --seeds 11,12,... --seconds 4

Sets the cell up once, then for each seed draws that seed's value sets,
runs the cell's path for a short window and compares a sample of its
results as a benchmark run does (the program's readings), and compares the
control on the same value sets: the reference in the program's place,
computed at bfloat16 (:meth:`bench.reference.Reference.control`). One JSON
line per seed. The benchmark's own runs never run this; the limit in the
configuration file lies between the largest program reading and the
smallest control reading (PERF.md gives both).
"""
import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings_over_seeds(cell, seeds, seconds, overrides=None):
    """``[{"seed", "program", "control"}]``: the worst readings of each."""
    from bench import harness, patterns, traffic
    from bench.reference import Reference

    cfg = dict(cell.config, **(overrides or {}))
    ops = patterns.operands(cfg, cell.root)
    n_ring, k = int(cell.traffic["ring"]), int(cell.traffic["check_samples"])
    _, warm = traffic.value_ring(seeds[0], n_ring, ops.a.nnz, ops.b_from_a)
    plan = harness.make_plan(cfg, ops, warm)
    cell.path.warm(plan, warm, cell.traffic)
    ref = Reference(ops)
    out = []
    for seed in seeds:
        ring, _ = traffic.value_ring(seed, n_ring, ops.a.nnz, ops.b_from_a)
        sampler = harness.Sampler(k, seed)
        res = cell.path.run(plan, ring, seconds, cell.traffic, sampler, contextlib.nullcontext)
        memos = {}
        program = harness.check(ref, sampler.kept, ring, memos)
        program.update(compared=len(sampler.kept), failed=res["attempted"] - res["completed"])
        control = {"pattern_mismatch": 0, "value_err": 0.0}
        for slot in sorted({s for _, s, _ in sampler.kept}):
            a_vals = ring[slot][0]
            r = ref.readings(ref.indptr, ref.indices, ref.control(a_vals), a_vals,
                             memos[slot])
            control = {key: max(control[key], r[key]) for key in control}
        out.append({"seed": seed, "program": program, "control": control})
        del sampler, memos
    plan.release()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.run import CACHE_DIR, TPU_LOG_DIR

    os.environ.setdefault("TPU_LOG_DIR", TPU_LOG_DIR)
    import jax

    from bench import harness

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.resolve(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    seeds = [int(s) for s in args.seeds.split(",")]
    for row in readings_over_seeds(cell, seeds, args.seconds):
        print(json.dumps(dict(row, workload=args.workload)), flush=True)
    print(f"control: {len(seeds)} seeds in {time.perf_counter() - t0:.1f} s",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
