"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line on stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with its limit, also the last lines on stderr. Exits 2, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for.

The compile cache is ``bench/.jax_cache`` and the plan store
``bench/.plans``, both inside the checkout at fixed paths, so only the first
run of a cell in a checkout builds the plan and compiles; libtpu's logs go
to ``bench/.tpu_logs`` unless ``TPU_LOG_DIR`` says otherwise.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, "bench", ".jax_cache")
TPU_LOG_DIR = os.path.join(ROOT, "bench", ".tpu_logs")  # libtpu's default is /tmp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", TPU_LOG_DIR)

    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    cell = harness.resolve(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); jax found "
              f"{len(devices)} {devices[0].platform!r} device(s)", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), t0=T0)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
