"""Benchmark driver: one section per paper table/figure + the roofline
report. ``PYTHONPATH=src python -m benchmarks.run``

Each section writes a machine-readable ``BENCH_<slug>.json`` next to its
stdout report (default ``benchmarks/out/``, override with ``--out-dir``)
so the perf trajectory is tracked across PRs: the payload carries the
section's returned rows/dict (``data``), wall time, and ok/error status.

Exits nonzero when any section fails so CI can gate on it."""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import traceback

# XLA_FLAGS is read once at backend init, so the opt-in GPU preset must be
# merged before anything below pulls in jax (xla_flags itself is jax-free).
from repro.launch.xla_flags import maybe_apply_gpu_xla_flags

maybe_apply_gpu_xla_flags()

from benchmarks import (
    bench_arch_params,
    bench_autotune,
    bench_chain,
    bench_chunk_knee,
    bench_energy,
    bench_gateway,
    bench_kernels,
    bench_omar,
    bench_runtime,
    bench_stuf,
    bench_verify,
    roofline,
)
from repro.launch.compile_cache import use_compile_cache

SECTIONS = [
    ("Fig 6 — OMAR vs NUM_PE", bench_omar.main),
    ("Table 7 — runtime", bench_runtime.main),
    ("Table 8 — STUF", bench_stuf.main),
    ("Table 9 / Fig 8 — energy", bench_energy.main),
    ("Sec 4.2.4 — architectural parameters", bench_arch_params.main),
    # --devices 4: the sharded-plan section (per-shard imbalance +
    # values/s scaling vs 1 device) — in-process over the chips on a TPU
    # host, in a forced-host-device subprocess on CPU.
    # --pipeline-depth: the async-serving streaming section (pipelined
    # steps/s vs synchronous at depths 1/2/4).
    ("Kernel schedule metrics",
     lambda: bench_kernels.main(
         ["--devices", "4", "--pipeline-depth", "1,2,4"])),
    # Measures the fused-vs-split run_batch knee on this host and reports
    # it against the configured _CHUNK_POLICY row (the policy's data
    # source; see repro.core.tuning.measure_chunk_knee).
    ("Chunk-fusion knee calibration",
     lambda: bench_chunk_knee.main(["--repeats", "2"])),
    # Tuned-vs-default values/s on paper matrices (+ model agreement);
    # the record's "ok" flag is the CI gate: tuned >= 0.95x default.
    ("Autotune", lambda: bench_autotune.main(["--repeats", "2"])),
    ("Gateway serving — throughput/latency", bench_gateway.main),
    # Compact-vs-block C bytes + chained A@B@A vs host round trip; the
    # record's "ok" gate: compact bytes < block bytes and chain >= 1.2x.
    ("Chain", lambda: bench_chain.main(["--repeats", "2"])),
    # Static-verifier cost: verify_plan + kernel lint timed against the
    # symbolic build they guard (the validate="deep" tax).
    ("Verify", lambda: bench_verify.main(["--repeats", "2"])),
    ("Roofline (from dry-run artifacts)", roofline.main),
]


def _slug(title: str) -> str:
    """'Table 7 — runtime' -> 'table_7_runtime' (filename-safe)."""
    return re.sub(r"_+", "_", re.sub(r"[^a-z0-9]+", "_", title.lower())).strip("_")


def _jsonable(obj):
    """Best-effort JSON coercion: numpy scalars/arrays, tuples, dataclass
    reprs — anything stranger degrades to str rather than failing the
    section after it already ran."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "item") and callable(obj.item):  # numpy scalar
        try:
            return obj.item()
        except Exception:
            pass
    if hasattr(obj, "tolist") and callable(obj.tolist):  # numpy array
        try:
            return obj.tolist()
        except Exception:
            pass
    return str(obj)


_EPILOG = """\
environment:
  REPRO_GPU_XLA_FLAGS=1   merge the GPU latency-hiding/pipelining XLA_FLAGS
                          preset (repro.launch.xla_flags) before jax starts;
                          flags you already set in XLA_FLAGS win. No-op on
                          CPU/TPU and by default.
  REPRO_SPGEMM_CHUNK_BYTES=<n>  override the per-set batch-fusion budget
                          measured by the chunk-knee calibration section.
"""


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--out-dir", default=os.path.join("benchmarks", "out"),
                    help="directory for BENCH_<section>.json artifacts")
    ap.add_argument("--only", default=None,
                    help="substring filter on section titles")
    args = ap.parse_args(argv)
    os.makedirs(args.out_dir, exist_ok=True)
    print(f"[bench] compile cache: {use_compile_cache()}")

    failures = []
    for title, fn in SECTIONS:
        if args.only and args.only.lower() not in title.lower():
            continue
        print(f"\n=== {title} " + "=" * max(1, 60 - len(title)))
        rec = {"section": title, "ok": True, "elapsed_s": None,
               "data": None, "error": None,
               "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z")}
        t0 = time.perf_counter()
        try:
            rec["data"] = _jsonable(fn())
        except Exception as e:
            failures.append(title)
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"
            print(f"SECTION FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
        rec["elapsed_s"] = time.perf_counter() - t0
        path = os.path.join(args.out_dir, f"BENCH_{_slug(title)}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"[bench] wrote {path} ({rec['elapsed_s']:.1f}s)")
    print("\n=== benchmarks done"
          + (f" ({len(failures)} section(s) failed: {failures})"
             if failures else " (all sections passed)"))
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
