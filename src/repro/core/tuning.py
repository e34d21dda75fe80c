"""Architectural-parameter models + measured calibration probes.

Analytical side (paper Sec. 4.2.4) — FPGA (paper-faithful): runtime
R = N_Ops / (F · SW · NUM_PE · U);
subject to bandwidth  f1(SW) = sizeof(float)·SW·F ≤ C1
and logic              f2(SW, NUM_PE) = β·SW·NUM_PE ≤ C2,
with the paper's closed-form optimum
    SW      = ceil(C1 / (sizeof(float)·F))
    NUM_PE  = ceil(C2 / (β·SW))
validated to reproduce the published SW=16, NUM_PE=32 on Arria 10 GX.

TPU side (hardware adaptation, DESIGN.md Sec. 2): the same two-constraint
structure re-targeted at tile shapes — the bandwidth constraint bounds the
streaming width (lane-aligned bn), the capacity constraint (VMEM instead of
logic) bounds the row-group panel G·bm·bn. ``tpu_tile_params`` returns MXU-
aligned (bm, bk, bn, G) maximizing modeled throughput.

Measured side: :func:`measure_chunk_knee` calibrates the batch-fusion
working-set budget (``repro.spgemm.executor._CHUNK_POLICY``) on the
*current* backend by sweeping plans of growing per-set working bytes and
timing fused vs. one-per-call batches. It is the documented re-measurement
path for the policy table (``python -m benchmarks.bench_chunk_knee``, or
the "Chunk-fusion knee" section of ``benchmarks/run.py``).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "FPGASpec",
    "ARRIA10_GX",
    "best_ms",
    "derive_fpga_params",
    "fpga_runtime_model",
    "interleaved_best_ms",
    "TPUSpec",
    "TPU_V5E",
    "measure_chunk_knee",
    "tpu_tile_params",
]


# ---------------------------------------------------------------------------
# FPGA model (paper-faithful)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FPGASpec:
    """Board constants (paper Table 5 for Arria 10 GX)."""

    name: str
    dsp_count: int
    mem_bandwidth_GBs: float  # C1
    clock_Hz: float  # F (achieved kernel clock)
    logic_capacity: float  # C2 (normalized logic units)
    beta: float  # fitted logic per unit parallelism (Sec. 4.2.4)


# The paper reports SW=16, NUM_PE=32 at 236 MHz with logic the binding
# constraint (97% logic @ 36% DSP).  β is back-fitted so the published
# optimum is reproduced: C2/β = SW·NUM_PE = 512.
ARRIA10_GX = FPGASpec(
    name="arria10-gx",
    dsp_count=1518,
    mem_bandwidth_GBs=15.0,
    clock_Hz=236e6,
    logic_capacity=512.0,
    beta=1.0,
)


def derive_fpga_params(spec: FPGASpec, float_bytes: int = 4) -> Tuple[int, int]:
    """Closed-form (SW, NUM_PE) per Sec. 4.2.4.

    SW = ceil(C1 / (sizeof(float) · F)); NUM_PE = ceil(C2 / (β · SW)).
    """
    sw = math.ceil(spec.mem_bandwidth_GBs * 1e9 / (float_bytes * spec.clock_Hz))
    num_pe = math.ceil(spec.logic_capacity / (spec.beta * sw))
    return sw, num_pe


def fpga_runtime_model(
    n_ops: int,
    spec: FPGASpec,
    sw: Optional[int] = None,
    num_pe: Optional[int] = None,
    stuf: float = 1.0,
) -> float:
    """Paper Eq. 2: R = N_Ops / (F · SW · NUM_PE · U)  [seconds].

    Note each DSP does a multiply+add per cycle, i.e. 2 FLOPs; N_Ops counts
    FLOPs, and SW·NUM_PE DSPs provide 2·SW·NUM_PE FLOPs/cycle. The paper
    lumps the 2 into U's definition of parallelism P; we follow the paper:
    P (computational parallelism) = 2 · #DSP-equivalents for STUF purposes,
    but Eq. 2 uses SW·NUM_PE MACs/cycle = 2·SW·NUM_PE FLOPs/cycle.
    """
    sw = sw if sw is not None else derive_fpga_params(spec)[0]
    num_pe = num_pe if num_pe is not None else derive_fpga_params(spec)[1]
    flops_per_cycle = 2.0 * sw * num_pe * stuf
    return n_ops / (spec.clock_Hz * flops_per_cycle)


# ---------------------------------------------------------------------------
# TPU re-target
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TPUSpec:
    name: str
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bandwidth: float  # bytes/s per chip
    ici_bandwidth: float  # bytes/s per link
    vmem_bytes: int  # per-core VMEM budget
    mxu_dim: int  # systolic array edge (tile alignment)
    lane: int  # vector lane count (last-dim alignment)
    sublane: int  # second-minor alignment for fp32


TPU_V5E = TPUSpec(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bandwidth=819e9,
    ici_bandwidth=50e9,
    vmem_bytes=16 * 2**20,  # ~16 MiB usable VMEM per core
    mxu_dim=128,
    lane=128,
    sublane=8,
)


def tpu_tile_params(
    spec: TPUSpec = TPU_V5E,
    dtype_bytes: int = 4,
    bn_target: Optional[int] = None,
    vmem_fraction: float = 0.7,
) -> Tuple[int, int, int, int]:
    """(bm, bk, bn, G) for the block-Gustavson kernels.

    Mirrors Sec. 4.2.4's two constraints:
      * streaming constraint — bn is the widest lane-aligned tile such that
        the B-stream bandwidth need ≤ HBM bandwidth at full MXU rate (on
        TPU this is trivially satisfied up to the VMEM bound, so bn is
        capacity-limited in practice, like the paper's SW was bandwidth-
        limited on the much slower DDR);
      * capacity constraint — the C accumulator panel (G·bm × bn), one B
        tile (bk × bn) and double buffers must fit ``vmem_fraction`` of
        VMEM; G (the NUM_PE analogue) is the largest group satisfying it.
    """
    bm = bk = spec.mxu_dim
    budget = spec.vmem_bytes * vmem_fraction
    bn = bn_target or spec.lane * 4  # 512 default: MXU-efficient N tile
    bn = max(spec.lane, (bn // spec.lane) * spec.lane)

    def footprint(g: int, bn_: int) -> float:
        acc = g * bm * bn_ * dtype_bytes  # C panel (single-buffered output)
        b_tile = 2 * bk * bn_ * dtype_bytes  # double-buffered B tile
        a_tile = 2 * bm * bk * dtype_bytes  # double-buffered A block
        return acc + b_tile + a_tile

    g = 1
    while footprint(g * 2, bn) <= budget:
        g *= 2
    # If even G=1 does not fit, shrink bn.
    while footprint(g, bn) > budget and bn > spec.lane:
        bn //= 2
    return bm, bk, bn, g


# ---------------------------------------------------------------------------
# Measured calibration: the batch-fusion knee
# ---------------------------------------------------------------------------

# (m, k, n, density, tile, group): element-plan cases whose per-set working
# bytes (4 * (n_panels*group + triples) * bm * bn, the batch_chunk basis)
# ramp from ~80 KiB to ~8 MiB — well under to well over every plausible
# CPU-cache knee, dense in the 0.25–3 MiB band where L2/L3 crossovers
# actually land, so the sweep brackets the fused-vs-split crossover.
_KNEE_CASES: Tuple[Tuple[int, int, int, float, int, int], ...] = (
    (64, 64, 64, 0.03, 16, 4),
    (96, 96, 96, 0.03, 16, 4),
    (128, 128, 128, 0.03, 16, 4),
    (160, 160, 160, 0.025, 16, 4),
    (192, 192, 192, 0.025, 16, 4),
    (224, 224, 224, 0.02, 16, 4),
    (256, 256, 256, 0.02, 16, 4),
    (320, 320, 320, 0.02, 16, 4),
)


def _random_int_coo(m: int, n: int, density: float, seed: int):
    """Small-integer float32 COO — values exact in f32, so fused/split
    paths are comparable bitwise as a calibration sanity check."""
    import numpy as np

    from repro.sparse.formats import COO

    rng = np.random.default_rng(seed)
    nnz = max(1, int(m * n * density))
    return COO(
        rng.integers(0, m, nnz),
        rng.integers(0, n, nnz),
        rng.integers(-3, 4, nnz).astype(np.float32),
        (m, n),
    ).sum_duplicates()


def best_ms(fn, repeats: int, timer=None) -> float:
    """Min-of-N wall time of ``fn`` in milliseconds.

    The shared probe primitive behind :func:`measure_chunk_knee` and the
    plan autotuner (``repro.spgemm.autotune``). ``timer`` is a
    ``time.perf_counter``-like callable, injectable so tuner tests run
    against a deterministic fake clock; it is called exactly twice per
    repeat (start, stop). The result is forced to host
    (``np.asarray``) inside the timed region so JAX's async dispatch
    cannot hide device time."""
    import numpy as np

    timer = timer if timer is not None else time.perf_counter
    best = float("inf")
    for _ in range(repeats):
        t0 = timer()
        np.asarray(fn())
        best = min(best, (timer() - t0) * 1e3)
    return best


def interleaved_best_ms(fns: Sequence, repeats: int, timer=None) -> List[float]:
    """Min-of-N over several probe thunks with **interleaved** repeats:
    round r times every ``fn`` once before round r+1 starts, so slow
    drift (thermal, background load) lands evenly on all candidates
    instead of biasing whichever ran last. Returns one best-ms per fn,
    in order. Timer calls: exactly two per (repeat, fn) measurement."""
    import numpy as np

    timer = timer if timer is not None else time.perf_counter
    best = [float("inf")] * len(fns)
    for _ in range(repeats):
        for i, fn in enumerate(fns):
            t0 = timer()
            np.asarray(fn())
            best[i] = min(best[i], (timer() - t0) * 1e3)
    return best


# Back-compat private alias (pre-autotune callers).
def _best_ms(fn, repeats: int) -> float:
    return best_ms(fn, repeats)


def measure_chunk_knee(
    batch: int = 8,
    repeats: int = 3,
    backend: str = "jnp",
    cases: Optional[Sequence[Tuple[int, int, int, float, int, int]]] = None,
    threshold: float = 1.0,
    seed: int = 0,
) -> Dict:
    """Measure the batch-fusion knee for ``executor._CHUNK_POLICY``.

    For each case the probe times a ``batch``-element value batch through
    the executor's ``run_batch`` two ways — **fused** (one device call for
    the whole batch) and **split** (one call per element, the ``chunk=1``
    policy) — bypassing ``batch_chunk`` so the policy under test does not
    steer its own calibration. The *knee* is the largest per-set working
    size (``4 * per_set_rows * bn`` bytes, the exact quantity
    ``batch_chunk`` compares against the policy budget) at which fusing
    still wins: above it the fused accumulator working set leaves the fast
    memory tier and per-set cost regresses.

    The smallest case additionally sweeps chunk sizes (1..batch) to place
    the second policy knob — the ``cache_bytes`` target that caps
    ``chunk * per_set`` — at the measured throughput plateau.

    Returns a JSON-able dict: per-case samples, ``knee_bytes``,
    ``chunk_sweep``, the suggested and currently configured policy rows.
    Run it on the backend being calibrated (CPU here; on a TPU/GPU host the
    same probe re-measures those rows — that is the documented path for
    updating the table).
    """
    import jax
    import numpy as np

    from repro.spgemm import PlanCache, spgemm_plan
    from repro.spgemm.executor import _CHUNK_POLICY

    rng = np.random.default_rng(seed)
    cache = PlanCache()
    samples: List[Dict] = []
    chunk_sweep: List[Dict] = []
    for ci, (m, k, n, density, tile, group) in enumerate(
        cases if cases is not None else _KNEE_CASES
    ):
        a = _random_int_coo(m, k, density, seed=seed + 2 * ci + 1)
        b = _random_int_coo(k, n, density, seed=seed + 2 * ci + 2)
        plan = spgemm_plan(a, b, tile=tile, group=group, backend=backend,
                           cache=cache)
        ex = plan._executor
        if ex is None:  # pragma: no cover - degenerate pattern
            continue
        per_set = 4 * ex._per_set_rows * ex._bn
        av = rng.integers(-3, 4, (batch, a.val.shape[0])).astype(np.float32)
        bv = rng.integers(-3, 4, (batch, b.val.shape[0])).astype(np.float32)

        def fused():
            return ex.run_batch(av, bv, rebind=True)

        def split():
            return [
                ex.run_batch(av[i:i + 1], bv[i:i + 1], rebind=True)
                for i in range(batch)
            ]

        fused(), split()  # compile both paths off the clock
        fused_ms = _best_ms(fused, repeats) / batch
        split_ms = _best_ms(lambda: np.concatenate(split()), repeats) / batch
        samples.append({
            "case": f"{m}x{k}x{n} d={density} tile={tile} g={group}",
            "per_set_bytes": int(per_set),
            "fused_ms_per_set": fused_ms,
            "split_ms_per_set": split_ms,
            "speedup": split_ms / max(fused_ms, 1e-9),
        })
        if ci == 0:
            for chunk in (1, 2, 4, batch):
                if chunk > batch:
                    continue

                def chunked():
                    return [
                        ex.run_batch(av[lo:lo + chunk], bv[lo:lo + chunk],
                                     rebind=True)
                        for lo in range(0, batch, chunk)
                    ]

                chunked()
                ms = _best_ms(lambda: np.concatenate(chunked()), repeats)
                chunk_sweep.append({
                    "chunk": chunk,
                    "ms_per_set": ms / batch,
                    "working_bytes": int(chunk * per_set),
                })

    # Prefix rule: the knee is the last per-set size (ascending) where
    # fusing still clears the threshold before the first regression.
    knee = 0
    for s in sorted(samples, key=lambda s: s["per_set_bytes"]):
        if s["speedup"] >= threshold:
            knee = s["per_set_bytes"]
        else:
            break
    best_chunk = min(chunk_sweep, key=lambda c: c["ms_per_set"])["chunk"] \
        if chunk_sweep else 1
    cache_bytes = max(knee, best_chunk * (samples[0]["per_set_bytes"]
                                          if samples else 0))
    device = jax.default_backend()
    return {
        "device_backend": device,
        "plan_backend": backend,
        "batch": batch,
        "repeats": repeats,
        "threshold": threshold,
        "samples": samples,
        "chunk_sweep": chunk_sweep,
        "knee_bytes": int(knee),
        "suggested_policy_row": [int(knee), int(cache_bytes)],
        "configured_policy_row": list(_CHUNK_POLICY[device]),
    }
