"""Performance / STUF / energy models (paper Sec. 4.2.4, 5.3.2, 5.3.3).

The paper measures wall-clock and power on an Arria 10 GX FPGA, a Xeon
E5-2637 v3 and a GTX TITAN X. This container is CPU-only, so (DESIGN.md
Sec. 8) the reproduction strategy is:

* CPU numbers: *measured* here with our implementations (numpy Gustavson =
  the MKL analogue, plus scipy's SpGEMM).
* FPGA numbers: *modeled* — paper Eq. 2 R = N_Ops/(F · 2·SW·NUM_PE · U)
  driven either by published STUF (Table 8) or by cycle counts from the
  faithful ``FSpGEMMSimulator``.
* Paper's published Tables 7/8/9 are embedded verbatim for comparison, and
  the benchmark output reports measured-vs-paper ratios.
* TPU numbers: roofline-modeled from the Pallas kernel's traffic/flop
  counts (the §Roofline methodology applied to the SpGEMM kernel itself).

STUF (spatial-temporal utilization factor):  U = N_Ops / (F · P · R)
with P = FLOPs available per cycle (paper Sec. 5.3.2).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

__all__ = [
    "DeviceModel",
    "CPU_XEON_E5_2637",
    "GPU_TITAN_X",
    "FPGA_ARRIA10",
    "TPU_V5E_CHIP",
    "TPU_CHIPS_BY_KIND",
    "stuf",
    "runtime_from_stuf",
    "energy",
    "spgemm_schedule_traffic",
    "spgemm_grid_step_vmem",
    "TPU_VMEM_BYTES",
    "TPU_SMEM_BYTES",
    "SCHEDULE_SMEM_BYTES_PER_TRIPLE",
    "SCHEDULE_SMEM_MARGIN_BYTES",
    "SCHEDULE_TRIPLES_PER_CALL",
    "ASSEMBLY_SMEM_BYTES_PER_ITEM",
    "assembly_items_per_call",
    "roofline_seconds",
    "PAPER_TABLE7_MS",
    "PAPER_TABLE8_STUF",
    "PAPER_TABLE9_J",
    "PAPER_MATRICES",
]


@dataclasses.dataclass(frozen=True)
class DeviceModel:
    name: str
    clock_Hz: float  # F
    parallelism: float  # P: FLOPs per cycle available
    avg_power_W: float  # average power during SpGEMM (paper-implied)
    mem_bandwidth: float = 0.0  # bytes/s (0 = unknown; roofline helpers
    # then treat the device as compute-bound only)

    @property
    def peak_flops(self) -> float:
        return self.clock_Hz * self.parallelism


# Paper Sec. 5.3.2: CPU = 2 sockets x 4 cores x 32 FLOPs/cycle @ 3.5 GHz;
# E5-2637 v3 is 4-channel DDR4-2133 per socket: ~68 GB/s.
CPU_XEON_E5_2637 = DeviceModel(
    "xeon-e5-2637v3", 3.5e9, 256.0, 128.0, mem_bandwidth=68e9
)
# GPU: 3072 CUDA cores (Table 5; Sec. 5.3.2's 3,584 is a typo), 2 FLOPs/cycle
# @ 1.0 GHz; 336 GB/s GDDR5.
GPU_TITAN_X = DeviceModel(
    "gtx-titan-x", 1.0e9, 6144.0, 160.0, mem_bandwidth=336e9
)
# FPGA: SW*NUM_PE = 512 DSPs busy, 2 FLOPs/cycle each @ 236 MHz; the paper's
# STUF normalizes by all 1,518 DSPs. avg power implied by Table 7/9: ~18.5 W.
# Bandwidth is the paper's C1 = 15 GB/s DDR.
FPGA_ARRIA10 = DeviceModel(
    "arria10-gx", 236e6, 2 * 1518.0, 18.5, mem_bandwidth=15e9
)
# TPU v5e-class single chip (roofline constants from the brief).
TPU_V5E_CHIP = DeviceModel(
    "tpu-v5e", 940e6, 197e12 / 940e6, 170.0, mem_bandwidth=819e9
)
# Chip models by ``jax.devices()[0].device_kind`` (v5e reports "TPU v5
# lite"). A chip that is not listed has no model: callers raise.
TPU_CHIPS_BY_KIND: Dict[str, DeviceModel] = {"TPU v5 lite": TPU_V5E_CHIP}


def stuf(n_ops: float, device: DeviceModel, runtime_s: float) -> float:
    """U = N_Ops / (F · P · R)   (paper Sec. 5.3.2)."""
    if runtime_s <= 0:
        return 0.0
    return n_ops / (device.peak_flops * runtime_s)


def runtime_from_stuf(n_ops: float, device: DeviceModel, u: float) -> float:
    """R = N_Ops / (F · P · U)   (paper Eq. 2 generalized)."""
    return n_ops / (device.peak_flops * u)


def energy(runtime_s: float, device: DeviceModel) -> float:
    """E = R · avg power (paper Sec. 5.3.3)."""
    return runtime_s * device.avg_power_W


def spgemm_schedule_traffic(
    *,
    num_triples: int,
    nnzb_a: int,
    b_fetches: int,
    n_panels: int,
    tile,
    group: int,
    dtype_bytes: int = 4,
) -> Dict[str, float]:
    """FLOP and streamed-byte counts of one scheduled block-Gustavson
    numeric phase, from the plan report's symbolic counters.

    Per triple the kernel runs a dense (bm x bk) @ (bk x bn) MAC —
    ``2·bm·bk·bn`` FLOPs. Traffic is the packed A blocks streamed once
    (``nnzb_a·bm·bk``), every scheduled B-tile fetch (``b_fetches·bk·bn``
    — the OMAR-reduced count, the paper's Sec. 4.2.2 win), and the C
    accumulator panels written out (``n_panels·group·bm·bn``).
    """
    bm, bk, bn = (int(t) for t in tile)
    flops = 2.0 * float(num_triples) * bm * bk * bn
    bytes_streamed = float(dtype_bytes) * (
        float(nnzb_a) * bm * bk
        + float(b_fetches) * bk * bn
        + float(n_panels) * group * bm * bn
    )
    return {"flops": flops, "bytes": bytes_streamed}


# Per-core VMEM capacity the Pallas kernels pipeline through (TPU v4/v5e
# class; see the accelerator guide). The kernel lint budgets grid-step
# working sets against this.
TPU_VMEM_BYTES = 16 << 20

# Scalar memory (SMEM) of one v5e core, where the kernel's five int32
# scalar-prefetch schedule arrays live (20 B per padded triple). Measured
# by compiling spgemm_scheduled_impl for a described v5e at 200,000
# triples: "RESOURCE_EXHAUSTED ... Ran out of memory in memory space
# smem. Used 3.83M of 1.00M" — so one pallas_call holds ~50k triples.
TPU_SMEM_BYTES = 1 << 20
SCHEDULE_SMEM_BYTES_PER_TRIPLE = 5 * 4
# SMEM left to the rest of a call. The described-v5e compile of one call
# at 8,997 blocks and 9,692 panels accepts 52,218 triples and refuses
# 52,229 ("Used 1.02M of 1.00M smem"); 64 KiB holds 3,066 of headroom
# below that.
SCHEDULE_SMEM_MARGIN_BYTES = 64 << 10
# The most triples one pallas_call takes (49,152): the executor cuts a
# longer schedule into calls of at most this many, at panel boundaries.
SCHEDULE_TRIPLES_PER_CALL = (
    TPU_SMEM_BYTES - SCHEDULE_SMEM_MARGIN_BYTES
) // SCHEDULE_SMEM_BYTES_PER_TRIPLE
# The run-wise assembly kernel's SMEM: three int32 scalar-prefetch arrays
# a grid step (item), and one output block's runs, two int32 words each,
# double-buffered.
ASSEMBLY_SMEM_BYTES_PER_ITEM = 3 * 4


def assembly_items_per_call(width: int) -> int:
    """The most items one run-wise assembly call takes beside its
    double-buffered run-table blocks of ``width`` runs."""
    table = 2 * (2 * width * 4)
    return (TPU_SMEM_BYTES - SCHEDULE_SMEM_MARGIN_BYTES - table) \
        // ASSEMBLY_SMEM_BYTES_PER_ITEM


def spgemm_grid_step_vmem(
    *,
    tile,
    group: int,
    dtype_bytes: int = 4,
    double_buffered: bool = True,
) -> float:
    """Per-grid-step VMEM working set of the scheduled Pallas kernel.

    Each grid step holds one A block (``bm x bk``), one B block
    (``bk x bn``), and one output panel (``group*bm x bn``) in VMEM —
    the same three block objects :func:`spgemm_schedule_traffic` counts
    stream traffic for, sized per step instead of per schedule. Pallas
    pipelines HBM copies against compute, so the resident set is double
    the single-step footprint (``double_buffered=True``, the default the
    kernels compile with). An oversized (tile, group) fails compilation
    or silently spills; :func:`repro.analysis.kernel_lint.
    lint_plan_kernel_specs` budgets this number against
    :data:`TPU_VMEM_BYTES` *before* any compile.
    """
    bm, bk, bn = (int(t) for t in tile)
    per_step = bm * bk + bk * bn + group * bm * bn
    return float(per_step) * dtype_bytes * (2 if double_buffered else 1)


def roofline_seconds(
    flops: float, bytes_streamed: float, device: DeviceModel
) -> float:
    """Roofline runtime estimate: max of the compute and memory floors.

    This is the model side of the autotuner's two-stage search
    (``repro.spgemm.autotune``): absolute seconds are host-dependent, but
    the *ordering* over candidate (tile, group) configs is what prunes
    the grid before measured probes. Devices with unknown bandwidth
    (``mem_bandwidth == 0``) rank by compute alone."""
    t = flops / device.peak_flops
    if device.mem_bandwidth > 0:
        t = max(t, bytes_streamed / device.mem_bandwidth)
    return t


PAPER_MATRICES = [
    "poisson3Da",
    "2cubes_sphere",
    "filter3D",
    "cage12",
    "scircuit",
    "mac_econ_fwd500",
    "offshore",
    "webbase-1M",
]

# Paper Table 7: runtime in ms (MKL CPU, cuSPARSE GPU, FSpGEMM FPGA).
PAPER_TABLE7_MS: Dict[str, Dict[str, float]] = {
    "poisson3Da": {"mkl": 27, "cusparse": 8, "fspgemm": 5},
    "2cubes_sphere": {"mkl": 21, "cusparse": 9, "fspgemm": 9},
    "filter3D": {"mkl": 44, "cusparse": 25, "fspgemm": 42},
    "cage12": {"mkl": 147, "cusparse": 46, "fspgemm": 15},
    "scircuit": {"mkl": 32, "cusparse": 14, "fspgemm": 6},
    "mac_econ_fwd500": {"mkl": 36, "cusparse": 11, "fspgemm": 7},
    "offshore": {"mkl": 71, "cusparse": 30, "fspgemm": 23},
    "webbase-1M": {"mkl": 181, "cusparse": 57, "fspgemm": 25},
}

# Paper Table 8: STUF.
PAPER_TABLE8_STUF: Dict[str, Dict[str, float]] = {
    "poisson3Da": {"mkl": 4.7e-4, "cusparse": 2.4e-4, "fspgemm": 3.4e-3},
    "2cubes_sphere": {"mkl": 1.4e-3, "cusparse": 5.0e-4, "fspgemm": 4.3e-3},
    "filter3D": {"mkl": 2.1e-3, "cusparse": 5.6e-4, "fspgemm": 2.9e-3},
    "cage12": {"mkl": 2.6e-4, "cusparse": 1.2e-4, "fspgemm": 3.2e-3},
    "scircuit": {"mkl": 2.9e-4, "cusparse": 1.0e-4, "fspgemm": 2.0e-3},
    "mac_econ_fwd500": {"mkl": 2.3e-4, "cusparse": 1.1e-4, "fspgemm": 1.5e-3},
    "offshore": {"mkl": 1.2e-4, "cusparse": 4.1e-5, "fspgemm": 4.6e-4},
    "webbase-1M": {"mkl": 4.2e-4, "cusparse": 2.0e-4, "fspgemm": 3.9e-3},
}

# Paper Table 9: energy in J.
PAPER_TABLE9_J: Dict[str, Dict[str, float]] = {
    "poisson3Da": {"mkl": 3.46, "cusparse": 1.31, "fspgemm": 0.09},
    "2cubes_sphere": {"mkl": 3.11, "cusparse": 1.22, "fspgemm": 0.17},
    "filter3D": {"mkl": 6.03, "cusparse": 3.43, "fspgemm": 0.79},
    "cage12": {"mkl": 16.91, "cusparse": 6.44, "fspgemm": 0.29},
    "scircuit": {"mkl": 4.35, "cusparse": 1.83, "fspgemm": 0.12},
    "mac_econ_fwd500": {"mkl": 5.22, "cusparse": 1.43, "fspgemm": 0.13},
    "offshore": {"mkl": 9.80, "cusparse": 3.99, "fspgemm": 0.44},
    "webbase-1M": {"mkl": 15.93, "cusparse": 9.86, "fspgemm": 0.47},
}
