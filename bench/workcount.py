"""The work one product requires, counted from the patterns alone.

These counts are the numerator of the numeric roofline share. They depend
only on the product, never on how a kernel tiles it, so the share reads
the same work whatever implements it.

* useful FLOPs: ``2 * sum over A's entries (i, k) of nnz(B[k, :])`` — one
  multiply and one add for each term of the product;
* compulsory bytes: float32 values in and out, ``4 * (nnz A + nnz B +
  nnz C)``. Both operands count, as the entry point is handed both.
"""
from __future__ import annotations

import numpy as np

from bench.patterns import Operands

__all__ = ["useful_flops", "compulsory_bytes", "roofline_floor_s"]

VALUE_BYTES = 4  # float32


def useful_flops(ops: Operands) -> int:
    b_row_nnz = np.bincount(ops.b.row, minlength=ops.b.shape[0])
    return 2 * int(b_row_nnz[ops.a.col].sum(dtype=np.int64))


def compulsory_bytes(ops: Operands, nnz_c: int) -> int:
    return VALUE_BYTES * (ops.a.nnz + ops.b.nnz + int(nnz_c))


def roofline_floor_s(flops: float, nbytes: float, peak: dict):
    """The least time the chip could take and the floor that binds:
    ``(seconds, "compute" | "memory")``."""
    t_flops = flops / float(peak["flops_per_s"])
    t_bytes = nbytes / float(peak["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
