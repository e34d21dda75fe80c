"""The plain reference and the comparison that decides ``correct``.

The reference is scipy's sparse product of the same seeded values in
float64: C = A @ B and, beside it, M = |A| @ |B|, whose pattern is the
structural product pattern (no term can cancel) and whose entries bound
the rounding error of each entry of C. Nothing here imports the program.

Readings of one result C (a CSR whose ``indptr``/``indices``/``data`` the
timed entry point returned):

* ``pattern_mismatch`` — 0 when C's ``indptr`` and ``indices`` equal the
  structural product pattern exactly, else 1;
* ``value_err`` — ``max |C - R| / M`` over every entry: the worst entry's
  error in units of its own magnitude bound, so one wrong entry anywhere
  shows, whatever the scale of its row.

The control puts the reference in the program's place at the nearest
precision below the configuration's float32: A's and B's values rounded to
bfloat16, products summed in float32 (:meth:`Reference.control`).
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np
import scipy.sparse as sp

from bench.patterns import Operands, Pattern

__all__ = ["Reference"]


def _csr(p: Pattern, indptr: np.ndarray, vals: np.ndarray):
    return sp.csr_matrix((vals, p.col, indptr), shape=p.shape)


def _indptr(p: Pattern) -> np.ndarray:
    out = np.zeros(p.shape[0] + 1, np.int64)
    np.cumsum(np.bincount(p.row, minlength=p.shape[0]), out=out[1:])
    return out


class Reference:
    """scipy products on the configuration's patterns, one value set at a
    time; the structural pattern is computed once."""

    def __init__(self, ops: Operands):
        self._ops = ops
        self._a_ptr = _indptr(ops.a)
        self._b_ptr = _indptr(ops.b)
        self.indptr = self.indices = self._keys = None

    def _operands(self, a_vals: np.ndarray, dtype):
        ops = self._ops
        a = _csr(ops.a, self._a_ptr, a_vals.astype(dtype))
        b = _csr(ops.b, self._b_ptr, a_vals[ops.b_from_a].astype(dtype))
        return a, b

    def _product(self, a, b):
        x = (a @ b).tocsr()
        x.sort_indices()
        return x

    def _on_pattern(self, x) -> np.ndarray:
        """``x``'s values at the structural pattern's entries (0 where the
        product cancelled exactly and scipy dropped the entry)."""
        if x.nnz == self._keys.shape[0]:
            return x.data
        rows = np.repeat(np.arange(x.shape[0], dtype=np.int64), np.diff(x.indptr))
        keys = rows * x.shape[1] + x.indices
        out = np.zeros(self._keys.shape[0], x.dtype)
        out[np.searchsorted(self._keys, keys)] = x.data
        return out

    def bound(self, a_vals: np.ndarray) -> np.ndarray:
        """M = |A| @ |B| on the structural pattern (fixes the pattern on the
        first call)."""
        a, b = self._operands(np.abs(a_vals), np.float64)
        m = self._product(a, b)
        if self.indptr is None:
            self.indptr = m.indptr.astype(np.int64)
            self.indices = m.indices.astype(np.int32)
            rows = np.repeat(np.arange(m.shape[0], dtype=np.int64),
                             np.diff(m.indptr))
            self._keys = rows * m.shape[1] + m.indices
        return m.data

    @property
    def nnz(self) -> int:
        return int(self._keys.shape[0])

    def exact(self, a_vals: np.ndarray) -> np.ndarray:
        """R = A @ B in float64 on the structural pattern."""
        return self._on_pattern(self._product(*self._operands(a_vals, np.float64)))

    def control(self, a_vals: np.ndarray) -> np.ndarray:
        """C as a bfloat16 path would give it: inputs rounded to bfloat16,
        products and sums in float32, on the structural pattern."""
        low = a_vals.astype(ml_dtypes.bfloat16).astype(np.float32)
        return self._on_pattern(self._product(*self._operands(low, np.float32)))

    def readings(self, indptr, indices, data, a_vals, memo=None) -> dict:
        """``pattern_mismatch`` and ``value_err`` of one result. ``memo``
        (one dict per value set) keeps M and R across results of that set."""
        memo = {} if memo is None else memo
        if "bound" not in memo:
            memo["bound"] = self.bound(a_vals)
        if not (np.array_equal(indptr, self.indptr)
                and np.array_equal(indices, self.indices)
                and data.shape == (self.nnz,)):
            return {"pattern_mismatch": 1, "value_err": math.inf}
        if "exact" not in memo:
            memo["exact"] = self.exact(a_vals)
        err = float(np.max(np.abs(data.astype(np.float64) - memo["exact"])
                           / memo["bound"], initial=0.0))
        if not math.isfinite(err):
            err = math.inf
        return {"pattern_mismatch": 0, "value_err": err}
