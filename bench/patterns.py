"""Sparsity patterns of the benchmark's configurations.

A configuration names its matrix by ``"matrix"``: the generator
``bench/matrices/<matrix>.py``, whose ``pattern(cfg)`` gives the
coordinates from the configuration's own sizes, so a later configuration
adds its matrix as a new file. Only coordinates are made here: the values
of a run come from :mod:`bench.traffic`. The operation is ``A2``
(C = A @ A), the product the FSpGEMM paper forms.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import ROOT, load_module

__all__ = ["Pattern", "Operands", "matrix_pattern", "operands"]


@dataclasses.dataclass(frozen=True)
class Pattern:
    """Canonical (row-major, duplicate-free) coordinates of a matrix."""

    row: np.ndarray  # int32
    col: np.ndarray  # int32
    shape: tuple

    @property
    def nnz(self) -> int:
        return int(self.row.shape[0])


@dataclasses.dataclass(frozen=True)
class Operands:
    """A, B and the map from A's values to B's: ``b_vals = a_vals[b_from_a]``."""

    a: Pattern
    b: Pattern
    b_from_a: np.ndarray


def matrix_pattern(cfg: dict, root: str = ROOT) -> Pattern:
    row, col, shape = load_module("matrices", cfg["matrix"], root).pattern(cfg)
    keys = row.astype(np.int64) * shape[1] + col
    if keys.size and not np.all(np.diff(keys) > 0):
        raise ValueError(f"matrix {cfg['matrix']!r} is not row-major and duplicate-free")
    return Pattern(row, col, tuple(shape))


def operands(cfg: dict, root: str = ROOT) -> Operands:
    op = cfg["operation"]
    if op != "A2":
        raise ValueError(f"unknown operation {op!r} (A2)")
    a = matrix_pattern(cfg, root)
    if a.shape[0] != a.shape[1]:
        raise ValueError(f"A2 needs a square matrix, got {a.shape}")
    return Operands(a, a, np.arange(a.nnz, dtype=np.int64))
