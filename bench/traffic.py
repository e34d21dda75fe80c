"""Value sets of a run, drawn from ``--seed``.

Traffic on a fixed pattern is a stream of fresh value sets, which is what
the plan/execute split is for. The idea is that of
``repro.data.pipeline.SpGEMMValueStream`` (step-indexed: set ``s`` is a pure
function of ``(seed, s)``), copied so the program cannot move it, with one
change: B's values are A's (``b_from_a``), so that ``A2`` is the product
its name says and not A times an unrelated B.

A run draws ``ring`` distinct sets before its window and cycles through
them; set ``ring`` is kept apart for warm-up, so a result left over from
warm-up never matches a set of the window.
"""
from __future__ import annotations

import numpy as np

__all__ = ["value_set", "value_ring"]


def value_set(seed: int, step: int, nnz_a: int, b_from_a: np.ndarray):
    """``(a_vals, b_vals)`` float32, standard normal, no exact zeros."""
    rng = np.random.default_rng((int(seed), int(step)))
    a = rng.standard_normal(nnz_a, dtype=np.float32)
    a[a == 0] = 1.0
    return a, a[b_from_a]


def value_ring(seed: int, ring: int, nnz_a: int, b_from_a: np.ndarray):
    """The window's ``ring`` sets and the warm-up set."""
    sets = [value_set(seed, s, nnz_a, b_from_a) for s in range(ring)]
    return sets, value_set(seed, ring, nnz_a, b_from_a)
