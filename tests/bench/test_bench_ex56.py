"""The ex56-ne32-A2 configuration: PETSc ex56's elasticity pattern, the cell
run small on CPU, with and without a split schedule, and the readers of the
kernel's call count and time per triple."""
import os
import sys
import time

import numpy as np
import pytest
import scipy.sparse as sp

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import harness, patterns, spans, trace  # noqa: E402

CELL = "ex56-ne32-A2.stream"
SEED = 2**31 + 2**20 + 7  # above 32 signed bits, as the check's seeds are


def _pattern(ne):
    return patterns.matrix_pattern({"matrix": "ex56", "grid": [ne, ne, ne]})


def _csr(p):
    return sp.csr_matrix((np.ones(p.nnz, np.float32), (p.row, p.col)), shape=p.shape)


@pytest.mark.parametrize("ne", [1, 2, 3, 5])
def test_pattern_is_ex56s(ne):
    """3 (ne+1)^3 rows; 81 nonzeros on an interior node's rows (27 nodes
    times 3 dofs); a symmetric pattern made of whole 3 x 3 blocks; nnz
    9 (3 (ne+1) - 2)^3, the closed form of 27-node coupling."""
    p = _pattern(ne)
    n = 3 * (ne + 1) ** 3
    assert p.shape == (n, n)
    assert p.nnz == 9 * (3 * (ne + 1) - 2) ** 3
    counts = np.bincount(p.row, minlength=n)
    node = np.arange(n) // 3
    ix, iy, iz = node % (ne + 1), node // (ne + 1) % (ne + 1), node // (ne + 1) ** 2
    interior = ((ix > 0) & (ix < ne) & (iy > 0) & (iy < ne) & (iz > 0) & (iz < ne))
    assert np.all(counts[interior] == 81) and np.all(counts <= 81)
    assert np.all(counts[~interior] < 81)
    a = _csr(p)
    assert (a != a.T).nnz == 0
    blocks = sp.csr_matrix((np.ones(p.nnz), (p.row // 3, p.col // 3)))
    assert np.all(blocks.data == 9)  # every coupled node pair is a whole 3 x 3 block
    assert np.array_equal(np.diff(blocks.indptr), counts[::3] // 3)


def test_pattern_at_ne_32():
    """The configuration's size: 107,811 rows, 9 * 97^3 nonzeros in A and
    9 * 159^3 in C = A^2."""
    p = patterns.matrix_pattern(harness.resolve(CELL).config)
    assert p.shape == (107_811, 107_811)
    assert p.nnz == 8_214_057 == 9 * 97**3
    a = _csr(p)
    assert (a @ a).nnz == 36_177_111 == 9 * 159**3


def _small(tmp_path):
    return {"grid": [3, 3, 3], "tile": 8, "backend": "pallas_interpret",
            "plan_dir": str(tmp_path / "plans")}


@pytest.mark.parametrize("budget", [None, 1_000])
def test_cell_runs_small_and_is_correct(tmp_path, monkeypatch, budget):
    """The cell end to end at ne 3 and tile 8 in interpret mode, its
    3,068-triple schedule in one call, and in four with the per-call
    budget at 1,000."""
    from repro.core import perfmodel

    if budget is not None:
        monkeypatch.setattr(perfmodel, "SCHEDULE_TRIPLES_PER_CALL", budget)
    calls = []
    result = harness.run_cell(
        harness.resolve(CELL), SEED, 0.3, False, t0=time.perf_counter(),
        overrides=_small(tmp_path),
        plan_hook=lambda plan: calls.append(plan.report.kernel_calls))
    assert calls == [1 if budget is None else 4]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["checks"]["compared"]["value"] >= 1
    assert result["checks"]["value_err"]["value"] < 1e-4


def _read(metric, ctx):
    return harness.load_module("metrics", metric).read(ctx)


KERNEL = "%k = f32[4] custom-call(), " + harness.load_module(
    "metrics", "kernel_ms").KERNEL_MARK


def _events(dispatch_args):
    """Two products in a 100 ns window, each dispatched once and running
    the kernel for 6 ns on the device; a third dispatch starts after the
    window."""
    return {
        "devices": {"/device:TPU:0": [["fusion", 5, 10], [KERNEL, 15, 6],
                                      ["fusion", 55, 10], [KERNEL, 65, 6]]},
        "scopes": {"/device:TPU:0": ["spgemm.bind", "spgemm.kernel"] * 2},
        "host": [["bench.window", 0, 100]],
        "spans": [["spgemm.dispatch", s, 2, dict(dispatch_args, step=i)]
                  for i, s in enumerate((1, 51, 120))],
    }


def _ctx(events, completed=2):
    w0, w1 = trace._window(events)
    return {"trace": trace.reduce(events), "program": spans.reduce(events),
            "completed": completed,
            "dispatches": [a for n, s, _, a in events["spans"]
                           if n == "spgemm.dispatch" and w0 <= s < w1]}


def test_readers_of_kernel_calls_and_time_per_triple():
    ctx = _ctx(_events({"kernel_calls": 2, "triples": 96_945, "bind_values": 3}))
    assert len(ctx["dispatches"]) == 2
    assert _read("kernel_calls", ctx) == pytest.approx(2.0)
    # 6 ns of kernel a product over its 96,945 triples, in microseconds.
    assert _read("kernel_triple_us", ctx) == pytest.approx(6e-9 * 1e6 / 96_945)
    # A program whose spans lack the counters (the parent's) reads nothing.
    bare = _ctx(_events({"bind_values": 3}))
    assert _read("kernel_calls", bare) is None
    assert _read("kernel_triple_us", bare) is None
    for metric in ("kernel_calls", "kernel_triple_us"):
        assert _read(metric, {"trace": None, "completed": 2}) is None
