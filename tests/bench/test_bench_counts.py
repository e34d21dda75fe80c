"""The benchmark's yardstick on CPU: the matrix generator against its
definition, the work counts against brute force, the reference against a
dense product, and the control against the configured limit."""
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import harness, patterns, traffic, workcount  # noqa: E402
from bench.reference import Reference  # noqa: E402


GRIDS = [(2, 3, 4), (3, 3, 3)]


@pytest.fixture
def small(tmp_path):
    return {"grid": [8, 8, 16], "backend": "pallas_interpret",
            "plan_dir": str(tmp_path / "plans")}


def _tiny(grid):
    return {"matrix": "hpcg27", "grid": list(grid), "operation": "A2"}


def _dense(p, vals):
    out = np.zeros(p.shape)
    out[p.row, p.col] = vals
    return out


def _hpcg_by_its_loops(nx, ny, nz):
    """HPCG's GenerateProblem, loop by loop: for each row, the columns of
    the in-grid points of its 27-point stencil, in the order it visits them."""
    rows, cols = [], []
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                row = iz * nx * ny + iy * nx + ix
                for sz in (-1, 0, 1):
                    for sy in (-1, 0, 1):
                        for sx in (-1, 0, 1):
                            x, y, z = ix + sx, iy + sy, iz + sz
                            if 0 <= x < nx and 0 <= y < ny and 0 <= z < nz:
                                rows.append(row)
                                cols.append(z * nx * ny + y * nx + x)
    return np.array(rows), np.array(cols)


@pytest.mark.parametrize("grid", [(1, 1, 1), (2, 2, 2), (3, 4, 5), (5, 1, 7),
                                  (4, 4, 4), (8, 2, 3), (6, 5, 4)])
def test_hpcg27_is_hpcgs_operator(grid):
    mine = patterns.matrix_pattern(_tiny(grid))
    rows, cols = _hpcg_by_its_loops(*grid)
    n = int(np.prod(grid))
    assert mine.shape == (n, n)
    assert np.array_equal(mine.row, rows) and np.array_equal(mine.col, cols)
    assert mine.nnz == np.prod([3 * g - 2 for g in grid])
    dense = _dense(mine, 1.0)
    assert np.array_equal(dense, dense.T)


def test_patterns_refuse_what_they_cannot_run(tmp_path):
    with pytest.raises(ValueError, match="unknown operation"):
        patterns.operands(dict(_tiny((2, 2, 2)), operation="AAt"))
    with pytest.raises(FileNotFoundError, match="no matrices file"):
        patterns.operands(dict(_tiny((2, 2, 2)), matrix="no_such_matrix"))


@pytest.mark.parametrize("grid", GRIDS)
def test_operands_and_values(grid):
    ops = patterns.operands(_tiny(grid))
    a, _ = traffic.value_set(2**31 + 5, 0, ops.a.nnz, ops.b_from_a)
    b = a[ops.b_from_a]
    da, db = _dense(ops.a, a), _dense(ops.b, b)
    assert np.array_equal(db, da)
    # B's pattern is canonical: row-major, no duplicates.
    keys = ops.b.row.astype(np.int64) * ops.b.shape[1] + ops.b.col
    assert np.all(np.diff(keys) > 0)
    again, _ = traffic.value_set(2**31 + 5, 0, ops.a.nnz, ops.b_from_a)
    other, _ = traffic.value_set(2**31 + 6, 0, ops.a.nnz, ops.b_from_a)
    assert np.array_equal(a, again) and not np.array_equal(a, other)
    assert a.dtype == np.float32 and np.all(a != 0)


@pytest.mark.parametrize("grid", GRIDS)
def test_work_counts_against_brute_force(grid):
    ops = patterns.operands(_tiny(grid))
    terms = 0
    for i, k in zip(ops.a.row, ops.a.col):
        terms += int(np.sum(ops.b.row == k))
    assert workcount.useful_flops(ops) == 2 * terms
    assert workcount.compulsory_bytes(ops, 100) == 4 * (ops.a.nnz + ops.b.nnz + 100)
    peak = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert workcount.roofline_floor_s(2e3, 1e3, peak) == (2.0, "compute")
    assert workcount.roofline_floor_s(1e3, 3e3, peak) == (3.0, "memory")


@pytest.mark.parametrize("grid", GRIDS)
def test_reference_against_a_dense_product(grid):
    ops = patterns.operands(_tiny(grid))
    a, b = traffic.value_set(1, 0, ops.a.nnz, ops.b_from_a)
    da, db = _dense(ops.a, a.astype(np.float64)), _dense(ops.b, b.astype(np.float64))
    exact = da @ db
    mag = np.abs(da) @ np.abs(db)
    ref = Reference(ops)
    bound = ref.bound(a)
    rows, cols = np.nonzero(mag)
    assert np.array_equal(ref.indices, cols)
    assert np.array_equal(ref.indptr, np.concatenate([[0], np.cumsum(np.bincount(rows, minlength=ops.a.shape[0]))]))
    assert np.allclose(bound, mag[rows, cols])
    assert np.allclose(ref.exact(a), exact[rows, cols])
    c = exact[rows, cols].astype(np.float32)
    r = ref.readings(ref.indptr, ref.indices, c, a)
    assert r["pattern_mismatch"] == 0 and r["value_err"] < 1e-6
    c[len(c) // 2] += 1e-3 * bound[len(c) // 2]
    assert ref.readings(ref.indptr, ref.indices, c, a)["value_err"] > 1e-4
    assert ref.readings(ref.indptr, ref.indices[::-1].copy(), c, a) == {
        "pattern_mismatch": 1, "value_err": math.inf}
    c[0] = np.nan
    assert ref.readings(ref.indptr, ref.indices, c, a)["value_err"] == math.inf


def test_cancelled_entries_stay_on_the_pattern():
    """C = [a0, a1] @ [a0, a0]^T cancels to exactly 0 for a = [1, -1]: scipy
    drops that entry from R, and the reference puts it back as 0."""
    ops = patterns.Operands(
        patterns.Pattern(np.array([0, 0], np.int32), np.array([0, 1], np.int32), (1, 2)),
        patterns.Pattern(np.array([0, 1], np.int32), np.array([0, 0], np.int32), (2, 1)),
        np.array([0, 0]))
    ref = Reference(ops)
    a = np.array([1.0, -1.0], np.float32)
    assert ref.bound(a).tolist() == [2.0]
    assert ref.exact(a).tolist() == [0.0]
    r = ref.readings(ref.indptr, ref.indices, np.array([0.0], np.float32), a)
    assert r == {"pattern_mismatch": 0, "value_err": 0.0}


def test_the_control_fails_and_the_program_passes(small):
    """At a size a test run holds: the bfloat16 control reads above the
    configured limit on every seed, the program below it (the chip run of
    bench/control.py reads the same numbers at the cells' own size)."""
    from bench import control

    cell = harness.resolve("hpcg40-A2.single")
    limit = cell.config["check"]["value_err_limit"]
    rows = control.readings_over_seeds(cell, [4, 2**31 + 11, 77], 0.2, overrides=small)
    for row in rows:
        assert row["program"]["compared"] >= 1 and row["program"]["failed"] == 0
        assert row["program"]["value_err"] < limit / 10
        assert row["control"]["value_err"] > limit * 10
        assert row["program"]["pattern_mismatch"] == row["control"]["pattern_mismatch"] == 0
