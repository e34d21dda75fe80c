"""The sparse operator of the HPCG benchmark (https://github.com/hpcg-benchmark/hpcg,
``GenerateProblem``): a 27-point stencil on an ``nx x ny x nz`` grid.

Row ``iz*nx*ny + iy*nx + ix`` (x fastest) holds a nonzero in the column of
every grid point ``(ix+sx, iy+sy, iz+sz)``, ``sx, sy, sz`` in ``{-1, 0, 1}``,
that lies in the grid; columns ascend. HPCG's values (26 on the diagonal,
-1 off it) are not used: a run's values come from :mod:`bench.traffic`.
The configuration gives the grid as ``"grid": [nx, ny, nz]``.
"""
import numpy as np


def pattern(cfg):
    """``(row, col, shape)`` of the operator, row-major, duplicate-free."""
    nx, ny, nz = (int(g) for g in cfg["grid"])
    n = nx * ny * nz
    iz, iy, ix = (a.ravel() for a in np.meshgrid(
        np.arange(nz), np.arange(ny), np.arange(nx), indexing="ij"))
    cols, inside = [], []
    for sz in (-1, 0, 1):
        for sy in (-1, 0, 1):
            for sx in (-1, 0, 1):
                x, y, z = ix + sx, iy + sy, iz + sz
                inside.append((x >= 0) & (x < nx) & (y >= 0) & (y < ny)
                              & (z >= 0) & (z < nz))
                cols.append(z * nx * ny + y * nx + x)
    keep = np.stack(inside, axis=1)  # [n, 27], stencil points in column order
    col = np.stack(cols, axis=1)[keep].astype(np.int32)
    row = np.repeat(np.arange(n, dtype=np.int32), keep.sum(axis=1))
    return row, col, (n, n)
