"""The operator of PETSc's GAMG elasticity test (``src/ksp/ksp/tutorials/ex56.c``):
3D linear elasticity with tri-linear hexahedral (Q1) elements, 3 displacement
dofs per node (block size 3), on a box of ``nx x ny x nz`` elements.

Nodes lie on the ``(nx+1) x (ny+1) x (nz+1)`` grid, node
``iz*(nx+1)*(ny+1) + iy*(nx+1) + ix`` (x fastest); its dofs are
``3*node + c``, ``c`` in ``{0, 1, 2}`` (interleaved). Two nodes couple when
they share an element, that is when they are at most one grid step apart in
each direction (27 nodes around an interior node), and a coupled pair is a
dense 3 x 3 block. Row-major and duplicate-free, columns ascending. The
values (E = 1, nu = 0.25, Dirichlet rows on y = 0) are not modelled: the
Dirichlet rows keep their blocks in the pattern, and a run's values come
from :mod:`bench.traffic`. The configuration gives the elements per side as
``"grid": [nx, ny, nz]``; ex56's ``-ne n`` is ``[n, n, n]``.
"""
import numpy as np


def pattern(cfg):
    """``(row, col, shape)`` of the operator, row-major, duplicate-free."""
    nx, ny, nz = (int(g) + 1 for g in cfg["grid"])  # nodes per side
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
    # [node, neighbour] in ascending neighbour order, widened to
    # [node, row dof, neighbour, column dof].
    keep = np.broadcast_to(np.stack(inside, axis=1)[:, None, :, None], (n, 3, 27, 3))
    dof = np.arange(3, dtype=np.int64)
    col = 3 * np.stack(cols, axis=1).astype(np.int64)[:, None, :, None] + dof
    row = 3 * np.arange(n, dtype=np.int64)[:, None, None, None] + dof[:, None, None]
    col = np.broadcast_to(col, keep.shape)[keep].astype(np.int32)
    row = np.broadcast_to(row, keep.shape)[keep].astype(np.int32)
    return row, col, (3 * n, 3 * n)
