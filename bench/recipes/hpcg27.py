"""HPCG's problem: the 27-point stencil on an ``nx x ny x nz`` grid.

As HPCG's reference ``GenerateProblem_ref.cpp`` builds it: one row per
grid point, numbered with ``x`` fastest, then ``y``, then ``z``; the
diagonal holds ``diagonal`` (26) and each of the up to 26 neighbours in
the 3 x 3 x 3 cube around the point, clipped at the grid's faces, holds
``offdiagonal`` (-1).  The matrix is SPD and the same for every seed.

The program's coordinate Gauss-Seidel takes a unit diagonal, so the
matrix is divided by its diagonal before it is stored in float32: the
system ``(A / 26) x = b / 26`` has the same solution and the same
relative residuals as HPCG's ``A x = b``.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def _dims(cfg: dict) -> tuple[int, int, int]:
    return int(cfg["nx"]), int(cfg["ny"]), int(cfg["nz"])


def max_nnz(cfg: dict) -> int:
    """The stored entries, exactly: ``3 m - 2`` neighbour pairs along each
    axis of length ``m``."""
    nx, ny, nz = _dims(cfg)
    return (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)


def build(cfg: dict, seed: int) -> sp.csr_matrix:
    """The configuration's matrix (``seed`` is not used: HPCG's matrix is
    fixed): float32 values, canonical CSR."""
    nx, ny, nz = _dims(cfg)
    n = nx * ny * nz
    idx = np.arange(n).reshape(nz, ny, nx)
    rows, cols = [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                here = idx[_span(dz, nz), _span(dy, ny), _span(dx, nx)]
                there = idx[_span(-dz, nz), _span(-dy, ny), _span(-dx, nx)]
                rows.append(here.ravel())
                cols.append(there.ravel())
    r, c = np.concatenate(rows), np.concatenate(cols)
    diag = float(cfg["diagonal"])
    vals = np.where(r == c, diag, float(cfg["offdiagonal"])) / diag
    A = sp.csr_matrix((vals.astype(np.float32), (r, c)), shape=(n, n))
    A.sum_duplicates()
    A.sort_indices()
    return A


def _span(d: int, m: int) -> slice:
    """The points whose neighbour at offset ``-d`` along an axis of length
    ``m`` lies on the grid."""
    return slice(max(d, 0), m + min(d, 0))
