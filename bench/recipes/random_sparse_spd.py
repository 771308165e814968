"""The paper's reference-scenario matrix, made from a seed.

A copy of ``random_sparse_spd``'s recipe, built sparse and densified once:
``A = I + c (S + S^T) / 2`` with ``row_nnz`` distinct random columns per
row of ``S`` (standard normal values), the diagonal of the symmetric part
dropped, and ``c`` set by Gershgorin so that the largest off-diagonal row
mass is ``offdiag_mass``.  The unit diagonal and the Gershgorin bound make
``A`` SPD with ``lam_min >= 1 - offdiag_mass``.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def max_nnz(cfg: dict) -> int:
    """An upper bound on the stored entries: the diagonal and both
    triangles' ``row_nnz`` entries per row."""
    return int(cfg["n"]) * (1 + 2 * int(cfg["row_nnz"]))


def build(cfg: dict, seed: int) -> sp.csr_matrix:
    """The configuration's matrix for ``seed``: float32 values, canonical
    CSR (sorted column ids, no duplicates).

    The pattern is drawn again, whole, until its fullest row holds exactly
    ``cfg["row_cap"]`` entries (the recipe's most frequent fullest row at
    this size), so every seed gives the program the same shapes and its
    compiled programs carry over from run to run."""
    n, row_nnz = int(cfg["n"]), int(cfg["row_nnz"])
    rng = np.random.default_rng([seed, n])
    while True:
        cols = _distinct_columns(rng, n, row_nnz)
        rows = np.repeat(np.arange(n), row_nnz)
        P = sp.csr_matrix((np.ones(n * row_nnz), (rows, cols.ravel())),
                          shape=(n, n))
        P = (P + P.T).tolil()
        P.setdiag(0.0)
        P = P.tocsr()
        P.eliminate_zeros()
        if int(np.diff(P.indptr).max()) + 1 == int(cfg["row_cap"]):
            break
    vals = rng.standard_normal((n, row_nnz))
    S = sp.csr_matrix((vals.ravel(), (rows, cols.ravel())), shape=(n, n))
    M = ((S + S.T) * 0.5).tolil()
    M.setdiag(0.0)
    M = M.tocsr()
    M.eliminate_zeros()
    M = M * (float(cfg["offdiag_mass"]) / abs(M).sum(axis=1).max())
    A = (sp.identity(n, format="csr") + M).astype(np.float32).tocsr()
    A.sum_duplicates()
    A.sort_indices()
    return A


def _distinct_columns(rng, n, row_nnz):
    """``row_nnz`` distinct uniform columns per row: a row with a repeat
    is drawn again, whole (the same law as sampling without replacement)."""
    cols = rng.integers(0, n, size=(n, row_nnz))
    while True:
        s = np.sort(cols, axis=1)
        dup = (s[:, 1:] == s[:, :-1]).any(axis=1)
        if not dup.any():
            return cols
        cols[dup] = rng.integers(0, n, size=(int(dup.sum()), row_nnz))
