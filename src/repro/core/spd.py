"""SPD problem construction and normalization utilities.

The paper assumes WLOG a unit diagonal (Sec. 2.3): for a general SPD ``B``
we solve ``A x = D z`` with ``A = D B D``, ``D = diag(B)^{-1/2}``, and map the
iterates back by ``y = D x``.  The generators below produce the paper's
*reference scenario*: large sparse SPD with between C1 and C2 nonzeros per
row and a small C2/C1 ratio.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class SPDProblem(NamedTuple):
    """A unit-diagonal SPD system ``A x = b`` with known solution."""

    A: jax.Array  # (n, n) dense, unit diagonal, SPD
    b: jax.Array  # (n, k) right-hand sides (k >= 1; the paper uses k = 51)
    x_star: jax.Array  # (n, k) exact solution
    # Diagnostics used by the theory module / tests.
    lam_min: jax.Array
    lam_max: jax.Array

    @property
    def n(self) -> int:
        return self.A.shape[0]

    @property
    def kappa(self) -> jax.Array:
        return self.lam_max / self.lam_min


def to_unit_diagonal(B: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Return ``(A, d)`` with ``A = D B D`` unit-diagonal, ``D = diag(d)``."""
    d = 1.0 / jnp.sqrt(jnp.diagonal(B))
    A = B * d[:, None] * d[None, :]
    # Exact ones on the diagonal (kills rounding fuzz that breaks (d,d)_A = 1).
    A = A.at[jnp.arange(A.shape[0]), jnp.arange(A.shape[0])].set(1.0)
    return A, d


#: Largest order whose eigenvalue extremes come from a dense ``eigvalsh``.
#: Above it (the n = 16384 reference scale) a dense decomposition costs
#: minutes — on a TPU its compile alone outgrows a 40 GiB host — so the
#: extremes come from float64 Lanczos on a sparse host copy instead.
DENSE_EIG_MAX = 4096


def _extreme_eigs(A: jax.Array) -> tuple[jax.Array, jax.Array]:
    if A.shape[0] <= DENSE_EIG_MAX:
        evals = jnp.linalg.eigvalsh(A)
        return evals[0], evals[-1]
    import scipy.sparse
    from scipy.sparse.linalg import eigsh
    S = scipy.sparse.csr_matrix(np.asarray(A, np.float64))
    lo, hi = eigsh(S, k=2, which="BE", return_eigenvectors=False)
    return jnp.asarray(lo, A.dtype), jnp.asarray(hi, A.dtype)


def _finish(A: np.ndarray, key: jax.Array, n_rhs: int) -> SPDProblem:
    A = jnp.asarray(A, jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    A, _ = to_unit_diagonal(A)
    lam_min, lam_max = _extreme_eigs(A)
    x_star = jax.random.normal(key, (A.shape[0], n_rhs), A.dtype)
    b = A @ x_star
    return SPDProblem(A=A, b=b, x_star=x_star, lam_min=lam_min, lam_max=lam_max)


def random_sparse_spd(
    n: int,
    row_nnz: int = 8,
    *,
    offdiag: float = 0.9,
    n_rhs: int = 1,
    seed: int = 0,
) -> SPDProblem:
    """Reference-scenario matrix: ~``row_nnz`` nonzeros/row, unit diagonal.

    ``A = I + c * (S + S^T)/2`` with ``c`` chosen via Gershgorin so that
    ``lam_min >= 1 - offdiag > 0``.  ``offdiag -> 1`` raises the condition
    number.  This mirrors the social-media matrix of Sec. 8 structurally:
    unstructured sparsity, modest nnz/row, multiple right-hand sides.
    """
    rng = np.random.default_rng(seed)
    M = np.zeros((n, n))
    for i in range(n):
        cols = rng.choice(n, size=row_nnz, replace=False)
        M[i, cols] = rng.standard_normal(row_nnz)
    M = (M + M.T) / 2.0
    np.fill_diagonal(M, 0.0)
    s = np.abs(M).sum(axis=1).max()
    M *= offdiag / max(s, 1e-30)
    A = np.eye(n) + M
    return _finish(A, jax.random.key(seed + 1), n_rhs)


def laplacian_spd(side: int, *, shift: float = 1e-2, n_rhs: int = 1, seed: int = 0) -> SPDProblem:
    """2-D grid Laplacian + shift, unit-diagonal-normalized.

    Ill-conditioned as ``side`` grows (kappa ~ side^2 / shift): the stress
    case where lam_min shrinks with n, discussed in the paper's weak-scaling
    remarks.
    """
    n = side * side
    A = np.zeros((n, n))
    for i in range(side):
        for j in range(side):
            p = i * side + j
            A[p, p] = 4.0 + shift
            for di, dj in ((1, 0), (-1, 0), (0, 1), (0, -1)):
                q_i, q_j = i + di, j + dj
                if 0 <= q_i < side and 0 <= q_j < side:
                    A[p, q_i * side + q_j] = -1.0
    return _finish(A, jax.random.key(seed + 1), n_rhs)


def dense_spd(n: int, *, n_rhs: int = 1, seed: int = 0) -> SPDProblem:
    """Dense Wishart-plus-identity SPD (outside the reference scenario)."""
    rng = np.random.default_rng(seed)
    G = rng.standard_normal((n, n))
    A = G @ G.T / n + np.eye(n)
    return _finish(A, jax.random.key(seed + 1), n_rhs)


def block_banded_spd(
    n: int, *, block: int = 128, bands: int = 2, offdiag: float = 0.9, n_rhs: int = 1, seed: int = 0
) -> SPDProblem:
    """Block-banded SPD used by the blocked Pallas kernels.

    Nonzeros live in ``bands`` blocks of width ``block`` on each side of the
    diagonal; contiguous block structure is the TPU-friendly layout argued
    for in DESIGN.md (HBM->VMEM streams stay contiguous).
    """
    assert n % block == 0
    rng = np.random.default_rng(seed)
    nb = n // block
    M = np.zeros((n, n))
    for bi in range(nb):
        for bj in range(max(0, bi - bands), min(nb, bi + bands + 1)):
            if bi == bj:
                continue
            blk = rng.standard_normal((block, block)) / block
            M[bi * block:(bi + 1) * block, bj * block:(bj + 1) * block] = blk
    M = (M + M.T) / 2.0
    np.fill_diagonal(M, 0.0)
    s = np.abs(M).sum(axis=1).max()
    M *= offdiag / max(s, 1e-30)
    A = np.eye(n) + M
    return _finish(A, jax.random.key(seed + 1), n_rhs)


@functools.partial(jax.jit, static_argnames=("width",))
def ell_from_dense(A: jax.Array, width: int) -> tuple[jax.Array, jax.Array]:
    """Convert dense ``A`` to fixed-width ELL: values (n, width), cols (n, width).

    Keeps the ``width`` largest-magnitude entries per row (exact when each
    row has <= width nonzeros).  Padding uses col = row's own index with
    value 0 so gathers stay in-bounds.
    """
    n = A.shape[0]
    mag = jnp.abs(A)
    _, cols = jax.lax.top_k(mag, width)  # (n, width)
    vals = jnp.take_along_axis(A, cols, axis=1)
    keep = jnp.take_along_axis(mag, cols, axis=1) > 0
    vals = jnp.where(keep, vals, 0.0)
    cols = jnp.where(keep, cols, jnp.arange(n)[:, None])
    return vals, cols


def a_norm_sq(A: jax.Array, v: jax.Array) -> jax.Array:
    """``||v||_A^2`` per RHS column: v is (n,) or (n, k)."""
    if v.ndim == 1:
        return v @ (A @ v)
    return jnp.einsum("nk,nk->k", v, A @ v)
