"""Synchronous randomized Gauss-Seidel: the Leventhal-Lewis rate (paper
eq. 2), multi-RHS behaviour, the unit-diagonal reduction (Sec. 2.3), and the
TPU block variant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (a_norm_sq, block_banded_spd, block_gs_solve,
                        random_sparse_spd, rgs_general, rgs_solve, spd,
                        theory, to_unit_diagonal)


@pytest.fixture(scope="module")
def prob():
    return random_sparse_spd(192, row_nnz=6, n_rhs=3, seed=3)


def test_monotone_expected_decrease(prob):
    """Error decreases at (close to) the proven linear rate, averaged over
    seeds.  E_m <= (1 - lam_min/n)^m E_0  (paper eq. 2)."""
    n = prob.n
    m = 4 * n
    errs = []
    for seed in range(8):
        res = rgs_solve(prob.A, prob.b, jnp.zeros_like(prob.x_star),
                        prob.x_star, key=jax.random.key(seed),
                        num_iters=m, record_every=m)
        errs.append(np.asarray(res.err_sq[-1]))
    e0 = np.asarray(a_norm_sq(prob.A, -prob.x_star))
    bound = float(theory.ll_bound(1.0, m, float(prob.lam_min), n))
    mean_ratio = np.mean(errs, axis=0) / e0
    # Expectation bound with generous slack for 8-seed averaging noise.
    assert np.all(mean_ratio <= 3.0 * bound), (mean_ratio, bound)
    assert np.all(mean_ratio < 1e-1)


def test_converges_to_solution(prob):
    res = rgs_solve(prob.A, prob.b, jnp.zeros_like(prob.x_star), prob.x_star,
                    key=jax.random.key(0), num_iters=30 * prob.n)
    assert float(res.resid[-1].max()) < 1e-3


def test_multi_rhs_matches_single(prob):
    """Each RHS column evolves independently given shared directions."""
    res_all = rgs_solve(prob.A, prob.b, jnp.zeros_like(prob.x_star),
                        prob.x_star, key=jax.random.key(7), num_iters=256)
    res_one = rgs_solve(prob.A, prob.b[:, :1],
                        jnp.zeros_like(prob.x_star[:, :1]),
                        prob.x_star[:, :1], key=jax.random.key(7),
                        num_iters=256)
    np.testing.assert_allclose(np.asarray(res_all.x[:, 0]),
                               np.asarray(res_one.x[:, 0]), atol=1e-5)


def test_unit_diagonal_reduction():
    """Sec 2.3: general iteration on B == unit-diagonal iteration on DBD
    with y = D x (same directions)."""
    rng = np.random.default_rng(0)
    G = rng.standard_normal((48, 48))
    B = G @ G.T + 8 * np.eye(48)
    Bj = jnp.asarray(B, jnp.float32)
    A, d = to_unit_diagonal(Bj)
    z = jnp.asarray(rng.standard_normal((48, 1)), jnp.float32)
    coords = jax.random.randint(jax.random.key(5), (400,), 0, 48)
    y = rgs_general(Bj, z, jnp.zeros((48, 1), jnp.float32), coords=coords,
                    num_iters=400)
    # unit-diagonal run on A x = D z
    bz = d[:, None] * z
    x_star = jnp.linalg.solve(A, bz)
    from repro.core.rgs import SolveResult  # reuse scan path via explicit loop
    x = jnp.zeros((48, 1), jnp.float32)
    for r in np.asarray(coords):
        x = x.at[r].add(bz[r] - A[r] @ x)
    np.testing.assert_allclose(np.asarray(y[:, 0]),
                               np.asarray((d[:, None] * x)[:, 0]),
                               rtol=2e-3, atol=2e-3)


def test_block_gs_converges(prob):
    res = block_gs_solve(prob.A, prob.b, jnp.zeros_like(prob.x_star),
                         prob.x_star, key=jax.random.key(1), num_sweeps=30,
                         block=32, beta=0.9)
    assert float(res.resid[-1].max()) < 1e-2
    assert float(res.err_sq[-1].max()) < float(res.err_sq[0].max())


@pytest.mark.parametrize("make", [
    lambda: random_sparse_spd(320, row_nnz=8, seed=5),
    lambda: block_banded_spd(256, block=32, bands=2, seed=5),
], ids=["sparse", "banded"])
def test_large_n_eigen_extremes_match_dense(make, monkeypatch):
    """Above ``DENSE_EIG_MAX`` the generators take lam_min/lam_max from
    float64 host Lanczos instead of a dense eigvalsh; both give the same
    extremes to f32 rounding (ARPACK converges to machine precision)."""
    dense = make()
    monkeypatch.setattr(spd, "DENSE_EIG_MAX", 0)
    lanczos = make()
    np.testing.assert_array_equal(np.asarray(lanczos.A), np.asarray(dense.A))
    np.testing.assert_allclose(float(lanczos.lam_min), float(dense.lam_min),
                               rtol=1e-5)
    np.testing.assert_allclose(float(lanczos.lam_max), float(dense.lam_max),
                               rtol=1e-5)
