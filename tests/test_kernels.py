"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape/dtype
sweeps + allclose, per the kernels/ contract — plus ragged/degenerate-shape
fuzzing of the sparse matvec kernels: empty panels, all-zero rows,
single-row panels, ragged last panels, and the agreement of the
scalar-prefetch empty-panel-skipping spmv_csr variant with the base
kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CsrOp, block_banded_spd, random_sparse_spd
from repro.core.spd import ell_from_dense
from repro.kernels import ops, ref
from repro.kernels.bbmv import dense_to_bands

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # bare jax+pytest environment: deterministic cases only
    HAVE_HYPOTHESIS = False


@pytest.mark.parametrize("n,block,k", [(256, 128, 8), (512, 128, 64), (512, 256, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_gs_sweep(n, block, k, dtype):
    prob = block_banded_spd(n, block=block, bands=1, n_rhs=k, seed=0)
    A = prob.A.astype(dtype)
    b = prob.b.astype(dtype)
    x0 = jnp.zeros_like(b)
    blocks = jax.random.randint(jax.random.key(1), (12,), 0, n // block)
    out = ops.block_gs_sweep(A, b, x0, blocks, block=block, beta=0.9)
    want = ref.block_gs_sweep_ref(A, b, x0, blocks, block=block, beta=0.9)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("n,block,bands,k", [(256, 128, 1, 4), (512, 128, 2, 8),
                                             (768, 256, 1, 16)])
def test_bbmv(n, block, bands, k):
    prob = block_banded_spd(n, block=block, bands=bands, n_rhs=k, seed=1)
    Ab = dense_to_bands(prob.A, bands=bands, block=block)
    out = ops.bbmv(Ab, prob.x_star, bands=bands, block=block)
    want = ref.bbmv_ref(prob.A, prob.x_star)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("n,width,k", [(256, 32, 4), (384, 48, 8)])
def test_spmv_ell(n, width, k):
    prob = random_sparse_spd(n, row_nnz=width // 4, n_rhs=k, seed=2)
    vals, cols = ell_from_dense(prob.A, width)
    out = ops.spmv_ell(vals, cols, prob.x_star, tile=128)
    want = ref.spmv_ell_ref(vals, cols, prob.x_star)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    # with enough ELL width the kernel equals the dense matvec too
    np.testing.assert_allclose(np.asarray(out), np.asarray(prob.A @ prob.x_star),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Sparse matvec kernels on ragged/degenerate shapes (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def _random_sparse(m, n, row_nnz, seed, *, zero_row_stride=0,
                   zero_panel=None, rows_per_panel=8):
    """Dense (m, n) matrix with ~row_nnz nonzeros/row, optionally zeroing
    every ``zero_row_stride``-th row and a whole panel of rows."""
    rng = np.random.default_rng(seed)
    A = np.zeros((m, n), np.float32)
    for i in range(m):
        cols = rng.choice(n, size=min(row_nnz, n), replace=False)
        A[i, cols] = rng.standard_normal(cols.size).astype(np.float32)
    if zero_row_stride:
        A[::zero_row_stride] = 0.0
    if zero_panel is not None:
        lo = zero_panel * rows_per_panel
        A[lo:lo + rows_per_panel] = 0.0
    return A


def _check_csr_kernels(A, *, rows_per_panel, k=3, seed=9):
    """Both spmv_csr variants vs the segment-sum reference vs dense, and
    the skip variant against the base kernel.

    The two kernels sum the same <= row_nnz products per row in the same
    slot order, but the CPU backend's fusion may contract a product and
    its add into one FMA in one kernel and not the other, which moves a
    partial sum by 1 ulp: at most row_nnz ulps of the largest partial sum
    (|y| <~ 30 here, ulp ~ 2e-6), far inside atol=1e-4."""
    m, n = A.shape
    op = CsrOp.from_dense(jnp.asarray(A), rows_per_panel=rows_per_panel)
    x = jax.random.normal(jax.random.key(seed), (n, k))
    want = A @ np.asarray(x)
    y_base = op.matvec(x, interpret=True)
    y_skip = op.matvec(x, interpret=True, skip_empty=True)
    y_ref = op.matvec_ref(x)
    np.testing.assert_allclose(np.asarray(y_base), want, atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(y_ref), want, atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(y_skip), np.asarray(y_base),
                               atol=1e-4, rtol=1e-6)
    # empty panels are skipped, not summed: their rows are exact zeros
    empty = ~np.asarray(A != 0).any(axis=1)
    assert not np.asarray(y_skip)[empty].any()


@pytest.mark.parametrize("case", [
    # empty panels: a zeroed 8-row panel plus every 3rd row zero
    dict(m=64, n=64, row_nnz=6, zero_row_stride=3, zero_panel=2,
         rows_per_panel=8),
    # single-row panels (rows_per_panel=1): every panel is one row,
    # zero rows become entirely empty panels
    dict(m=40, n=24, row_nnz=4, zero_row_stride=5, rows_per_panel=1),
    # ragged last panel: m not a multiple of rows_per_panel
    dict(m=53, n=32, row_nnz=5, rows_per_panel=8),
    # rectangular wide + a zero panel
    dict(m=32, n=96, row_nnz=7, zero_panel=0, rows_per_panel=8),
    # everything empty: the all-zero matrix
    dict(m=24, n=16, row_nnz=0, rows_per_panel=8),
])
def test_spmv_csr_degenerate_shapes(case):
    rows_per_panel = case.pop("rows_per_panel")
    A = _random_sparse(**case, seed=11, rows_per_panel=rows_per_panel)
    _check_csr_kernels(A, rows_per_panel=rows_per_panel)


def test_spmv_ell_degenerate_shapes():
    # all-zero rows pad to duplicate column 0 entries with zero values —
    # the kernel must not double-count them
    prob = random_sparse_spd(256, row_nnz=6, n_rhs=2, seed=4)
    A = np.array(prob.A)
    A[::4] = 0.0
    vals, cols = ell_from_dense(jnp.asarray(A), 32)
    x = jax.random.normal(jax.random.key(5), (256, 2))
    out = ops.spmv_ell(vals, cols, x, tile=128)
    np.testing.assert_allclose(np.asarray(out), A @ np.asarray(x),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.spmv_ell_ref(vals, cols, x)),
                               atol=1e-5)
    # width-1 windows (a diagonal-ish matrix) at a non-tile-aligned n: the
    # kernel wrapper refuses the shape instead of swapping in the
    # reference, and EllOp.matvec pads to whole tiles so the kernel runs
    from repro.core import EllOp
    D = np.zeros((72, 72), np.float32)
    D[np.arange(72), (np.arange(72) * 7) % 72] = \
        np.random.default_rng(0).standard_normal(72).astype(np.float32)
    dv, dc = ell_from_dense(jnp.asarray(D), 1)
    xd = jax.random.normal(jax.random.key(6), (72, 3))
    with pytest.raises(ValueError, match="do not tile"):
        ops.spmv_ell(dv, dc, xd)
    np.testing.assert_allclose(np.asarray(EllOp(dv, dc).matvec(xd)),
                               D @ np.asarray(xd), atol=1e-4, rtol=1e-4)


if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=12)
    @given(m=st.integers(1, 80), n=st.sampled_from([8, 16, 32, 64]),
           row_nnz=st.integers(0, 8), rows_per_panel=st.sampled_from([1, 4, 8]),
           zero_row_stride=st.sampled_from([0, 2, 3]),
           seed=st.integers(0, 2 ** 16))
    def test_spmv_csr_fuzz(m, n, row_nnz, rows_per_panel, zero_row_stride,
                           seed):
        A = _random_sparse(m, n, row_nnz, seed % 997,
                           zero_row_stride=zero_row_stride,
                           rows_per_panel=rows_per_panel)
        _check_csr_kernels(A, rows_per_panel=rows_per_panel, k=2)

    @settings(deadline=None, max_examples=10)
    @given(n=st.sampled_from([128, 256, 384]), row_nnz=st.integers(1, 10),
           width_pad=st.integers(0, 8), seed=st.integers(0, 2 ** 16))
    def test_spmv_ell_fuzz(n, row_nnz, width_pad, seed):
        prob = random_sparse_spd(n, row_nnz=row_nnz, n_rhs=2,
                                 seed=seed % 997)
        An = np.asarray(prob.A)
        width = int((An != 0).sum(axis=1).max()) + width_pad
        vals, cols = ell_from_dense(prob.A, width)
        x = jax.random.normal(jax.random.key(seed % 101), (n, 2))
        out = ops.spmv_ell(vals, cols, x, tile=128)
        np.testing.assert_allclose(np.asarray(out), An @ np.asarray(x),
                                   atol=1e-3, rtol=1e-3)


def test_block_gs_kernel_solves():
    """End-to-end: repeated kernel sweeps actually solve the system."""
    prob = block_banded_spd(512, block=128, bands=1, n_rhs=8, seed=5)
    x = jnp.zeros_like(prob.b)
    nb = 512 // 128
    for sweep in range(40):
        blocks = jax.random.permutation(jax.random.key(sweep), nb)
        x = ops.block_gs_sweep(prob.A, prob.b, x, blocks, block=128, beta=1.0)
    resid = float(jnp.linalg.norm(prob.b - prob.A @ x) / jnp.linalg.norm(prob.b))
    assert resid < 1e-3, resid
