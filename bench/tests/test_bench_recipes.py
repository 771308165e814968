"""The configurations' matrices, by hand."""
import numpy as np
import pytest
import scipy.sparse as sp

from bench.recipes import hpcg27, random_sparse_spd

HPCG = {"nx": 3, "ny": 3, "nz": 3, "diagonal": 26.0, "offdiagonal": -1.0}


def test_hpcg27_hand_count():
    A = hpcg27.build(HPCG, seed=0)
    # 7 neighbour pairs (|i - j| <= 1) along each axis of 3 points
    assert A.shape == (27, 27) and A.nnz == 7 ** 3 == hpcg27.max_nnz(HPCG)
    counts = np.diff(A.indptr)
    # the centre point sees the whole cube, a corner 2 x 2 x 2 of it
    assert counts[13] == 27 and counts[0] == 8 and counts[26] == 8
    assert np.all(A.diagonal() == 1.0)
    off = (A - sp.diags(A.diagonal())).tocsr()
    off.eliminate_zeros()
    assert off.nnz == 7 ** 3 - 27
    assert set(np.unique(off.data)) == {np.float32(-1.0 / 26.0)}
    assert abs(A - A.T).max() == 0.0


def test_hpcg27_row_numbering_x_fastest():
    cfg = {**HPCG, "nx": 4, "ny": 2, "nz": 2}
    A = hpcg27.build(cfg, seed=0).toarray()
    # point (x, y, z) is row x + 4 y + 8 z: (0, 0, 0) reaches (1, 1, 1) = 13
    # but not (2, 0, 0) = 2
    assert A[0, 13] != 0 and A[0, 2] == 0 and A[0, 1] != 0


def test_hpcg27_same_for_every_seed():
    a = hpcg27.build(HPCG, seed=1)
    b = hpcg27.build(HPCG, seed=2**40 + 3)
    assert (a != b).nnz == 0


@pytest.mark.parametrize("seed", [5, 2**35 + 11])
def test_random_sparse_spd_shape_and_bound(seed):
    cfg = {"n": 256, "row_nnz": 4, "offdiag_mass": 0.9, "row_cap": 15}
    A = random_sparse_spd.build(cfg, seed)
    assert int(np.diff(A.indptr).max()) == 15
    assert A.nnz <= random_sparse_spd.max_nnz(cfg)
    assert np.allclose(A.diagonal(), 1.0)
    off = abs(A - sp.diags(A.diagonal())).sum(axis=1)
    assert float(off.max()) == pytest.approx(0.9, rel=1e-5)
    assert abs(A - A.T).max() == 0.0
