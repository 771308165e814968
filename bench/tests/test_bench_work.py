"""Peaks by device kind, and the work of one GS update by hand."""
import numpy as np
import pytest
import scipy.sparse as sp

from bench import trace_reduce, work


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        trace_reduce.load_peaks("TPU v99 imaginary")


def test_v5e_peaks_present():
    p = trace_reduce.load_peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def toy():
    # rows hold 1, 3 and 2 real nonzeros: a padded layout would store 3
    # slots per row, but padding must not count
    dense = np.array([[1.0, 0, 0], [0.5, 1.0, 0.25], [0, 0.5, 1.0]],
                     np.float32)
    return sp.csr_matrix(dense)


def test_gs_update_hand_count_float32():
    w = work.gs_update(toy(), k=2)
    nnz = (1 + 3 + 2) / 3
    assert w["flops"] == pytest.approx(2 * nnz * 2 + 2 * 2)
    assert w["bytes"] == pytest.approx(nnz * (4 + 4) + 4 * 2 + 4 * 2)


def test_gs_update_hand_count_bfloat16():
    w = work.gs_update(toy(), k=1, storage_dtype="bfloat16")
    assert w["bytes"] == pytest.approx(2 * (2 + 2) + 4 + 4)


def test_roofline_share_names_the_bound():
    w = {"flops": 10.0, "bytes": 100.0}
    peaks = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    share, bound = work.roofline_share(w, updates=10, kernel_s=2.0,
                                       peaks=peaks)
    assert bound == "bytes" and share == pytest.approx(1.0 / 2.0)
