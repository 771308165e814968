"""The work of one coordinate Gauss-Seidel update, from the matrix.

An update of row ``r`` reads the row's stored nonzeros (value and column
id at their stored widths) and the ``k`` entries of ``b[r]``, computes
``gamma = b[r] - <A_r, x>`` for ``k`` columns and writes ``x[r]``
(``k`` entries).  Only real nonzeros count: padding slots of any layout
are left out, so a layout change cannot inflate the count.  Reads of the
VMEM-resident iterate are not HBM traffic and are not counted.
"""
from __future__ import annotations

import numpy as np

VALUE_BYTES = {"float32": 4, "bfloat16": 2}
#: column ids narrow to int16 with low-precision values (n <= 32767)
INDEX_BYTES = {"float32": 4, "bfloat16": 2}


def gs_update(A, k: int, storage_dtype: str = "float32") -> dict:
    """Mean FLOPs and HBM bytes of one update of the sparse matrix ``A``
    (scipy CSR) with ``k`` right-hand sides, rows picked uniformly."""
    n = A.shape[1]
    nnz = float(np.mean(np.diff(A.indptr)))
    idx = INDEX_BYTES[storage_dtype] if n <= 32767 else 4
    flops = 2.0 * nnz * k + 2.0 * k        # row dot, then x[r] += gamma
    bytes_ = nnz * (VALUE_BYTES[storage_dtype] + idx) + 4.0 * k + 4.0 * k
    return {"flops": flops, "bytes": bytes_}


def roofline_share(work: dict, updates: float, kernel_s: float,
                   peaks: dict) -> tuple[float, str]:
    """``(share, bound)``: the least time the chip could take for the
    updates, over the measured kernel time, and which peak bounds it."""
    t_flops = work["flops"] * updates / peaks["flops_per_s"]
    t_bytes = work["bytes"] * updates / peaks["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    return max(t_flops, t_bytes) / kernel_s, bound
