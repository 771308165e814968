"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend kernels execute in interpret mode; on TPU they compile
natively.  ``interpret=None`` auto-detects.  No wrapper substitutes the
pure-jnp reference for its kernel: a shape that breaks a kernel's tiling
is an error, and a caller that wants the reference calls
``repro.kernels.ref`` itself.
"""
from __future__ import annotations

import jax

from repro.kernels.banded_gs import (banded_gs_sweep as _banded_gs_sweep,
                                     banded_rk_sweep as _banded_rk_sweep)
from repro.kernels.bbmv import bbmv as _bbmv, dense_to_bands
from repro.kernels.block_gs import block_gs_sweep as _block_gs_sweep
from repro.kernels.spmv_csr import (
    spmv_csr as _spmv_csr,
    spmv_csr_prefetch as _spmv_csr_prefetch,
    spmv_csr_sliced as _spmv_csr_sliced,
    spmv_csr_sliced_prefetch as _spmv_csr_sliced_prefetch,
)
from repro.kernels.spmv_ell import spmv_ell as _spmv_ell
from repro.kernels.sweep_csr import (
    sweep_rows_gs as _sweep_rows_gs,
    sweep_rows_rk as _sweep_rows_rk,
    sweep_rows_rk_delta as _sweep_rows_rk_delta,
)


def interpret_default() -> bool:
    """Whether ``interpret=None`` resolves to interpret-mode execution on
    the current backend — the single source the benchmark provenance
    stamp and the tuning table's ``interpret_mode`` field both read, so
    interpret-mode timings can never masquerade as hardware numbers."""
    return jax.default_backend() == "cpu"


def _interp(interpret):
    if interpret is None:
        return interpret_default()
    return interpret


def _require_tiles(n, tile, what):
    if n % tile != 0:
        raise ValueError(f"{what}: {n} rows do not tile by {tile}")


def block_gs_sweep(A, b, x, blocks, *, block=128, beta=1.0, interpret=None):
    _require_tiles(A.shape[0], block, "block_gs_sweep")
    return _block_gs_sweep(
        A, b, x, blocks, block=block, beta=beta, interpret=_interp(interpret)
    )


def bbmv(A_bands, x, *, bands, block, interpret=None):
    return _bbmv(A_bands, x, bands=bands, block=block, interpret=_interp(interpret))


def spmv_ell(vals, cols, x, *, tile=128, interpret=None):
    _require_tiles(vals.shape[0], tile, "spmv_ell")
    return _spmv_ell(vals, cols, x, tile=tile, interpret=_interp(interpret))


def spmv_csr(data, indices, row_id, x, *, m, rows_per_panel, panel_width,
             interpret=None):
    # No tiling-fallback branch: CsrOp.from_dense always allocates
    # num_panels * panel_width (+ row-window slack) slots, and the kernel
    # asserts that invariant itself.
    return _spmv_csr(data, indices, row_id, x, m=m,
                     rows_per_panel=rows_per_panel, panel_width=panel_width,
                     interpret=_interp(interpret))


def spmv_csr_prefetch(data, indices, row_id, panel_nnz, x, *, m,
                      rows_per_panel, panel_width, interpret=None):
    """Empty-panel-skipping spmv_csr (scalar-prefetched per-panel nnz)."""
    return _spmv_csr_prefetch(data, indices, row_id, panel_nnz, x, m=m,
                              rows_per_panel=rows_per_panel,
                              panel_width=panel_width,
                              interpret=_interp(interpret))


def spmv_csr_sliced(vals, cols, x, *, m, rows_per_panel, panels_per_tile=0,
                    interpret=None):
    """Gather-accumulate CSR matvec on the sliced-ELL view (the default
    ``CsrOp.matvec`` path; no empty-panel predication)."""
    return _spmv_csr_sliced(vals, cols, x, m=m, rows_per_panel=rows_per_panel,
                            panels_per_tile=panels_per_tile,
                            interpret=_interp(interpret))


def spmv_csr_sliced_prefetch(vals, cols, panel_nnz, x, *, m, rows_per_panel,
                             interpret=None):
    """Empty-panel-skipping ``spmv_csr_sliced`` (scalar-prefetched nnz)."""
    return _spmv_csr_sliced_prefetch(vals, cols, panel_nnz, x, m=m,
                                     rows_per_panel=rows_per_panel,
                                     interpret=_interp(interpret))


def banded_gs_sweep(A_bands, b, xw, picks, *, block, bands, beta=1.0,
                    interpret=None):
    """Fused banded block-GS sweep (halo-padded window stays VMEM-resident;
    picks scalar-prefetched)."""
    return _banded_gs_sweep(A_bands, b, xw, picks, block=block, bands=bands,
                            beta=beta, interpret=_interp(interpret))


def banded_rk_sweep(A_bands, b, rn, xw, dw, picks, gates, *, block, bands,
                    beta=1.0, interpret=None):
    """Fused masked banded Kaczmarz sweep over (window, delta) carries."""
    return _banded_rk_sweep(A_bands, b, rn, xw, dw, picks, gates, block=block,
                            bands=bands, beta=beta,
                            interpret=_interp(interpret))


def sweep_rows_gs(vals, cols, b, x, picks, *, beta=1.0, write_base=0,
                  interpret=None):
    """Fused coordinate-GS sweep over padded sparse rows (CSR/ELL).
    ``write_base`` offsets writes by a (possibly traced) slab offset —
    the distributed local phase's global row base."""
    return _sweep_rows_gs(vals, cols, b, x, picks, beta=beta,
                          write_base=write_base,
                          interpret=_interp(interpret))


def sweep_rows_rk(vals, cols, b, rn, x, picks, *, beta=1.0, interpret=None):
    """Fused Kaczmarz sweep over padded sparse rows (CSR/ELL)."""
    return _sweep_rows_rk(vals, cols, b, rn, x, picks, beta=beta,
                          interpret=_interp(interpret))


def sweep_rows_rk_delta(vals, cols, b, rn, x, d, picks, *, beta=1.0,
                        interpret=None):
    """Fused two-carry (replica, round-delta) Kaczmarz sweep over padded
    sparse rows — the distributed local phase of ``sparse_rk``."""
    return _sweep_rows_rk_delta(vals, cols, b, rn, x, d, picks, beta=beta,
                                interpret=_interp(interpret))


__all__ = [
    "banded_gs_sweep",
    "banded_rk_sweep",
    "bbmv",
    "block_gs_sweep",
    "dense_to_bands",
    "spmv_csr",
    "spmv_csr_prefetch",
    "spmv_csr_sliced",
    "spmv_csr_sliced_prefetch",
    "spmv_ell",
    "sweep_rows_gs",
    "sweep_rows_rk",
    "sweep_rows_rk_delta",
]
