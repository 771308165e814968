"""Unified matrix-format layer for the RGS/RK solver engine (DESIGN.md §3).

The paper's algorithms are one family — randomized row/coordinate actions
with bounded-staleness reads — and the matrix *format* is an orthogonal
axis: what changes between dense, block-banded, and ELL storage is only how
a row panel is read and which remote coordinates an update can touch.  This
module factors that axis out as operator classes sharing one protocol:

* ``matvec(x)``            — full ``A @ x`` (Pallas kernel on TPU, pure-jnp
                             reference on CPU / interpret mode);
* ``row_panel(bi)``        — the dense rows of aligned block ``bi``;
* ``residual_panel(...)``  — ``(b - A x)`` restricted to a block of rows;
* ``nnz_cost()``           — stored nonzeros (bytes/flops per matvec);
* ``halo_width``           — how far (in rows) an update's reads/writes can
                             reach outside an owned slab.  ``None`` means
                             unbounded (the sync strategy must replicate the
                             full iterate); a finite width lets the engine
                             choose neighbor halo exchange over all-gather;
* ``shard_spec(axis)``     — how the stored arrays shard over a worker axis.

Operators are registered pytrees, so they pass straight through ``jax.jit``
(arrays as leaves, static layout metadata as aux data).  The distributed
engine additionally uses the module-level ``banded_*`` panel routines, which
operate on a worker's *sharded* tile array inside ``shard_map`` — they are
kept as free functions (and their arithmetic is kept exactly as the
pre-refactor solvers wrote it) because the bit-identity contract of the
legacy entry points depends on the order of operations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax.tree_util import register_pytree_node_class

__all__ = [
    "BlockBandedOp",
    "CsrOp",
    "DenseOp",
    "EllOp",
    "as_operator",
    "banded_panel_residual",
    "banded_panel_residual_window",
    "banded_rows_matvec",
    "banded_window_matvec",
    "canonical_storage_dtype",
    "slab_neighbor_matrix",
]

# The storage-precision axis (DESIGN.md §7): coefficient panels may be held
# low-precision while row_norms_sq, sampling scales, and the iterate stay
# f32 — the kernels up-cast tiles on load and accumulate in f32.  ``None``
# keeps the input dtype untouched (the pre-existing behavior, bitwise).
STORAGE_DTYPES = ("float32", "bfloat16")


def canonical_storage_dtype(storage_dtype):
    """Validate/normalize a ``storage_dtype`` argument to a jnp dtype.

    ``None`` -> None (keep the input dtype, the bitwise-pinned default).
    """
    if storage_dtype is None:
        return None
    name = (storage_dtype if isinstance(storage_dtype, str)
            else jnp.dtype(storage_dtype).name)
    if name not in STORAGE_DTYPES:
        raise ValueError(
            f"unknown storage_dtype: {storage_dtype!r} "
            f"(choose from {STORAGE_DTYPES})")
    return jnp.dtype(name)


def _index_dtype(values_dtype, n: int):
    """Column-index dtype paired with a value dtype: low-precision values
    narrow the index stream to int16 when every column id fits, halving
    the index bytes alongside the value bytes (the sparse paths are
    bandwidth-bound, so the index stream is half the win)."""
    if jnp.dtype(values_dtype).itemsize < 4 and n <= np.iinfo(np.int16).max:
        return np.int16
    return np.int32


def slab_neighbor_matrix(rows, cols, real, m: int, n: int,
                         num_workers: int) -> np.ndarray:
    """Host-side neighbor graph of a row-slab partition.

    ``need[w, v]`` is True when worker ``w``'s rows (slab ``[w*m/P,
    (w+1)*m/P)``) read at least one coefficient owned by worker ``v``
    (column slab ``[v*n/P, (v+1)*n/P)``).  The diagonal is always True.
    This is what the engine's ``sync="a2a"`` strategy builds its masked
    ppermute schedule from — and what lets it fall back to all-gather when
    the graph is dense.
    """
    if m % num_workers or n % num_workers:
        raise ValueError(
            f"worker count ({num_workers}) must divide rows ({m}) and "
            f"columns ({n}) for a slab partition")
    rows = np.asarray(rows).reshape(-1)
    cols = np.asarray(cols).reshape(-1)
    real = np.asarray(real).reshape(-1)
    need = np.zeros((num_workers, num_workers), bool)
    w = rows[real] // (m // num_workers)
    v = cols[real] // (n // num_workers)
    need[w, v] = True
    np.fill_diagonal(need, True)
    return need


# ---------------------------------------------------------------------------
# Operator classes
# ---------------------------------------------------------------------------

@register_pytree_node_class
class DenseOp:
    """Dense row-major operator — square SPD or rectangular (m, n)."""

    def __init__(self, A: jax.Array):
        self.A = A

    def tree_flatten(self):
        return (self.A,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self) -> tuple[int, int]:
        return self.A.shape

    @property
    def halo_width(self):
        """Dense rows read every column: no finite halo."""
        return None

    def matvec(self, x: jax.Array) -> jax.Array:
        return self.A @ x

    def row(self, r) -> jax.Array:
        return self.A[r]

    def row_dot(self, r, x: jax.Array) -> jax.Array:
        """``A[r] @ x`` — the Θ(n) read a coordinate/row action performs."""
        return self.A[r] @ x

    def row_panel(self, bi, block: int) -> jax.Array:
        rows = bi * block + jnp.arange(block)
        return self.A[rows]

    def residual_panel(self, b, x, bi, block: int) -> jax.Array:
        rows = bi * block + jnp.arange(block)
        return b[rows] - self.A[rows] @ x

    def row_norms_sq(self) -> jax.Array:
        """Per-row ||A_i||² — always f32 (sampling/divisors stay exact)."""
        A = self.A.astype(jnp.float32)
        return jnp.einsum("mn,mn->m", A, A)

    def rk_update(self, x, r, g, beta):
        """Kaczmarz row action, exact legacy operation order (the row
        up-casts to f32; identity for f32 storage)."""
        return x + beta * self.A[r].astype(jnp.float32)[:, None] * g[None, :]

    def nnz_cost(self) -> int:
        m, n = self.A.shape
        return m * n

    def shard_spec(self, axis: str) -> P:
        return P(axis, None)

    def to_dense(self) -> jax.Array:
        return self.A


@register_pytree_node_class
class BlockBandedOp:
    """Block-banded operator: tiles ``A_bands[nb, 2*bands+1, block, block]``.

    The TPU-native sparse layout (kernels/bbmv.py): contiguous HBM->VMEM
    streams, MXU-shaped tiles, and a *finite halo* — a row panel only ever
    reads x within ``bands*block`` rows of itself, which is what lets the
    distributed engine swap the all-gather for a neighbor halo exchange.
    """

    def __init__(self, A_bands: jax.Array, *, bands: int):
        self.A_bands = A_bands
        self.bands = bands

    def tree_flatten(self):
        return (self.A_bands,), self.bands

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, bands=aux)

    @classmethod
    def from_dense(cls, A: jax.Array, *, block: int, bands: int,
                   storage_dtype=None) -> "BlockBandedOp":
        from repro.kernels.bbmv import dense_to_bands
        tiles = dense_to_bands(A, bands=bands, block=block)
        dt = canonical_storage_dtype(storage_dtype)
        if dt is not None:
            tiles = tiles.astype(dt)
        return cls(tiles, bands=bands)

    @property
    def nb(self) -> int:
        return self.A_bands.shape[0]

    @property
    def block(self) -> int:
        return self.A_bands.shape[2]

    @property
    def width(self) -> int:
        return self.A_bands.shape[1]

    @property
    def n(self) -> int:
        return self.nb * self.block

    @property
    def shape(self) -> tuple[int, int]:
        return (self.n, self.n)

    @property
    def halo_width(self) -> int:
        return self.bands * self.block

    def matvec(self, x: jax.Array, *, interpret=None) -> jax.Array:
        """``A @ x`` via the Pallas kernel (interpret-mode on CPU)."""
        from repro.kernels import ops
        return ops.bbmv(self.A_bands, x, bands=self.bands, block=self.block,
                        interpret=interpret)

    def matvec_ref(self, x: jax.Array) -> jax.Array:
        """Pure-jnp reference matvec (no Pallas)."""
        return banded_rows_matvec(self.A_bands, x, 0, self.nb, self.nb,
                                  self.block, self.bands)

    def packed_band_tiles(self) -> jax.Array:
        """Border tiles zero-padded for the fused sweep kernels (which
        bake validity into the data instead of masking) — the single
        packing definition the sequential ``gs_sweep`` and the engine's
        three fused distributed banded strategies all share."""
        from repro.kernels.banded_gs import pack_bands_local
        return pack_bands_local(self.A_bands, 0, self.nb, self.nb,
                                self.bands)

    def gs_sweep(self, b, x, picks, *, beta: float = 1.0,
                 interpret=None) -> jax.Array:
        """Fused sequential block-GS sweep: ``len(picks)`` block-row
        updates in one Pallas launch (kernels/banded_gs.py), the iterate
        VMEM-resident throughout.  Border validity is baked into the data
        (``packed_band_tiles``; ``dense_to_bands`` already stores border
        tiles as zeros), so the arithmetic — and the iterate — is bitwise
        the scan engine's."""
        from repro.kernels import ops
        halo = self.bands * self.block
        xw = jnp.pad(x, ((halo, halo), (0, 0)))
        xw = ops.banded_gs_sweep(self.packed_band_tiles(), b, xw, picks,
                                 block=self.block, bands=self.bands,
                                 beta=beta, interpret=interpret)
        return xw[halo:halo + self.n]

    def row_panel(self, bi) -> jax.Array:
        """Dense (block, n) rows of block-row ``bi`` (diagnostic use)."""
        tiles = self.A_bands[bi]                       # (width, block, block)
        out = jnp.zeros((self.block, self.n), tiles.dtype)
        for d in range(self.width):
            cb = bi + d - self.bands
            cbc = jnp.clip(cb, 0, self.nb - 1)
            valid = (cb >= 0) & (cb < self.nb)
            out = jax.lax.dynamic_update_slice(
                out, jnp.where(valid, tiles[d], 0.0), (0, cbc * self.block))
        return out

    def residual_panel(self, b, x, bi) -> jax.Array:
        """``(b - A x)`` on block-row ``bi`` — Θ(width) tile reads."""
        return banded_panel_residual(
            self.A_bands, b, x, bi, bi, self.nb, self.block, self.bands)

    def row_norms_sq(self) -> jax.Array:
        """Per-row ||A_i||^2 from the tiles, shaped (nb, block) — always
        computed (and returned) in f32 regardless of the tile storage
        dtype: the sampling distribution and RK divisors stay exact."""
        t = self.A_bands.astype(jnp.float32)
        return jnp.sum(t * t, axis=(1, 3))

    def nnz_cost(self) -> int:
        return self.nb * self.width * self.block * self.block

    def shard_spec(self, axis: str) -> P:
        return P(axis, None, None, None)

    def to_dense(self) -> jax.Array:
        out = jnp.zeros((self.n, self.n), self.A_bands.dtype)
        for bi in range(self.nb):
            for d in range(self.width):
                cb = bi + d - self.bands
                if 0 <= cb < self.nb:
                    out = out.at[bi * self.block:(bi + 1) * self.block,
                                 cb * self.block:(cb + 1) * self.block].set(
                        self.A_bands[bi, d])
        return out


@register_pytree_node_class
class EllOp:
    """Fixed-width ELLPACK operator: ``vals``/``cols`` of shape (n, width).

    The GPU-style gather format (kernels/spmv_ell.py) — kept as a first-class
    format so the engine's sequential row actions get a true Θ(nnz) read on
    unstructured sparsity, and as the contrast case in the kernel benchmarks.
    """

    def __init__(self, vals: jax.Array, cols: jax.Array):
        self.vals = vals
        self.cols = cols
        self._neighbors_cache: dict[int, "np.ndarray"] = {}

    def tree_flatten(self):
        return (self.vals, self.cols), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def from_dense(cls, A: jax.Array, *, width: int,
                   storage_dtype=None) -> "EllOp":
        from repro.core.spd import ell_from_dense
        vals, cols = ell_from_dense(A, width)
        dt = canonical_storage_dtype(storage_dtype)
        if dt is not None:
            vals = vals.astype(dt)
            cols = cols.astype(_index_dtype(dt, A.shape[1]))
        return cls(vals, cols)

    @property
    def shape(self) -> tuple[int, int]:
        n = self.vals.shape[0]
        return (n, n)

    @property
    def width(self) -> int:
        return self.vals.shape[1]

    @property
    def halo_width(self):
        """Gather columns are unstructured: no finite halo."""
        return None

    def matvec(self, x: jax.Array, *, interpret=None) -> jax.Array:
        """``A @ x`` via the ELL kernel; the row count pads to whole
        128-row tiles with zero-valued rows, whose outputs are dropped."""
        from repro.kernels import ops
        n = self.vals.shape[0]
        pad = -n % 128
        vals = jnp.pad(self.vals, ((0, pad), (0, 0)))
        cols = jnp.pad(self.cols, ((0, pad), (0, 0)))
        return ops.spmv_ell(vals, cols, x, tile=128,
                            interpret=interpret)[:n]

    def matvec_ref(self, x: jax.Array) -> jax.Array:
        from repro.kernels import ref
        return ref.spmv_ell_ref(self.vals, self.cols, x)

    def row_dot(self, r, x: jax.Array) -> jax.Array:
        """``A[r] @ x`` in Θ(width): gather the row's columns only (the
        value window up-casts to f32; identity for f32 storage)."""
        return jnp.einsum("w,wk->k", self.vals[r].astype(jnp.float32),
                          x[self.cols[r]])

    def row_norms_sq(self) -> jax.Array:
        """Per-row ||A_i||² — always f32 (sampling/divisors stay exact)."""
        v = self.vals.astype(jnp.float32)
        return jnp.einsum("nw,nw->n", v, v)

    def rk_update(self, x, r, g, beta):
        """Kaczmarz row action as a Θ(width) scatter-add (padding cols carry
        zero values, so duplicate indices contribute nothing).  The value
        window up-casts to f32 so low-precision storage still applies an
        f32-accumulated update (identity for f32 storage)."""
        vw = self.vals[r].astype(jnp.float32)
        return x.at[self.cols[r]].add(beta * vw[:, None] * g[None, :])

    def nnz_cost(self) -> int:
        n, w = self.vals.shape
        return n * w

    def padded_rows(self) -> tuple[jax.Array, jax.Array]:
        """ELL already is the per-row padded-window form (CsrOp protocol)."""
        return self.vals, self.cols

    def gs_sweep(self, b, x, picks, *, beta: float = 1.0, write_base=0,
                 interpret=None) -> jax.Array:
        """Fused sequential coordinate-GS sweep (kernels/sweep_csr.py —
        ELL storage already is the padded-row form it streams).
        ``write_base`` offsets writes for distributed slab-local phases."""
        from repro.kernels import ops
        return ops.sweep_rows_gs(self.vals, self.cols, b, x, picks,
                                 beta=beta, write_base=write_base,
                                 interpret=interpret)

    def rk_sweep(self, b, rn, x, picks, *, beta: float = 1.0,
                 interpret=None) -> jax.Array:
        """Fused sequential Kaczmarz sweep (kernels/sweep_csr.py).  ``rn``
        is the caller's row-norm vector so the divisor matches the scan
        engine's sampling distribution exactly."""
        from repro.kernels import ops
        return ops.sweep_rows_rk(self.vals, self.cols, b, rn, x, picks,
                                 beta=beta, interpret=interpret)

    def slab_neighbors(self, num_workers: int) -> np.ndarray:
        """Row-slab neighbor graph (host-side; see slab_neighbor_matrix).
        Memoized per worker count, like CsrOp."""
        if num_workers not in self._neighbors_cache:
            n, w = self.vals.shape
            rows = np.broadcast_to(np.arange(n)[:, None], (n, w))
            self._neighbors_cache[num_workers] = slab_neighbor_matrix(
                rows, self.cols, np.asarray(self.vals) != 0, n, n,
                num_workers)
        return self._neighbors_cache[num_workers]

    def shard_spec(self, axis: str) -> P:
        return P(axis, None)

    def to_dense(self) -> jax.Array:
        n = self.vals.shape[0]
        out = jnp.zeros((n, n), self.vals.dtype)
        return out.at[jnp.arange(n)[:, None], self.cols].add(self.vals)


@register_pytree_node_class
class CsrOp:
    """General compressed-sparse-row operator, panel-aligned for the TPU.

    The format of the paper's reference scenario: unstructured sparsity,
    arbitrary (possibly rectangular) shape, exact nonzero storage.  Layout
    (kernels/spmv_csr.py): nonzeros stay in row-major CSR order, but each
    *panel* of ``rows_per_panel`` consecutive rows is padded to a common
    nnz budget ``panel_width`` (a lane multiple), so the flat arrays
    reshape to ``(num_panels, panel_width)`` and stream contiguously.

    * ``data``/``indices``/``row_id`` — value, column, and row of every
      slot (padding slots carry value 0, so they never contribute);
    * ``row_start``/``row_nnz`` — the CSR row pointers against the padded
      layout: row ``r`` occupies slots ``[row_start[r], row_start[r] +
      row_nnz[r])``, always contiguous and never straddling a panel.
      The flat arrays keep ``row_cap`` slack slots past the last panel so a
      fixed-size ``row_cap`` window read never runs off the end.

    In place of the scalar ``halo_width`` (meaningless for unstructured
    sparsity — a single far-off coupling would inflate it to n), the format
    answers *per-row reach* queries: ``row_reach()`` per row, and
    ``slab_neighbors(P)`` — the row-slab neighbor graph the distributed
    engine's ``sync="a2a"`` strategy exchanges along.
    """

    def __init__(self, data, indices, row_id, row_start, row_nnz, *,
                 shape, nnz, row_cap, rows_per_panel, panel_width):
        self.data = data
        self.indices = indices
        self.row_id = row_id
        self.row_start = row_start
        self.row_nnz = row_nnz
        self._shape = tuple(shape)
        self.nnz = nnz
        self.row_cap = row_cap
        self.rows_per_panel = rows_per_panel
        self.panel_width = panel_width
        self._neighbors_cache: dict[int, np.ndarray] = {}
        self._panel_nnz_cache: jax.Array | None = None
        self._sliced_cache: tuple[jax.Array, jax.Array] | None = None

    def tree_flatten(self):
        leaves = (self.data, self.indices, self.row_id, self.row_start,
                  self.row_nnz)
        aux = (self._shape, self.nnz, self.row_cap, self.rows_per_panel,
               self.panel_width)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, nnz, row_cap, rows_per_panel, panel_width = aux
        return cls(*children, shape=shape, nnz=nnz, row_cap=row_cap,
                   rows_per_panel=rows_per_panel, panel_width=panel_width)

    @classmethod
    def from_dense(cls, A: jax.Array, *, rows_per_panel: int = 8,
                   lane: int = 128, storage_dtype=None) -> "CsrOp":
        """Exact CSR capture of every nonzero of dense ``A`` (host-side).

        ``storage_dtype`` rounds the captured *values* to a low-precision
        storage dtype (the pattern is taken from the input dtype first, so
        the stored sparsity is dtype-independent); column indices narrow
        to int16 alongside when every id fits (``_index_dtype``)."""
        An = np.asarray(A)
        m, n = An.shape
        nz = An != 0.0
        counts = nz.sum(axis=1).astype(np.int64)
        cap = max(int(counts.max()) if m else 1, 1)
        row_vals = np.zeros((max(m, 1), cap), An.dtype)
        row_cols = np.zeros((max(m, 1), cap), np.int32)
        for r in range(m):
            cj = np.nonzero(nz[r])[0]
            row_vals[r, :cj.size] = An[r, cj]
            row_cols[r, :cj.size] = cj
        dt = canonical_storage_dtype(storage_dtype)
        if dt is not None:
            row_vals = row_vals.astype(dt)
        return cls._assemble(row_vals, row_cols, counts, shape=(m, n),
                             rows_per_panel=rows_per_panel, lane=lane)

    @classmethod
    def _assemble(cls, row_vals, row_cols, counts, *, shape,
                  rows_per_panel: int = 8, lane: int = 128) -> "CsrOp":
        """Pack per-row nonzero windows into the panel-aligned flat layout.

        ``row_vals``/``row_cols`` are host arrays of shape (m, >= max nnz/row)
        whose first ``counts[r]`` slots hold row ``r``'s values and *global*
        column ids (slots past the count are ignored); this is the shared
        assembly path of ``from_dense`` and the row-permutation constructor
        in ``core.partition`` (a permuted operator re-panelizes here so the
        panel machinery never sees non-contiguity).
        """
        m, n = shape
        counts = np.asarray(counts, np.int64).reshape(-1)
        nnz = int(counts.sum())
        row_cap = max(int(counts.max()) if m else 1, 1)
        R = rows_per_panel
        num_panels = -(-m // R)
        padded_counts = np.zeros((num_panels * R,), np.int64)
        padded_counts[:m] = counts
        panel_nnz = padded_counts.reshape(num_panels, R).sum(axis=1)
        W = int(-(-max(int(panel_nnz.max()) if num_panels else 1, 1) // lane)
                * lane)
        total = num_panels * W + row_cap        # row-window slack at the end
        vals_np = np.asarray(row_vals)
        data = np.zeros((total,), vals_np.dtype)
        # Low-precision values narrow the column stream too (re-derived
        # here so re-assembly — e.g. partition.permute_rows — preserves
        # the compact layout); row_id/row_start stay int32: row_start
        # addresses the flat layout, whose extent is not bounded by n.
        cols = np.zeros((total,), _index_dtype(vals_np.dtype, n))
        rows = np.zeros((total,), np.int32)
        row_start = np.zeros((max(m, 1),), np.int32)
        for p in range(num_panels):
            cursor = p * W
            for r in range(p * R, min((p + 1) * R, m)):
                c = int(counts[r])
                row_start[r] = cursor
                data[cursor:cursor + c] = row_vals[r, :c]
                cols[cursor:cursor + c] = row_cols[r, :c]
                rows[cursor:cursor + c] = r
                cursor += c
        return cls(jnp.asarray(data), jnp.asarray(cols),
                   jnp.asarray(rows), jnp.asarray(row_start),
                   jnp.asarray(counts.astype(np.int32)),
                   shape=(m, n), nnz=nnz, row_cap=row_cap,
                   rows_per_panel=R, panel_width=W)

    @property
    def shape(self) -> tuple[int, int]:
        return self._shape

    @property
    def halo_width(self):
        """Unstructured reach: no *scalar* halo (see ``row_reach``)."""
        return None

    def matvec(self, x: jax.Array, *, interpret=None,
               skip_empty: bool | None = None,
               variant: str | None = None) -> jax.Array:
        """``A @ x`` — the tunable CSR matvec dispatch seam.

        Four pinned kernel variants serve this entry point: the sliced-ELL
        gather-accumulate kernel (``"sliced"``, the PR-5 overhaul that
        retired the one-hot-matmul segment sum from the matvec path), its
        empty-panel-predicated twin (``"sliced_prefetch"`` —
        scalar-prefetched per-panel nnz counts; empty panels — common
        after norm-balanced partitioning of banded-structure matrices —
        write zeros without gathering ``x``), and the legacy segment-sum
        pair (``"segsum"`` / ``"segsum_prefetch"``, the measured contrast
        case).  Selection order (repro.tune):

        1. an explicit ``variant`` forces that kernel (bitwise-pinned);
        2. an explicit ``skip_empty`` bool forces the pre-autotune pick:
           the sliced kernel, predicated iff True (bitwise-pinned);
        3. the active tuning table's ``matvec`` entry for this operator's
           shape bucket and storage dtype, when one exists;
        4. the pre-autotune auto-selection, bitwise-unchanged: the
           predicated sliced kernel when the stored pattern actually has
           empty panels, the plain sliced kernel otherwise.

        The predication stream (``panel_nnz``) needs concrete metadata, so
        under jit tracing steps 3–4 drop to the variant's non-prefetch
        sibling (exactly the pre-autotune tracer behavior)."""
        from repro.kernels import ops
        if variant is None:
            if skip_empty is not None:
                variant = "sliced_prefetch" if skip_empty else "sliced"
            else:
                from repro.tune import runtime as tune_runtime
                variant = tune_runtime.matvec_variant(self)
                if variant is None:
                    if isinstance(self.row_nnz, jax.core.Tracer):
                        variant = "sliced"
                    else:
                        empty = bool(
                            (np.asarray(self.panel_nnz()) == 0).any())
                        variant = "sliced_prefetch" if empty else "sliced"
                elif variant.endswith("_prefetch") \
                        and isinstance(self.row_nnz, jax.core.Tracer):
                    variant = variant[:-len("_prefetch")]
        if variant in ("segsum", "segsum_prefetch"):
            if variant == "segsum_prefetch":
                return ops.spmv_csr_prefetch(
                    self.data, self.indices, self.row_id, self.panel_nnz(),
                    x, m=self._shape[0], rows_per_panel=self.rows_per_panel,
                    panel_width=self.panel_width, interpret=interpret)
            return ops.spmv_csr(self.data, self.indices, self.row_id, x,
                                m=self._shape[0],
                                rows_per_panel=self.rows_per_panel,
                                panel_width=self.panel_width,
                                interpret=interpret)
        if variant not in ("sliced", "sliced_prefetch"):
            from repro.tune.table import MATVEC_VARIANTS
            raise ValueError(f"unknown matvec variant: {variant!r} "
                             f"(expected one of {MATVEC_VARIANTS})")
        vals, cols = self.sliced_rows()
        if variant == "sliced_prefetch":
            return ops.spmv_csr_sliced_prefetch(
                vals, cols, self.panel_nnz(), x, m=self._shape[0],
                rows_per_panel=self.rows_per_panel, interpret=interpret)
        return ops.spmv_csr_sliced(vals, cols, x, m=self._shape[0],
                                   rows_per_panel=self.rows_per_panel,
                                   interpret=interpret)

    def matvec_segsum(self, x: jax.Array, *, interpret=None,
                      skip_empty: bool = False) -> jax.Array:
        """The legacy segment-sum-as-one-hot-matmul matvec kernels, kept
        as the measured contrast case (benchmarks/bench_kernels.py) and as
        an independent second kernel implementation in the conformance
        tests."""
        from repro.kernels import ops
        if skip_empty:
            return ops.spmv_csr_prefetch(
                self.data, self.indices, self.row_id, self.panel_nnz(), x,
                m=self._shape[0], rows_per_panel=self.rows_per_panel,
                panel_width=self.panel_width, interpret=interpret)
        return ops.spmv_csr(self.data, self.indices, self.row_id, x,
                            m=self._shape[0],
                            rows_per_panel=self.rows_per_panel,
                            panel_width=self.panel_width, interpret=interpret)

    def sliced_rows(self) -> tuple[jax.Array, jax.Array]:
        """Sliced-ELL view of the stored nonzeros: the ``padded_rows()``
        windows padded to a lane-friendly width and to whole panels
        (``num_panels * rows_per_panel`` rows), panel-major — what the
        gather-accumulate matvec kernels stream.  Memoized host-side when
        the leaves are concrete (the view is static metadata of the stored
        pattern); recomputed in-graph under jit."""
        if self._sliced_cache is not None:
            return self._sliced_cache
        R = self.rows_per_panel
        m = self._shape[0]
        mp = -(-max(m, 1) // R) * R
        width = -(-self.row_cap // 8) * 8
        vals, cols = self.padded_rows()
        if width > self.row_cap or mp > m:
            vals = jnp.pad(vals, ((0, mp - m), (0, width - self.row_cap)))
            cols = jnp.pad(cols, ((0, mp - m), (0, width - self.row_cap)))
        if not isinstance(self.data, jax.core.Tracer):
            self._sliced_cache = (vals, cols)
        return vals, cols

    def panel_nnz(self) -> jax.Array:
        """Per-panel stored-nonzero counts, shape (num_panels,) — the
        predicate stream the empty-panel-skipping matvec prefetches.
        Memoized: it is static metadata of the stored pattern, and the
        skip variant consults it on every matvec."""
        if self._panel_nnz_cache is None:
            R = self.rows_per_panel
            m = self._shape[0]
            num_panels = -(-m // R)
            # Host-side, like slab_neighbors: never caches a tracer (an
            # attempt to trace through raises a concretization error).
            padded = np.zeros((num_panels * R,), np.int64)
            padded[:m] = np.asarray(self.row_nnz)
            self._panel_nnz_cache = jnp.asarray(
                padded.reshape(num_panels, R).sum(axis=1).astype(np.int32))
        return self._panel_nnz_cache

    def matvec_ref(self, x: jax.Array) -> jax.Array:
        from repro.kernels import ref
        return ref.spmv_csr_ref(self.data, self.indices, self.row_id, x,
                                m=self._shape[0])

    def _row_window(self, r):
        """Row ``r``'s values/columns as a fixed Θ(row_cap) masked window."""
        vw = jax.lax.dynamic_slice_in_dim(self.data, self.row_start[r],
                                          self.row_cap, 0)
        cw = jax.lax.dynamic_slice_in_dim(self.indices, self.row_start[r],
                                          self.row_cap, 0)
        mask = jnp.arange(self.row_cap) < self.row_nnz[r]
        return jnp.where(mask, vw, 0.0), jnp.where(mask, cw, 0)

    def row_dot(self, r, x: jax.Array) -> jax.Array:
        """``A[r] @ x`` in Θ(row_cap): gather the row's columns only (the
        value window up-casts to f32; identity for f32 storage)."""
        vw, cw = self._row_window(r)
        return jnp.einsum("w,wk->k", vw.astype(jnp.float32), x[cw])

    def row_panel(self, bi, block: int) -> jax.Array:
        """Dense (block, n) rows of aligned block ``bi`` (block-GS reads)."""
        rows = bi * block + jnp.arange(block)
        vw, cw = jax.vmap(self._row_window)(rows)
        out = jnp.zeros((block, self._shape[1]), self.data.dtype)
        return out.at[jnp.arange(block)[:, None], cw].add(vw)

    def residual_panel(self, b, x, bi, block: int) -> jax.Array:
        """``(b - A x)`` on aligned row block ``bi`` — Θ(block·row_cap)."""
        rows = bi * block + jnp.arange(block)
        dots = jax.vmap(lambda r: self.row_dot(r, x))(rows)
        return b[rows] - dots

    def row_norms_sq(self) -> jax.Array:
        """Per-row ||A_i||² — always f32 (sampling/divisors stay exact)."""
        d = self.data.astype(jnp.float32)
        return jax.ops.segment_sum(d * d, self.row_id,
                                   num_segments=self._shape[0])

    def rk_update(self, x, r, g, beta):
        """Kaczmarz row action as a Θ(row_cap) scatter-add (masked padding
        slots carry zero values, so duplicate indices contribute nothing).
        The value window up-casts to f32 (identity for f32 storage)."""
        vw, cw = self._row_window(r)
        vw = vw.astype(jnp.float32)
        return x.at[cw].add(beta * vw[:, None] * g[None, :])

    def padded_rows(self) -> tuple[jax.Array, jax.Array]:
        """(m, row_cap) per-row value/column windows with global column ids
        — the slab-shardable form the distributed engine partitions."""
        with jax.named_scope("repro.row_windows"):
            idx = self.row_start[:, None] + jnp.arange(self.row_cap)[None, :]
            idx = jnp.minimum(idx, self.data.shape[0] - 1)
            mask = jnp.arange(self.row_cap)[None, :] < self.row_nnz[:, None]
            vals = jnp.where(mask, self.data[idx], 0.0)
            cols = jnp.where(mask, self.indices[idx], 0)
        return vals, cols

    def gs_sweep(self, b, x, picks, *, beta: float = 1.0, write_base=0,
                 interpret=None) -> jax.Array:
        """Fused sequential coordinate-GS sweep (kernels/sweep_csr.py):
        the row windows stream via scalar-prefetch index maps over the
        ``padded_rows()`` form — the same masked windows ``row_dot``
        reads, summed in slot order, so the iterate matches the scan
        engine's to f32 roundoff.
        ``write_base`` offsets writes for distributed slab-local phases."""
        from repro.kernels import ops
        vals, cols = self.padded_rows()
        return ops.sweep_rows_gs(vals, cols, b, x, picks, beta=beta,
                                 write_base=write_base,
                                 interpret=interpret)

    def rk_sweep(self, b, rn, x, picks, *, beta: float = 1.0,
                 interpret=None) -> jax.Array:
        """Fused sequential Kaczmarz sweep (kernels/sweep_csr.py).  ``rn``
        is the caller's row-norm vector so the divisor matches the scan
        engine's sampling distribution exactly."""
        from repro.kernels import ops
        vals, cols = self.padded_rows()
        return ops.sweep_rows_rk(vals, cols, b, rn, x, picks, beta=beta,
                                 interpret=interpret)

    def row_reach(self) -> jax.Array:
        """Per-row reach ``max_j |col_ij - i|`` — the per-row refinement of
        the scalar ``halo_width`` (square systems; 0 for empty rows)."""
        d = jnp.abs(self.indices - self.row_id)
        d = jnp.where(self.data != 0, d, 0)
        return jnp.maximum(
            jax.ops.segment_max(d, self.row_id,
                                num_segments=self._shape[0]), 0)

    def slab_neighbors(self, num_workers: int) -> np.ndarray:
        """Row-slab neighbor graph (host-side; see slab_neighbor_matrix).
        Memoized per worker count — the graph is a property of the stored
        sparsity pattern, and solve_distributed consults it every call."""
        if num_workers not in self._neighbors_cache:
            m, n = self._shape
            self._neighbors_cache[num_workers] = slab_neighbor_matrix(
                self.row_id, self.indices, np.asarray(self.data) != 0,
                m, n, num_workers)
        return self._neighbors_cache[num_workers]

    def nnz_cost(self) -> int:
        return self.nnz

    def shard_spec(self, axis: str) -> P:
        """Spec of the ``padded_rows()`` slab form the engine shards (the
        flat panel layout itself does not split evenly on a row axis)."""
        return P(axis, None)

    def to_dense(self) -> jax.Array:
        out = jnp.zeros(self._shape, self.data.dtype)
        return out.at[self.row_id, self.indices].add(self.data)


def as_operator(A: jax.Array, format: str = "dense", *, block: int = 128,
                bands: int = 2, width: int = 32, rows_per_panel: int = 8,
                storage_dtype=None):
    """Build an operator of the requested ``format`` from a dense matrix.

    ``storage_dtype`` ("float32"/"bfloat16"/None) selects the coefficient
    storage precision for every format; ``None`` keeps the input dtype
    (the bitwise-pinned default).  The iterate, ``row_norms_sq``, and all
    kernel accumulators stay f32 regardless.
    """
    dt = canonical_storage_dtype(storage_dtype)
    if format == "dense":
        return DenseOp(A if dt is None else jnp.asarray(A).astype(dt))
    if format == "banded":
        return BlockBandedOp.from_dense(A, block=block, bands=bands,
                                        storage_dtype=storage_dtype)
    if format == "ell":
        return EllOp.from_dense(A, width=width, storage_dtype=storage_dtype)
    if format == "csr":
        return CsrOp.from_dense(A, rows_per_panel=rows_per_panel,
                                storage_dtype=storage_dtype)
    raise ValueError(f"unknown operator format: {format!r}")


# ---------------------------------------------------------------------------
# Shard-local banded panel routines (used inside shard_map by the engine)
# ---------------------------------------------------------------------------
# The arithmetic below is transplanted *verbatim* from the pre-refactor
# parallel_rgs solvers: the legacy entry points' bit-identity contract
# depends on the exact operation order, so do not "simplify" these.

def banded_panel_residual(Ab_sh, b_sh, xw, bi_local, gb, nb, block, bands):
    """``(b - A x)`` on a worker's local block-row, reading the *global*
    (n, k) iterate ``xw``.  ``gb`` is the global block-row index of
    ``bi_local`` (``gb = w * nb_local + bi_local`` under sharding)."""
    width = 2 * bands + 1
    acc = jax.lax.dynamic_slice_in_dim(
        b_sh, bi_local * block, block, 0).astype(jnp.float32)
    tiles = jax.lax.dynamic_slice_in_dim(Ab_sh, bi_local, 1, 0)[0]
    for d in range(width):
        cb = gb + d - bands                  # global column block
        cbc = jnp.clip(cb, 0, nb - 1)
        xs = jax.lax.dynamic_slice_in_dim(xw, cbc * block, block, 0)
        contrib = jnp.dot(tiles[d], xs, preferred_element_type=jnp.float32)
        valid = (cb >= 0) & (cb < nb)
        acc = acc - jnp.where(valid, contrib, 0.0)
    return acc.astype(xw.dtype)


def banded_panel_residual_window(Ab_sh, b_sh, xw, bi, gb, nb, slab, block,
                                 bands):
    """``(b - A x)`` on local block-row ``bi``, reading a halo-padded
    *window* ``xw`` of shape (slab + 2*bands*block, k)."""
    width = 2 * bands + 1
    halo = bands * block
    acc = jax.lax.dynamic_slice_in_dim(
        b_sh, bi * block, block, 0).astype(jnp.float32)
    tiles = jax.lax.dynamic_slice_in_dim(Ab_sh, bi, 1, 0)[0]
    for d in range(width):
        cb = gb + d - bands
        xs = jax.lax.dynamic_slice_in_dim(
            xw, jnp.clip((bi + d) * block, 0, slab + 2 * halo - block),
            block, 0)
        contrib = jnp.dot(tiles[d], xs, preferred_element_type=jnp.float32)
        acc = acc - jnp.where((cb >= 0) & (cb < nb), contrib, 0.0)
    return acc.astype(xw.dtype)


def banded_rows_matvec(Ab_sh, x, w, nb, nb_local, block, bands):
    """``(A x)`` for the ``nb_local`` block-rows owned by worker ``w``,
    reading the global (n, k) vector ``x``."""
    width = 2 * bands + 1

    def one(bi):
        gb = w * nb_local + bi
        acc = jnp.zeros((block, x.shape[1]), jnp.float32)
        for d in range(width):
            cb = gb + d - bands
            cbc = jnp.clip(cb, 0, nb - 1)
            xs = jax.lax.dynamic_slice_in_dim(x, cbc * block, block, 0)
            contrib = jnp.dot(Ab_sh[bi, d], xs, preferred_element_type=jnp.float32)
            acc = acc + jnp.where((cb >= 0) & (cb < nb), contrib, 0.0)
        return acc.astype(x.dtype)

    out = jax.vmap(one)(jnp.arange(nb_local))          # (nb_local, block, k)
    return out.reshape(nb_local * block, x.shape[1])


def banded_window_matvec(Ab_sh, vw, w, nb, nb_local, block, bands):
    """``(A v)`` for the worker's own block-rows, reading a halo-padded
    window ``vw`` of shape (nb_local*block + 2*bands*block, k)."""
    width = 2 * bands + 1
    slab = nb_local * block
    halo = bands * block

    def one(bi):
        gb = w * nb_local + bi
        acc = jnp.zeros((block, vw.shape[1]), jnp.float32)
        for d in range(width):
            cb = gb + d - bands
            xs = jax.lax.dynamic_slice_in_dim(
                vw, jnp.clip((bi + d) * block, 0, slab + 2 * halo - block),
                block, 0)
            contrib = jnp.dot(Ab_sh[bi, d], xs, preferred_element_type=jnp.float32)
            acc = acc + jnp.where((cb >= 0) & (cb < nb), contrib, 0.0)
        return acc.astype(vw.dtype)

    out = jax.vmap(one)(jnp.arange(nb_local))
    return out.reshape(slab, vw.shape[1])
