"""Operator layer (repro.core.operators): one property-based conformance
grid over all four formats (ISSUE 4 satellite).

``check_conformance`` asserts the full operator protocol against the dense
oracle — matvec (Pallas kernel in interpret mode AND pure-jnp reference),
``row_norms_sq`` non-negative and consistent with ``row_panel`` reads,
``row_dot``/``rk_update`` row actions, ``padded_rows`` round-tripping the
matrix, ``slab_neighbors`` exactly the slab graph of the dense sparsity
pattern (symmetric whenever the pattern is, always True on the diagonal,
shape (P, P)), and ``to_dense`` reconstruction.  A deterministic
format x shape x sparsity grid always runs (tier-1 works on bare
jax+pytest); when hypothesis is installed the same checker fuzzes over
random shapes/sparsity/seeds.  The engine-facing tests (dispatch, pytree
flattening, sequential format-genericity) ride below unchanged.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BlockBandedOp, CsrOp, DenseOp, EllOp, as_operator,
                        block_banded_spd, random_sparse_lsq,
                        random_sparse_spd)
from repro.core.engine import solve_sequential

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # bare jax+pytest environment: deterministic grid only
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# The conformance checker
# ---------------------------------------------------------------------------

def _dense_slab_graph(An, num_workers):
    """Oracle for slab_neighbors: need[w, v] <=> row slab w stores a
    nonzero in column slab v (diagonal always True)."""
    m, n = An.shape
    rs, cs = m // num_workers, n // num_workers
    need = np.zeros((num_workers, num_workers), bool)
    for w in range(num_workers):
        for v in range(num_workers):
            need[w, v] = bool(
                (An[w * rs:(w + 1) * rs, v * cs:(v + 1) * cs] != 0).any())
    np.fill_diagonal(need, True)
    return need


def check_conformance(op, A, *, rtol=1e-4, atol=1e-4):
    """Assert the full operator protocol against the dense oracle ``A``.

    For a low-precision ``storage_dtype`` operator the caller passes the
    ROUNDED dense oracle (``f32(bf16(A))``): the operator's coefficients
    are exactly the rounded values, so every value comparison stays tight
    — only accumulation order separates the two sides — while
    ``row_norms_sq`` must come back f32 regardless of storage.
    """
    An = np.asarray(A)
    m, n = An.shape
    assert op.shape == (m, n)
    key = jax.random.key(hash((m, n)) % (2 ** 31))
    x = jax.random.normal(key, (n, 3), A.dtype)

    # matvec: Pallas kernel (interpret mode on CPU) and pure-jnp reference
    want = An @ np.asarray(x)
    kwargs = {"interpret": True} if not isinstance(op, DenseOp) else {}
    np.testing.assert_allclose(np.asarray(op.matvec(x, **kwargs)), want,
                               rtol=rtol, atol=atol)
    if hasattr(op, "matvec_ref"):
        np.testing.assert_allclose(np.asarray(op.matvec_ref(x)), want,
                                   rtol=rtol, atol=atol)

    # row_norms_sq: f32 whatever the storage dtype (sampling distributions
    # and RK divisors never degrade), non-negative, matches the dense rows
    rn_arr = op.row_norms_sq()
    assert rn_arr.dtype == jnp.float32
    rn = np.asarray(rn_arr).reshape(-1)
    assert rn.shape == (m,) and (rn >= 0).all()
    np.testing.assert_allclose(rn, (An * An).sum(axis=1), rtol=1e-4,
                               atol=1e-5)

    # ...and consistent with row_panel reads where the format has them
    # (panels come back in storage dtype; square in f32 like the operator)
    if isinstance(op, BlockBandedOp):
        panel = np.asarray(op.row_panel(0)).astype(np.float32)
        np.testing.assert_allclose((panel * panel).sum(axis=1),
                                   rn[:op.block], rtol=1e-4, atol=1e-5)
    elif hasattr(op, "row_panel"):
        block = max(m // 8, 1)
        if m % block == 0:
            panel = np.asarray(op.row_panel(1, block)).astype(np.float32)
            np.testing.assert_allclose((panel * panel).sum(axis=1),
                                       rn[block:2 * block], rtol=1e-4,
                                       atol=1e-5)

    # row actions (Θ(nnz/row) reads the sequential engine performs)
    b = jnp.asarray(An) @ x + 0.5
    if hasattr(op, "row_dot"):
        dop = DenseOp(jnp.asarray(An))
        for r in (0, m // 2, m - 1):
            np.testing.assert_allclose(np.asarray(op.row_dot(r, x)),
                                       np.asarray(dop.row_dot(r, x)),
                                       rtol=1e-4, atol=1e-5)
        g = jnp.ones((x.shape[1],))
        np.testing.assert_allclose(
            np.asarray(op.rk_update(x, m // 2, g, 0.9)),
            np.asarray(dop.rk_update(x, m // 2, g, 0.9)), atol=1e-5)

    # residual_panel: the block-GS read, vs the dense expression
    if isinstance(op, BlockBandedOp):
        bi = op.nb - 1
        rows = slice(bi * op.block, (bi + 1) * op.block)
        np.testing.assert_allclose(
            np.asarray(op.residual_panel(b, x, bi)),
            np.asarray(b[rows]) - An[rows] @ np.asarray(x),
            rtol=1e-4, atol=1e-4)
    elif hasattr(op, "residual_panel"):
        block = max(m // 8, 1)
        if m % block == 0:
            rows = slice(block, 2 * block)
            np.testing.assert_allclose(
                np.asarray(op.residual_panel(b, x, 1, block)),
                np.asarray(b[rows]) - An[rows] @ np.asarray(x),
                rtol=1e-4, atol=1e-4)

    # padded_rows round-trips the matrix (global column ids, zero padding)
    if hasattr(op, "padded_rows"):
        vals, cols = op.padded_rows()
        assert vals.shape == cols.shape and vals.shape[0] == m
        recon = jnp.zeros((m, n), vals.dtype).at[
            jnp.arange(m)[:, None], cols].add(vals)
        np.testing.assert_allclose(
            np.asarray(recon).astype(np.float32), An, atol=1e-6)

    # slab_neighbors IS the slab graph of the dense pattern — this
    # subsumes in-bounds shape/dtype and symmetry-when-the-pattern-is
    if hasattr(op, "slab_neighbors"):
        for P in (2, 4):
            if m % P or n % P:
                continue
            need = op.slab_neighbors(P)
            assert need.shape == (P, P) and need.dtype == bool
            assert need.diagonal().all()
            np.testing.assert_array_equal(need, _dense_slab_graph(An, P))
            if m == n and np.array_equal((An != 0), (An != 0).T):
                np.testing.assert_array_equal(need, need.T)

    # nnz_cost: stored slots cover the true nonzeros.  The banded layout
    # stores zero-padded border tiles, which can exceed dense storage when
    # the band width approaches the block count — every other format is
    # bounded by the dense count.
    nnz_true = int((An != 0).sum())
    assert nnz_true <= op.nnz_cost()
    if not isinstance(op, BlockBandedOp):
        assert op.nnz_cost() <= max(m * n, 1)

    # halo_width: finite iff the format bounds an update's reach
    if isinstance(op, BlockBandedOp):
        assert op.halo_width == op.bands * op.block
    else:
        assert op.halo_width is None

    # to_dense reconstructs the stored values
    np.testing.assert_allclose(
        np.asarray(op.to_dense()).astype(np.float32), An, atol=1e-6)


# ---------------------------------------------------------------------------
# Deterministic grid (always runs; tier-1 must not need hypothesis)
# ---------------------------------------------------------------------------

def _case(fmt, spec):
    if spec["kind"] == "spd":
        A = random_sparse_spd(spec["n"], row_nnz=spec["row_nnz"],
                              seed=spec["seed"]).A
    elif spec["kind"] == "lsq":
        A = random_sparse_lsq(spec["m"], spec["n"], row_nnz=spec["row_nnz"],
                              seed=spec["seed"]).A
    else:
        A = block_banded_spd(spec["n"], block=spec["block"],
                             bands=spec["bands"], seed=spec["seed"]).A
    if spec.get("zero_rows"):
        A = jnp.asarray(np.array(A) * (np.arange(A.shape[0]) % 3 != 0
                                       )[:, None].astype(np.float32))
    kw = {"storage_dtype": spec.get("storage_dtype")}
    if fmt == "banded":
        kw.update(block=spec["block"], bands=spec["bands"])
    elif fmt == "ell":
        kw.update(width=spec["width"])
    op = as_operator(A, fmt, **kw)
    if kw["storage_dtype"] is not None:
        # Low-precision storage: the oracle is the ROUNDED dense matrix —
        # the operator holds exactly those values, so the conformance
        # tolerances need no loosening.
        A = jnp.asarray(A).astype(kw["storage_dtype"]).astype(jnp.float32)
    return op, A


GRID = [
    ("dense", dict(kind="spd", n=64, row_nnz=6, seed=0)),
    ("dense", dict(kind="lsq", m=96, n=32, row_nnz=5, seed=1)),
    ("banded", dict(kind="banded", n=128, block=16, bands=1, seed=2)),
    ("banded", dict(kind="banded", n=256, block=32, bands=2, seed=3)),
    ("ell", dict(kind="spd", n=64, row_nnz=6, width=32, seed=4)),
    ("ell", dict(kind="spd", n=96, row_nnz=8, width=48, seed=5,
                 zero_rows=True)),
    ("csr", dict(kind="spd", n=64, row_nnz=6, seed=6)),
    ("csr", dict(kind="lsq", m=96, n=32, row_nnz=5, seed=7)),
    ("csr", dict(kind="lsq", m=64, n=16, row_nnz=3, seed=8,
                 zero_rows=True)),
    # mixed-precision storage: same protocol vs the bf16-rounded oracle
    ("dense", dict(kind="spd", n=64, row_nnz=6, seed=0,
                   storage_dtype="bfloat16")),
    ("banded", dict(kind="banded", n=128, block=16, bands=1, seed=2,
                    storage_dtype="bfloat16")),
    ("ell", dict(kind="spd", n=64, row_nnz=6, width=32, seed=4,
                 storage_dtype="bfloat16")),
    ("csr", dict(kind="spd", n=64, row_nnz=6, seed=6,
                 storage_dtype="bfloat16")),
    ("csr", dict(kind="lsq", m=96, n=32, row_nnz=5, seed=7,
                 storage_dtype="bfloat16")),
]


@pytest.mark.parametrize("fmt,spec", GRID,
                         ids=[f"{f}-{i}" for i, (f, _) in enumerate(GRID)])
def test_operator_conformance_grid(fmt, spec):
    op, A = _case(fmt, spec)
    check_conformance(op, A)


# ---------------------------------------------------------------------------
# Hypothesis layer: the same checker over random shapes/sparsity
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=10)
    @given(st.sampled_from([32, 48, 64, 96]),
           st.integers(2, 10), st.integers(0, 2 ** 16), st.booleans())
    def test_conformance_fuzz_square(n, row_nnz, seed, zero_rows):
        A = random_sparse_spd(n, row_nnz=min(row_nnz, n // 2),
                              seed=seed % 997).A
        if zero_rows:
            A = jnp.asarray(np.array(A) * (np.arange(n) % 4 != 1
                                           )[:, None].astype(np.float32))
        for fmt, kw in (("dense", {}), ("ell", dict(width=n)),
                        ("csr", {})):
            check_conformance(as_operator(A, fmt, **kw), A)

    @settings(deadline=None, max_examples=10)
    @given(st.sampled_from([(64, 16), (96, 32), (128, 32)]),
           st.integers(1, 8), st.integers(0, 2 ** 16))
    def test_conformance_fuzz_rectangular(shape, row_nnz, seed):
        m, n = shape
        A = random_sparse_lsq(m, n, row_nnz=min(row_nnz, n),
                              seed=seed % 997).A
        for fmt in ("dense", "csr"):
            check_conformance(as_operator(A, fmt), A)

    @settings(deadline=None, max_examples=6)
    @given(st.sampled_from([(128, 16, 1), (128, 32, 2), (256, 32, 1)]),
           st.integers(0, 2 ** 16))
    def test_conformance_fuzz_banded(cfg, seed):
        n, block, bands = cfg
        A = block_banded_spd(n, block=block, bands=bands,
                             seed=seed % 997).A
        check_conformance(
            as_operator(A, "banded", block=block, bands=bands), A)
        check_conformance(as_operator(A, "csr"), A)


# ---------------------------------------------------------------------------
# Engine-facing tests (dispatch, pytrees, sequential format-genericity)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def banded_prob():
    return block_banded_spd(512, block=32, bands=2, n_rhs=4, seed=0)


@pytest.fixture(scope="module")
def sparse_prob():
    return random_sparse_spd(256, row_nnz=8, n_rhs=3, seed=1)


def test_as_operator_dispatch(sparse_prob):
    assert isinstance(as_operator(sparse_prob.A, "dense"), DenseOp)
    assert isinstance(
        as_operator(sparse_prob.A, "banded", block=32, bands=2),
        BlockBandedOp)
    assert isinstance(as_operator(sparse_prob.A, "ell", width=16), EllOp)
    assert isinstance(as_operator(sparse_prob.A, "csr"), CsrOp)
    with pytest.raises(ValueError):
        as_operator(sparse_prob.A, "coo")


def test_storage_dtype_layout(sparse_prob):
    """Mixed-precision storage invariants the conformance grid cannot see:

    * ``storage_dtype=None`` is byte-identical to the pre-parameter layout
      (the bitwise-compatibility contract of DESIGN.md);
    * bf16 storage narrows the column-index stream to int16 when every
      global column id fits (n <= int16 max) — the pairing that makes the
      A-stream 2+2 bytes/slot instead of 4+4;
    * row bookkeeping (row_id/row_start/row_nnz) stays int32, and the
      pytree leaf counts are unchanged (dtype rides in the leaves, not
      the aux data).
    """
    A = sparse_prob.A
    base = CsrOp.from_dense(A)
    same = CsrOp.from_dense(A, storage_dtype=None)
    for lb, ls in zip(jax.tree_util.tree_leaves(base),
                      jax.tree_util.tree_leaves(same)):
        assert lb.dtype == ls.dtype
        np.testing.assert_array_equal(np.asarray(lb), np.asarray(ls))

    lp = CsrOp.from_dense(A, storage_dtype="bfloat16")
    assert lp.data.dtype == jnp.bfloat16
    assert lp.indices.dtype == jnp.int16          # n=256 fits int16
    assert lp.row_id.dtype == jnp.int32
    assert lp.row_start.dtype == jnp.int32 and lp.row_nnz.dtype == jnp.int32
    assert len(jax.tree_util.tree_leaves(lp)) == 5
    assert lp.nnz == base.nnz and lp.row_cap == base.row_cap

    elp = EllOp.from_dense(A, width=16, storage_dtype="bfloat16")
    assert elp.vals.dtype == jnp.bfloat16 and elp.cols.dtype == jnp.int16
    assert len(jax.tree_util.tree_leaves(elp)) == 2

    with pytest.raises(ValueError):
        as_operator(A, "csr", storage_dtype="float16")


def _slot_gather_windows(op):
    """Oracle for ``CsrOp.padded_rows``: the (m, row_cap) windows built by
    one scalar gather per slot."""
    idx = op.row_start[:, None] + jnp.arange(op.row_cap)[None, :]
    idx = jnp.minimum(idx, op.data.shape[0] - 1)
    mask = jnp.arange(op.row_cap)[None, :] < op.row_nnz[:, None]
    return (jnp.where(mask, op.data[idx], 0.0),
            jnp.where(mask, op.indices[idx], 0))


def _edge_rows_matrix():
    """40 x 40 rows with 0 and row_cap nonzeros.  Packed with ``lane=1``,
    the last panel is the fullest, so its empty last row starts at
    ``num_panels * W`` and its window is exactly the slack at the end."""
    rng = np.random.default_rng(11)
    A = np.zeros((40, 40), np.float32)
    counts = rng.integers(1, 6, 40)
    counts[[0, 13, 39]] = 0
    counts[[5, 32, 38]] = 12
    for r, c in enumerate(counts):
        A[r, rng.choice(40, c, replace=False)] = rng.standard_normal(c)
    return A


@pytest.mark.parametrize("call", ["eager", "jit"])
@pytest.mark.parametrize("layout", ["packed", "permuted"])
@pytest.mark.parametrize("storage", [None, "bfloat16"])
def test_csr_padded_rows_match_slot_gather(storage, layout, call):
    from repro.core import partition as pt
    op = CsrOp.from_dense(_edge_rows_matrix(), lane=1, storage_dtype=storage)
    assert op.row_cap == 12
    assert int(op.row_start[-1]) + op.row_cap == op.data.shape[0]
    if layout == "permuted":
        op = pt.permute_rows(op, pt.balanced_row_permutation(op, 4),
                             symmetric=True)
    got = (op.padded_rows() if call == "eager"
           else jax.jit(lambda o: o.padded_rows())(op))
    want = _slot_gather_windows(op)
    assert got[0].dtype == op.data.dtype and got[1].dtype == op.indices.dtype
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape == (40, 12)
        np.testing.assert_array_equal(g.view(f"u{g.itemsize}"),
                                      w.view(f"u{w.itemsize}"))


def _ragged_matrix(m, n):
    """m x n rows of 0 to 9 nonzeros, rows 0 and m // 2 empty."""
    rng = np.random.default_rng(m * 100 + n)
    A = np.zeros((m, n), np.float32)
    counts = rng.integers(1, 10, m)
    counts[[0, m // 2]] = 0
    for r, c in enumerate(counts):
        A[r, rng.choice(n, c, replace=False)] = rng.standard_normal(c)
    return A


def _f32_dot_bound(A, x, terms):
    """Float32 error bound of a ``terms``-long dot product, row by row:
    each product and each add rounds once, so the error stays under
    ``(terms + 1) * eps * (|A| @ |x|)`` (Higham, Thm. 3.1 to first order)."""
    return (terms + 1) * np.finfo(np.float32).eps * (np.abs(A) @ np.abs(x))


@pytest.mark.parametrize("call", ["eager", "jit"])
@pytest.mark.parametrize("storage", [None, "bfloat16"])
@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("shape", [(40, 40), (40, 24), (24, 40)])
def test_csr_matvec_ref_is_window_reduce(shape, k, storage, call):
    """``CsrOp.matvec_ref`` is the ELL reference over ``padded_rows()``:
    bitwise that form, and the float64 ``A @ x`` to float32 rounding, on
    ragged rows with empty ones, square and rectangular, eager and jitted."""
    from repro.kernels import ref
    m, n = shape
    op = CsrOp.from_dense(_ragged_matrix(m, n), storage_dtype=storage)
    if storage == "bfloat16":
        assert op.indices.dtype == jnp.int16
    x = jax.random.normal(jax.random.key(k), (n, k), jnp.float32)

    def mv(o, v):
        return o.matvec_ref(v)

    def ell(o, v):
        return ref.spmv_ell_ref(*o.padded_rows(), v)

    if call == "jit":
        mv, ell = jax.jit(mv), jax.jit(ell)
    got = np.asarray(mv(op, x))
    assert got.dtype == np.float32 and got.shape == (m, k)
    np.testing.assert_array_equal(got.view(np.uint32),
                                  np.asarray(ell(op, x)).view(np.uint32))

    # The stored values exactly (bf16 storage rounds them), in float64.
    A64 = np.asarray(op.to_dense(), np.float64)
    x64 = np.asarray(x, np.float64)
    err = np.abs(got - A64 @ x64)
    assert (err <= _f32_dot_bound(A64, x64, op.row_cap)).all()
    assert (got[[0, m // 2]] == 0).all()          # empty rows read zero


def test_csr_record_metrics_match_float64(sparse_prob):
    """``record_metrics`` on a ``CsrOp`` (through its window-reduce
    ``matvec_ref``) gives the float64 residual and A-norm error."""
    from repro.core.engine import record_metrics
    op = CsrOp.from_dense(sparse_prob.A)
    xs = sparse_prob.x_star
    x = xs + 1e-2 * jax.random.normal(jax.random.key(3), xs.shape)
    err, resid = jax.jit(functools.partial(record_metrics, norm="A"))(
        op, sparse_prob.b, x, xs)
    A64, b64 = (np.asarray(v, np.float64) for v in (sparse_prob.A,
                                                    sparse_prob.b))
    x64, e64 = np.asarray(x, np.float64), np.asarray(x - xs, np.float64)
    r64 = b64 - A64 @ x64
    eps = np.finfo(np.float32).eps
    # Each entry of b − Ax carries its row's dot bound, one more rounding
    # for the subtraction, and b's own; the norm over m rows rounds m times.
    rerr = _f32_dot_bound(A64, x64, op.row_cap + 1) + eps * np.abs(b64)
    rn64 = np.linalg.norm(r64, axis=0)
    tol = np.linalg.norm(rerr, axis=0) + op.shape[0] * eps * rn64
    assert (np.abs(np.asarray(resid) - rn64) <= tol).all()
    # eᵀAe: each entry of Ae carries the dot bound; the outer sum over n
    # rows adds n roundings of the |e|ᵀ|A||e| scale.
    want = np.einsum("nk,nk->k", e64, A64 @ e64)
    scale = np.einsum("nk,nk->k", np.abs(e64), np.abs(A64) @ np.abs(e64))
    tol = (op.row_cap + op.shape[0] + 2) * eps * scale
    assert (np.abs(np.asarray(err) - want) <= tol).all()


def test_shard_specs_and_structure(banded_prob, sparse_prob):
    """Metadata the conformance grid does not pin: shard specs and the
    banded tile geometry."""
    bop = BlockBandedOp.from_dense(banded_prob.A, block=32, bands=2)
    assert bop.nb == 16 and bop.block == 32 and bop.width == 5
    assert DenseOp(sparse_prob.A).shard_spec("w") == \
        jax.sharding.PartitionSpec("w", None)
    assert bop.shard_spec("w") == \
        jax.sharding.PartitionSpec("w", None, None, None)
    assert CsrOp.from_dense(sparse_prob.A).shard_spec("w") == \
        jax.sharding.PartitionSpec("w", None)


def test_csr_row_reach(banded_prob, sparse_prob):
    """Per-row reach refines the scalar halo: bounded by the band on a
    banded-structure matrix, and the slab graph of unstructured sparsity is
    dense (what the a2a fallback keys on)."""
    bop = CsrOp.from_dense(banded_prob.A)   # block=32, bands=2 -> reach<160
    reach = np.asarray(bop.row_reach())
    assert reach.shape == (512,) and reach.max() < 5 * 32
    need = bop.slab_neighbors(4)
    assert not need[0, 2] and not need[0, 3]      # far slabs never read
    assert CsrOp.from_dense(sparse_prob.A).slab_neighbors(4).all()


def test_operators_are_pytrees(sparse_prob):
    """Operators pass through jit/tree transforms (the engine requires it)."""
    op = EllOp.from_dense(sparse_prob.A, width=16)
    leaves, treedef = jax.tree_util.tree_flatten(op)
    assert len(leaves) == 2
    op2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(op2, EllOp) and op2.width == 16

    cop = CsrOp.from_dense(sparse_prob.A)
    cleaves, ctreedef = jax.tree_util.tree_flatten(cop)
    assert len(cleaves) == 5
    cop2 = jax.tree_util.tree_unflatten(ctreedef, cleaves)
    assert isinstance(cop2, CsrOp) and cop2.shape == cop.shape

    @jax.jit
    def through(o, x):
        return o.matvec_ref(x)

    for o in (op, cop):
        np.testing.assert_allclose(
            np.asarray(through(o, sparse_prob.x_star)),
            np.asarray(o.matvec_ref(sparse_prob.x_star)), atol=1e-6)


def test_sequential_engine_ell_tracks_dense(sparse_prob):
    """The same GS/RK action run through the ELL format stays within fp
    noise of the dense format (same keys => same index sequence)."""
    x0 = jnp.zeros_like(sparse_prob.x_star)
    eop = EllOp.from_dense(sparse_prob.A, width=32)   # width >= row_nnz: exact
    dop = DenseOp(sparse_prob.A)
    se = solve_sequential(eop, sparse_prob.b, x0, sparse_prob.x_star,
                          action="gs", key=jax.random.key(4), num_iters=2048)
    sd = solve_sequential(dop, sparse_prob.b, x0, sparse_prob.x_star,
                          action="gs", key=jax.random.key(4), num_iters=2048)
    assert float(jnp.abs(se.x - sd.x).max()) < 1e-4
    # row (Kaczmarz) action too — note sampling uses the ELL row norms,
    # which equal the dense row norms when the width captures every nonzero
    re = solve_sequential(eop, sparse_prob.b, x0, sparse_prob.x_star,
                          action="rk", key=jax.random.key(5), num_iters=1024)
    rd = solve_sequential(dop, sparse_prob.b, x0, sparse_prob.x_star,
                          action="rk", key=jax.random.key(5), num_iters=1024)
    assert float(jnp.abs(re.x - rd.x).max()) < 1e-4


def test_sequential_engine_csr_tracks_dense(sparse_prob):
    """GS / block-GS / RK actions through the CSR format stay within fp
    noise of the dense format (same keys => same index sequence)."""
    x0 = jnp.zeros_like(sparse_prob.x_star)
    cop = CsrOp.from_dense(sparse_prob.A)
    dop = DenseOp(sparse_prob.A)
    for action, kw in (("gs", {}), ("gs", {"block": 16}), ("rk", {})):
        ni = 512 if kw else 2048
        sc = solve_sequential(cop, sparse_prob.b, x0, sparse_prob.x_star,
                              action=action, key=jax.random.key(4),
                              num_iters=ni, **kw)
        sd = solve_sequential(dop, sparse_prob.b, x0, sparse_prob.x_star,
                              action=action, key=jax.random.key(4),
                              num_iters=ni, **kw)
        assert float(jnp.abs(sc.x - sd.x).max()) < 1e-4, (action, kw)


def test_sequential_engine_banded_converges(banded_prob):
    """Θ(nnz) sequential block-GS on the banded format actually solves."""
    op = BlockBandedOp.from_dense(banded_prob.A, block=32, bands=2)
    x0 = jnp.zeros_like(banded_prob.x_star)
    res = solve_sequential(op, banded_prob.b, x0, banded_prob.x_star,
                           action="gs", key=jax.random.key(3), num_iters=320,
                           beta=0.9, record_every=80)
    e = np.asarray(res.err_sq)
    assert e[-1].max() < 1e-2 * e[0].max()
    rel = float(jnp.linalg.norm(banded_prob.b - banded_prob.A @ res.x)
                / jnp.linalg.norm(banded_prob.b))
    assert rel < 1e-2, rel
