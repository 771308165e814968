"""Fused sweep-kernel layer (PR 5): parity with the scan engine across the
operator-conformance grid, kernel-level fuzz on ragged/degenerate pick
streams, the CSR matvec overhaul, and the distributed fused local phases.

Parity contract: ``fused=True`` iterates match the scan engine to ≤ 1e-5
relative error for the padded-row (CSR/ELL) GS and RK actions, and bitwise
for the banded GS action, whose kernel shares the scan's arithmetic order.
The padded-row kernels sum each row's ≤ width products in slot order
while the scan's ``einsum`` picks its own order: each step's inner product
moves by at most width·eps relative (≈ 5e-7 at width 8), and a GS or RK
sweep on these well-conditioned systems does not amplify a per-step
perturbation, so 48 steps stay well inside 1e-5.  An explicit
``fused=True`` on a format without a sweep kernel raises.
"""
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import block_banded_spd, random_sparse_lsq, random_sparse_spd
from repro.core.engine import Schedule, sample_rows, solve, solve_sequential
from repro.core.operators import BlockBandedOp, CsrOp, DenseOp, EllOp
from repro.kernels import ops

from conftest import run_forced_device_script
from test_operators import GRID, _case


def _solve(op, b, x0, x_star, action, fused):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        return solve_sequential(op, b, x0, x_star, action=action,
                                key=jax.random.key(7), num_iters=48,
                                record_every=24, beta=0.9, fused=fused)


def _assert_rel_close(want, got, rtol=1e-5):
    """||got - want|| / ||want|| <= rtol (the module's parity budget)."""
    denom = float(jnp.linalg.norm(want)) or 1.0
    assert float(jnp.linalg.norm(got - want)) / denom <= rtol


@pytest.mark.parametrize("fmt,spec", GRID,
                         ids=[f"{f}-{i}" for i, (f, _) in enumerate(GRID)])
def test_fused_matches_scan_on_grid(fmt, spec):
    """solve_sequential(fused=True) vs the scan engine over the full
    operator-conformance grid: banded GS bitwise, CSR/ELL GS and RK <= 1e-5
    relative; formats without a sweep kernel (dense) raise."""
    op, A = _case(fmt, spec)
    m, n = op.shape
    k = 2
    x_star = jax.random.normal(jax.random.key(11), (n, k), jnp.float32)
    b = jnp.asarray(np.asarray(A)) @ x_star
    x0 = jnp.zeros_like(x_star)

    actions = []
    if m == n:
        actions.append("gs")
    if fmt != "banded":      # sequential banded RK is not a scan path either
        actions.append("rk")
    for action in actions:
        rs = _solve(op, b, x0, x_star, action, fused=False)
        if fmt == "dense":
            with pytest.raises(NotImplementedError, match="no fused sweep"):
                _solve(op, b, x0, x_star, action, fused=True)
            continue
        rf = _solve(op, b, x0, x_star, action, fused=True)
        if action == "gs" and fmt == "banded":
            np.testing.assert_array_equal(np.asarray(rs.x), np.asarray(rf.x))
            np.testing.assert_array_equal(np.asarray(rs.err_sq),
                                          np.asarray(rf.err_sq))
            np.testing.assert_array_equal(np.asarray(rs.resid),
                                          np.asarray(rf.resid))
        else:
            _assert_rel_close(rs.x, rf.x)
            np.testing.assert_allclose(np.asarray(rs.resid),
                                       np.asarray(rf.resid), rtol=1e-4,
                                       atol=1e-5)


def test_fused_fallback_warns_and_matches():
    """Formats without a sweep kernel (dense) refuse an explicit
    ``fused=True`` instead of silently running the scan; ``fused="auto"``
    (nothing forced) runs the scan's exact iterates."""
    prob = random_sparse_spd(64, row_nnz=6, n_rhs=2, seed=3)
    op = DenseOp(prob.A)
    x0 = jnp.zeros_like(prob.x_star)
    for action in ("gs", "rk"):
        rs = solve_sequential(op, prob.b, x0, prob.x_star, action=action,
                              key=jax.random.key(1), num_iters=16)
        with pytest.raises(NotImplementedError, match="no fused sweep kernel"):
            solve_sequential(op, prob.b, x0, prob.x_star, action=action,
                             key=jax.random.key(1), num_iters=16, fused=True)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            ra = solve_sequential(op, prob.b, x0, prob.x_star, action=action,
                                  key=jax.random.key(1), num_iters=16,
                                  fused="auto")
        np.testing.assert_array_equal(np.asarray(rs.x), np.asarray(ra.x))


def test_fused_front_door():
    """Schedule(fused=True) through solve() reaches the sweep path (csr GS,
    within the parity budget); on the bounded-delay simulator it is
    rejected at
    ``Schedule.validate()`` (effective-config validation, ISSUE 9) — the
    old warn-and-ignore fallback silently ran a different execution mode
    than the schedule asked for."""
    prob = random_sparse_spd(64, row_nnz=6, n_rhs=2, seed=4)
    kw = dict(key=jax.random.key(2), format="csr")
    r0 = solve(prob, schedule=Schedule(num_iters=32, record_every=16), **kw)
    r1 = solve(prob, schedule=Schedule(num_iters=32, record_every=16,
                                       fused=True), **kw)
    _assert_rel_close(r0.x, r1.x)
    # the solve(..., fused=...) override beats schedule.fused
    r2 = solve(prob, schedule=Schedule(num_iters=32, record_every=16),
               fused=True, **kw)
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))
    with pytest.raises(ValueError, match="bounded-delay simulator"):
        solve(prob, delay_key=jax.random.key(3),
              schedule=Schedule(num_iters=16, tau=4, fused=True), **kw)


# ---------------------------------------------------------------------------
# Kernel-level references and degenerate pick-stream fuzz
# ---------------------------------------------------------------------------

def _gs_ref(op, b, x, picks, beta=1.0):
    def step(x, r):
        return x.at[r].add(beta * (b[r] - op.row_dot(r, x))), None
    return jax.lax.scan(step, x, picks)[0]


def _rk_ref(op, b, rn, x, picks, beta=1.0):
    def step(x, r):
        g = (b[r] - op.row_dot(r, x)) / rn[r]
        return op.rk_update(x, r, g, beta), None
    return jax.lax.scan(step, x, picks)[0]


@pytest.mark.parametrize("picks", [
    [],                          # empty sweep: the kernel must be a no-op
    [5, 5, 5, 5],                # repeated row (self-coupled updates)
    [12, 12, 0, 5, 12],          # ragged last panel (m=13, R=8) + repeats
    [0, 12, 6, 3, 9, 1],
], ids=["empty", "repeated", "last-panel", "mixed"])
def test_sweep_rows_degenerate_picks(picks):
    # GS needs a square system (rows index the iterate): n=13 keeps the
    # last CSR panel ragged (13 % 8 != 0) so pick 12 exercises it.
    k = 3
    sprob = random_sparse_spd(13, row_nnz=3, n_rhs=k, seed=5)
    x_sq = jax.random.normal(jax.random.key(6), (13, k))
    picks = jnp.asarray(picks, jnp.int32)
    cop = CsrOp.from_dense(sprob.A)
    for op in (cop, EllOp(*cop.padded_rows())):
        vals, cols = op.padded_rows()
        got = ops.sweep_rows_gs(vals, cols, sprob.b, x_sq, picks, beta=0.7)
        want = _gs_ref(op, sprob.b, x_sq, picks, beta=0.7)
        _assert_rel_close(want, got)

    # RK additionally covers the rectangular shape (writes land in column
    # space, so picks range over all 13 rows while x has 8).
    lprob = random_sparse_lsq(13, 8, row_nnz=3, n_rhs=k, seed=5)
    x_rect = jax.random.normal(jax.random.key(6), (8, k))
    lcop = CsrOp.from_dense(lprob.A)
    for op in (lcop, EllOp(*lcop.padded_rows())):
        vals, cols = op.padded_rows()
        rn = op.row_norms_sq()
        got = ops.sweep_rows_rk(vals, cols, lprob.b, rn, x_rect, picks,
                                beta=0.7)
        want = _rk_ref(op, lprob.b, rn, x_rect, picks, beta=0.7)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_sweep_rows_zero_rows_are_noops():
    """GS picks landing on all-zero rows only move x by beta*b[r] — and the
    kernel agrees with the scan reference bitwise (the masked windows carry
    exact zeros)."""
    m = n = 16
    A = np.array(random_sparse_spd(n, row_nnz=4, seed=7).A)
    A[::5] = 0.0
    op = CsrOp.from_dense(jnp.asarray(A))
    b = jnp.ones((m, 2))
    x = jnp.zeros((n, 2))
    picks = jnp.asarray([0, 5, 10, 15, 5, 0], jnp.int32)
    vals, cols = op.padded_rows()
    got = ops.sweep_rows_gs(vals, cols, b, x, picks)
    want = _gs_ref(op, b, x, picks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kernel", ["gs", "rk_delta"])
def test_sweep_rows_long_stream_splits_into_launches(kernel, monkeypatch):
    """A pick stream longer than ``MAX_PICKS_PER_LAUNCH`` (the SMEM
    prefetch cap) runs as consecutive launches over the same carries: the
    same iterates as chaining the kernel over the segments by hand."""
    from repro.kernels import sweep_csr
    n, k, seg, steps = 24, 2, 4, 11          # 3 launches, the last ragged
    prob = random_sparse_spd(n, row_nnz=4, n_rhs=k, seed=21)
    op = CsrOp.from_dense(prob.A)
    vals, cols = op.padded_rows()
    rn = op.row_norms_sq()
    picks = jax.random.randint(jax.random.key(23), (steps,), 0, n)

    def run(carries, p):
        if kernel == "gs":
            return (ops.sweep_rows_gs(vals, cols, prob.b, carries[0], p,
                                      beta=0.8),)
        return ops.sweep_rows_rk_delta(vals, cols, prob.b, rn, *carries, p,
                                       beta=0.8)

    x = jax.random.normal(jax.random.key(22), (n, k))
    carries = (x,) if kernel == "gs" else (x, jnp.zeros_like(x))
    by_hand = carries
    for lo in range(0, steps, seg):
        by_hand = run(by_hand, picks[lo:lo + seg])
    monkeypatch.setattr(sweep_csr, "MAX_PICKS_PER_LAUNCH", seg)
    split = run(carries, picks)
    for got, want in zip(split, by_hand, strict=True):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if kernel == "gs":
        _assert_rel_close(_gs_ref(op, prob.b, x, picks, beta=0.8), split[0])


def test_banded_sweeps_empty_picks():
    prob = block_banded_spd(64, block=16, bands=1, n_rhs=2, seed=8)
    op = BlockBandedOp.from_dense(prob.A, block=16, bands=1)
    empty = jnp.zeros((0,), jnp.int32)
    x = jax.random.normal(jax.random.key(9), (64, 2))
    out = op.gs_sweep(prob.b, x, empty)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    halo = op.bands * op.block
    xw = jnp.pad(x, ((halo, halo), (0, 0)))
    dw = jnp.zeros_like(xw)
    rn = jnp.where(op.row_norms_sq() > 0, op.row_norms_sq(), 1.0)
    xo, do = ops.banded_rk_sweep(op.A_bands, prob.b, rn, xw, dw, empty,
                                 empty, block=op.block, bands=op.bands)
    np.testing.assert_array_equal(np.asarray(xo), np.asarray(xw))
    np.testing.assert_array_equal(np.asarray(do), np.asarray(dw))


def test_rk_sweep_long_stream_stays_close():
    """A full sampled RK sweep (the engine's actual pick law, many steps)
    stays within the 1e-5 relative-parity budget on a rectangular
    system."""
    prob = random_sparse_lsq(128, 32, row_nnz=6, n_rhs=2, seed=10)
    op = CsrOp.from_dense(prob.A)
    rn = op.row_norms_sq()
    picks = sample_rows(jax.random.key(12), rn, 256)
    x = jnp.zeros((32, 2))
    vals, cols = op.padded_rows()
    got = ops.sweep_rows_rk(vals, cols, prob.b, rn, x, picks, beta=0.9)
    want = _rk_ref(op, prob.b, rn, x, picks, beta=0.9)
    denom = float(jnp.linalg.norm(want)) or 1.0
    assert float(jnp.linalg.norm(got - want)) / denom <= 1e-5


# ---------------------------------------------------------------------------
# CSR matvec overhaul: sliced gather-accumulate is the default path
# ---------------------------------------------------------------------------

def test_csr_matvec_paths_agree():
    """Default (sliced), forced-skip, and legacy segsum matvecs agree with
    the dense oracle; auto-selection picks predication exactly when the
    pattern has empty panels."""
    prob = random_sparse_spd(96, row_nnz=7, n_rhs=3, seed=13)
    x = jax.random.normal(jax.random.key(14), (96, 3))
    cop = CsrOp.from_dense(prob.A)
    want = prob.A @ x
    for y in (cop.matvec(x), cop.matvec(x, skip_empty=True),
              cop.matvec(x, skip_empty=False), cop.matvec_segsum(x)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert not bool((np.asarray(cop.panel_nnz()) == 0).any())

    Ap = np.array(prob.A)
    Ap[0:cop.rows_per_panel] = 0.0
    pop = CsrOp.from_dense(jnp.asarray(Ap))
    assert bool((np.asarray(pop.panel_nnz()) == 0).any())
    # predicated and plain kernels are bitwise-identical
    np.testing.assert_array_equal(
        np.asarray(pop.matvec(x, skip_empty=True)),
        np.asarray(pop.matvec(x, skip_empty=False)))


def test_csr_sliced_rows_view():
    """The sliced view reconstructs the matrix and is memoized on concrete
    operators."""
    prob = random_sparse_lsq(13, 8, row_nnz=3, n_rhs=1, seed=15)
    op = CsrOp.from_dense(prob.A)
    vals, cols = op.sliced_rows()
    mp = -(-13 // op.rows_per_panel) * op.rows_per_panel
    assert vals.shape == cols.shape and vals.shape[0] == mp
    assert vals.shape[1] % 8 == 0 and vals.shape[1] >= op.row_cap
    recon = jnp.zeros((13, 8)).at[
        jnp.arange(mp)[:, None].clip(0, 12), cols].add(
            jnp.where(jnp.arange(mp)[:, None] < 13, vals, 0.0))
    np.testing.assert_allclose(np.asarray(recon), np.asarray(prob.A),
                               atol=1e-6)
    assert op.sliced_rows()[0] is vals          # memoized


# ---------------------------------------------------------------------------
# Distributed fused local phases (forced-4-device subprocess)
# ---------------------------------------------------------------------------

DIST_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import block_banded_spd
    from repro.core.operators import BlockBandedOp, CsrOp
    from repro.core.engine import solve_distributed
    from repro.launch.mesh import make_host_mesh

    prob = block_banded_spd(256, block=16, bands=1, n_rhs=3, seed=2)
    op = BlockBandedOp.from_dense(prob.A, block=16, bands=1)
    mesh = make_host_mesh(4)
    x0 = jnp.zeros_like(prob.x_star)
    kw = dict(key=jax.random.key(5), mesh=mesh, rounds=5, local_steps=4,
              beta=0.8)
    for action, syncs in (("gs", ("allgather", "halo")), ("rk", ("psum",))):
        for sync in syncs:
            r0 = solve_distributed(op, prob.b, x0, prob.x_star,
                                   action=action, sync=sync, **kw)
            r1 = solve_distributed(op, prob.b, x0, prob.x_star,
                                   action=action, sync=sync, fused=True,
                                   **kw)
            assert jnp.array_equal(r0.x, r1.x), (action, sync)
            assert jnp.array_equal(r0.resid, r1.resid), (action, sync)
            assert jnp.array_equal(r0.err_sq, r1.err_sq), (action, sync)

    # sparse distributed local phases are fused too: GS and local-sampling
    # RK within the module's 1e-5 relative parity budget (slot-order row
    # sums vs the scan's einsum)
    def rel(a, b):
        return float(jnp.linalg.norm(a - b)) / (float(jnp.linalg.norm(a))
                                                or 1.0)

    cop = CsrOp.from_dense(prob.A)
    for sync in ("allgather", "a2a"):
        r0 = solve_distributed(cop, prob.b, x0, prob.x_star, action="gs",
                               sync=sync, **kw)
        r1 = solve_distributed(cop, prob.b, x0, prob.x_star, action="gs",
                               sync=sync, fused=True, **kw)
        assert rel(r0.x, r1.x) <= 1e-5, sync
        assert rel(r0.resid, r1.resid) <= 1e-5, sync
    r0 = solve_distributed(cop, prob.b, x0, prob.x_star, action="rk",
                           sync="psum", **kw)
    r1 = solve_distributed(cop, prob.b, x0, prob.x_star, action="rk",
                           sync="psum", fused=True, **kw)
    assert rel(r0.x, r1.x) <= 1e-5

    # strategies without a fused phase (dense) refuse an explicit fused=True
    from repro.core.operators import DenseOp
    dop = DenseOp(prob.A)
    try:
        solve_distributed(dop, prob.b, x0, prob.x_star, action="gs",
                          sync="allgather", fused=True, **kw)
        raise AssertionError("fused=True on dense_gs did not raise")
    except NotImplementedError as e:
        assert "no fused sweep kernel" in str(e)
    print("FUSED_DIST_OK")
""")


@pytest.mark.slow
def test_distributed_fused_matches_scan():
    run_forced_device_script(DIST_SCRIPT, marker="FUSED_DIST_OK")
