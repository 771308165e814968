"""PR 6: overlapped (double-buffered) sync, the measured-staleness trace,
and the distributed-metrics bugfix sweep.

Contracts under test:

* ``scheduled_tau(..., overlap=True)`` adds exactly the quantified
  previous-round payload; ``Schedule(overlap=True)`` validates and routes
  it through ``effective_tau``.
* The overlapped strategy variants (halo_gs / sparse_gs / sparse_rk)
  converge on the forced-4-device mesh, their per-round ``lag`` trace is
  0 on round one and the in-flight payload afterwards, and the measured
  staleness ``max(lag) + scheduled_tau(overlap=False)`` never exceeds the
  scheduled overlap bound.  Strategies without an overlapped variant fall
  back to lockstep EXACTLY (bitwise) with a ``UserWarning``.
* ``solve_distributed(x_star=None)`` works on EVERY strategy row of
  ``_DISTRIBUTED_STRATEGIES`` (NaN err_sq, finite residuals) — the dense
  strategies used to crash (ISSUE 6 satellite).
* ``theory.epoch_len`` / ``chi_consistent`` reject ``lam_max >= n`` with
  an informative ``ValueError`` instead of a math domain error.
* ``_sequential_fused_impl`` keeps ``beta`` static by design: same beta
  hits the jit cache, a new beta adds exactly one entry.
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import random_sparse_spd
from repro.core.engine import Schedule, scheduled_tau
from repro.core import theory

from conftest import run_forced_device_script


# ---------------------------------------------------------------------------
# Staleness accounting (pure host-side rules)
# ---------------------------------------------------------------------------

def test_scheduled_tau_overlap_term():
    # per-worker streams (GS): + (P-1) * L
    assert scheduled_tau(4, 8) == 24
    assert scheduled_tau(4, 8, overlap=True) == 48
    # shared stream (dense/banded RK): + L
    assert scheduled_tau(4, 8, shared_stream=True) == 7
    assert scheduled_tau(4, 8, shared_stream=True, overlap=True) == 15
    # local sampling (sparse RK): + (P-1) * L
    assert scheduled_tau(4, 8, local_sampling=True) == 31
    assert scheduled_tau(4, 8, local_sampling=True, overlap=True) == 55
    # P = 1: nothing is ever in flight
    for kw in ({}, {"shared_stream": True}, {"local_sampling": True}):
        assert scheduled_tau(1, 8, overlap=True, **kw) == \
            scheduled_tau(1, 8, **kw)


def test_schedule_overlap_validation():
    Schedule(rounds=4, local_steps=8, overlap=True).validate()
    with pytest.raises(ValueError, match="overlap"):
        Schedule(num_iters=16, overlap=True).validate()
    sched = Schedule(rounds=4, local_steps=8, overlap=True)
    assert sched.effective_tau(4) == 48
    assert sched.effective_tau(4, local_sampling=True) == 55
    assert Schedule(rounds=4, local_steps=8).effective_tau(4) == 24


# ---------------------------------------------------------------------------
# Theory boundary guards (satellite)
# ---------------------------------------------------------------------------

def test_theory_lam_max_boundary():
    assert theory.epoch_len(2.0, 64) > 0
    assert np.isfinite(theory.chi_consistent(0.1, 4, 2.0, 64))
    for bad in (64.0, 65.0, 0.0, -1.0):
        with pytest.raises(ValueError, match="lam_max"):
            theory.epoch_len(bad, 64)
        with pytest.raises(ValueError, match="lam_max"):
            theory.chi_consistent(0.1, 4, bad, 64)
    # just inside the boundary stays defined (large, but finite)
    assert theory.epoch_len(63.999, 64) >= 1
    assert np.isfinite(theory.chi_consistent(0.1, 2, 63.999, 64))


# ---------------------------------------------------------------------------
# Static-beta contract of the fused sequential path (satellite)
# ---------------------------------------------------------------------------

def test_fused_beta_static_recompiles():
    """``beta`` is deliberately static on the fused path (baked into the
    sweep kernel): repeating a beta must hit the jit cache, a new beta
    must add exactly one cache entry."""
    from repro.core.engine import _sequential_fused_impl, solve_sequential

    prob = random_sparse_spd(32, row_nnz=4, n_rhs=2, seed=21)
    from repro.core.operators import CsrOp
    op = CsrOp.from_dense(prob.A)
    x0 = jnp.zeros_like(prob.x_star)

    def run(beta):
        return solve_sequential(op, prob.b, x0, prob.x_star, action="gs",
                                key=jax.random.key(3), num_iters=8,
                                beta=beta, fused=True)

    run(0.5)
    base = _sequential_fused_impl._cache_size()
    run(0.5)                                        # cache hit
    assert _sequential_fused_impl._cache_size() == base
    run(0.25)                                       # one recompile
    assert _sequential_fused_impl._cache_size() == base + 1


# ---------------------------------------------------------------------------
# Forced-4-device: overlapped variants + x_star=None strategy sweep
# ---------------------------------------------------------------------------

OVERLAP_SCRIPT = textwrap.dedent("""
    import warnings
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import block_banded_spd, random_sparse_spd
    from repro.core.operators import BlockBandedOp, CsrOp, DenseOp
    from repro.core.engine import Schedule, scheduled_tau, solve, \\
        solve_distributed
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(4)
    P, L, rounds = 4, 8, 30
    prob = random_sparse_spd(64, row_nnz=6, n_rhs=2, seed=2)
    cop = CsrOp.from_dense(prob.A)
    x0 = jnp.zeros_like(prob.x_star)
    kw = dict(key=jax.random.key(5), mesh=mesh, rounds=rounds,
              local_steps=L, beta=0.9)

    def check_lag(r, base_tau):
        lag = np.asarray(r.lag)
        assert lag.shape == (rounds,)
        assert lag[0] == 0, lag[:3]                  # nothing in flight yet
        assert (lag[1:] == (P - 1) * L).all(), lag   # steady payload
        # measured staleness respects the scheduled overlap bound
        assert int(lag.max()) + base_tau <= int(r.tau)

    # --- sparse_gs: overlap converges, lag as scheduled, fused matches ---
    r_lock = solve_distributed(cop, prob.b, x0, prob.x_star, action="gs",
                               sync="allgather", **kw)
    assert r_lock.lag is None
    r_ov = solve_distributed(cop, prob.b, x0, prob.x_star, action="gs",
                             sync="allgather", overlap=True, **kw)
    assert int(r_ov.tau) == scheduled_tau(P, L, overlap=True) == 48
    check_lag(r_ov, scheduled_tau(P, L))
    assert float(r_ov.err_sq[-1].max()) < 1e-3       # converges
    assert not jnp.array_equal(r_ov.x, r_lock.x)     # genuinely staler reads
    r_ovf = solve_distributed(cop, prob.b, x0, prob.x_star, action="gs",
                              sync="allgather", overlap=True, fused=True,
                              **kw)
    # fused overlap: the kernel sums each row in slot order where the scan
    # uses einsum — f32 roundoff, inside the 1e-5 relative parity budget
    # of tests/test_sweep_kernels.py
    denom = float(jnp.linalg.norm(r_ov.x)) or 1.0
    assert float(jnp.linalg.norm(r_ov.x - r_ovf.x)) / denom <= 1e-5
    np.testing.assert_allclose(np.asarray(r_ov.err_sq),
                               np.asarray(r_ovf.err_sq), rtol=1e-4, atol=1e-9)
    # a2a overlap reads the same slabs -> identical iterates
    r_a2a = solve_distributed(cop, prob.b, x0, prob.x_star, action="gs",
                              sync="a2a", overlap=True, **kw)
    if not jnp.array_equal(r_a2a.x, r_ov.x):
        # dense neighbor graph fell back to allgather; still identical
        raise AssertionError("a2a overlap diverged from allgather overlap")

    # --- sparse_rk: overlap converges (final delta flushed), lag trace ---
    r_lock = solve_distributed(cop, prob.b, x0, prob.x_star, action="rk",
                               sync="psum", **kw)
    r_ov = solve_distributed(cop, prob.b, x0, prob.x_star, action="rk",
                             sync="psum", overlap=True, **kw)
    assert int(r_ov.tau) == scheduled_tau(P, L, local_sampling=True,
                                          overlap=True) == 55
    check_lag(r_ov, scheduled_tau(P, L, local_sampling=True))
    assert float(r_ov.err_sq[-1].max()) < 2e-2
    r_ovf = solve_distributed(cop, prob.b, x0, prob.x_star, action="rk",
                              sync="psum", overlap=True, fused=True, **kw)
    denom = float(jnp.linalg.norm(r_ov.x)) or 1.0
    assert float(jnp.linalg.norm(r_ov.x - r_ovf.x)) / denom <= 1e-5

    # --- halo_gs: overlapped edge exchange converges ---
    bprob = block_banded_spd(256, block=16, bands=1, n_rhs=2, seed=2)
    bop = BlockBandedOp.from_dense(bprob.A, block=16, bands=1)
    bx0 = jnp.zeros_like(bprob.x_star)
    r_ov = solve_distributed(bop, bprob.b, bx0, bprob.x_star, action="gs",
                             sync="halo", overlap=True, fused=True, **kw)
    check_lag(r_ov, scheduled_tau(P, L))
    assert float(r_ov.err_sq[-1].max()) < 1e-6

    # --- strategies without an overlapped variant: exact fallback ---
    dop = DenseOp(prob.A)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r_fb = solve_distributed(dop, prob.b, x0, prob.x_star, action="gs",
                                 sync="allgather", overlap=True, **kw)
    assert any("no overlapped-sync variant" in str(x.message) for x in w)
    r_ls = solve_distributed(dop, prob.b, x0, prob.x_star, action="gs",
                             sync="allgather", **kw)
    assert r_fb.lag is None and int(r_fb.tau) == int(r_ls.tau)
    assert jnp.array_equal(r_fb.x, r_ls.x)

    # --- front door: Schedule(overlap=True) reaches the variant ---
    r = solve(prob, key=jax.random.key(5), format="csr", mesh=mesh,
              beta=0.9, schedule=Schedule(rounds=rounds, local_steps=L,
                                          overlap=True))
    assert r.lag is not None                         # SPD -> gs -> sparse_gs
    assert int(r.tau) == scheduled_tau(P, L, overlap=True)
    print("OVERLAP_OK")
""")


@pytest.mark.slow
def test_overlap_forced_devices():
    run_forced_device_script(OVERLAP_SCRIPT, marker="OVERLAP_OK")


XSTAR_NONE_SCRIPT = textwrap.dedent("""
    import warnings
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import block_banded_spd
    from repro.core.operators import BlockBandedOp, CsrOp, DenseOp, EllOp
    from repro.core.engine import _DISTRIBUTED_STRATEGIES, solve_distributed
    from repro.launch.mesh import make_host_mesh

    # One square SPD system servable by every format (banded structure).
    prob = block_banded_spd(256, block=16, bands=1, n_rhs=2, seed=3)
    cop = CsrOp.from_dense(prob.A)
    ops = {
        "DenseOp": DenseOp(prob.A),
        "BlockBandedOp": BlockBandedOp.from_dense(prob.A, block=16, bands=1),
        "CsrOp": cop,
        "EllOp": EllOp(*cop.padded_rows()),
    }
    mesh = make_host_mesh(4)
    x0 = jnp.zeros_like(prob.x_star)
    for (action, fmt, sync) in sorted(_DISTRIBUTED_STRATEGIES):
        r = solve_distributed(ops[fmt], prob.b, x0, None, action=action,
                              sync=sync, key=jax.random.key(7), mesh=mesh,
                              rounds=3, local_steps=4)
        row = (action, fmt, sync)
        assert np.isnan(np.asarray(r.err_sq)).all(), row
        assert np.isfinite(np.asarray(r.resid)).all(), row
        assert np.isfinite(np.asarray(r.x)).all(), row
    print("XSTAR_NONE_OK")
""")


@pytest.mark.slow
def test_x_star_none_all_strategies():
    """solve_distributed(x_star=None) must work on every strategy row —
    the dense strategies dereferenced xs_full unconditionally (ISSUE 6
    satellite)."""
    run_forced_device_script(XSTAR_NONE_SCRIPT, marker="XSTAR_NONE_OK")
