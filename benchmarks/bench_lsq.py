"""RK vs CG-on-normal-equations in the low-accuracy regime (paper Sec. 7/8).

Equal-work comparison on overdetermined least squares: one RK sweep
(m row updates, O(mn) flops) vs one CG iteration on A^T A (two A matvecs,
O(mn) flops).  Reports per-sweep residual trajectories, wall time, and the
sweep count at which each solver first reaches the low-accuracy targets the
paper's regression workload needs (1e-1, 1e-2 relative residual above the
LSQ optimum).

Honest-reporting note (mirrors fig1_residual): with the fair baseline —
Jacobi-rescaled normal equations, Sec. 2.3 — CG leads at high accuracy
even on skewed designs.  RK's measured edge is the first sweeps (it
reaches the 1e-1 low-accuracy target in ~2 sweeps, before CG's spectrum
advantage compounds) plus the paper's scalability argument: an RK sweep
has ZERO global synchronization points while every CG iteration pays 2
blocking all-reduces, after an up-front A^T A formation the row-action
method never needs.

    PYTHONPATH=src python -m benchmarks.bench_lsq

A full run persists its records (wall-clock, relresid trajectories, problem
dims, P/tau) to BENCH_lsq.json at the repo root so later PRs can diff the
perf trajectory.  The ``overlap_tau`` section (``run_overlap_tau``)
records scheduled vs measured staleness for the overlapped-sync variants
and the theory quantities at both; it and ``run_precision`` run in this
process on a 4-device mesh, so on the CPU backend start with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import time

from benchmarks.common import emit, timed, worker_mesh, write_json
from repro.core import (BlockBandedOp, CsrOp, Schedule, block_banded_spd,
                        cg_solve, random_lsq, random_sparse_lsq,
                        random_sparse_spd, rk_solve,
                        solve, theory, to_unit_diagonal)
from repro.core.engine import scheduled_tau, solve_distributed
from repro.launch.mesh import make_host_mesh


def _first_at(relresid, targets, floor):
    out = {}
    for t in targets:
        hit = np.nonzero(relresid <= floor + t)[0]
        out[t] = int(hit[0]) + 1 if hit.size else 0   # 0 = never reached
    return out


def run(m: int = 4096, n: int = 512, rhs: int = 8, sweeps: int = 12,
        noise: float = 0.01, col_scale: float = 1.0, seed: int = 0):
    prob = random_lsq(m, n, n_rhs=rhs, noise=noise, col_scale=col_scale,
                      seed=seed)
    x0 = jnp.zeros_like(prob.x_star)
    bn = float(jnp.linalg.norm(prob.b))
    floor = float(jnp.linalg.norm(prob.b - prob.A @ prob.x_star)) / bn

    res = rk_solve(prob.A, prob.b, x0, prob.x_star, key=jax.random.key(1),
                   num_iters=sweeps * m, record_every=m)
    # Jacobi-rescaled normal equations (Sec. 2.3) — the strongest fair
    # version of the baseline on skewed designs.
    An, dn = to_unit_diagonal(prob.A.T @ prob.A)
    bn_eq = dn[:, None] * (prob.A.T @ prob.b)
    cg = cg_solve(An, bn_eq, x0, prob.x_star / dn[:, None], num_iters=sweeps)

    rk_r = np.linalg.norm(np.asarray(res.resid), axis=1) / bn
    # CG records the (rescaled) normal-equation residual per iteration
    # (per-iteration x is not kept); the final true residual is recomputed.
    cg_ne = np.linalg.norm(np.asarray(cg.resid), axis=1)
    cg_final = float(jnp.linalg.norm(
        prob.b - prob.A @ (dn[:, None] * cg.x))) / bn

    t_rk = timed(lambda: rk_solve(prob.A, prob.b, x0, prob.x_star,
                                  key=jax.random.key(1), num_iters=sweeps * m,
                                  record_every=m).x)
    t_cg = timed(lambda: cg_solve(An, bn_eq, x0, prob.x_star / dn[:, None],
                                  num_iters=sweeps).x)
    t_ne = timed(lambda: prob.A.T @ prob.A)   # normal-equation formation cost

    for s in range(sweeps):
        emit("bench_lsq", sweep=s + 1, rk_relresid=f"{rk_r[s]:.4e}",
             cg_ne_resid=f"{cg_ne[s]:.4e}")
    hits = _first_at(rk_r, (1e-1, 1e-2), floor)
    emit("bench_lsq", summary=1, m=m, n=n, rhs=rhs,
         kappa=f"{float(prob.kappa):.1f}", floor=f"{floor:.3e}",
         rk_final=f"{rk_r[-1]:.3e}", cg_final=f"{cg_final:.3e}",
         rk_sweeps_to_1e1=hits[1e-1], rk_sweeps_to_1e2=hits[1e-2],
         rk_s=f"{t_rk:.2f}", cg_s=f"{t_cg:.2f}", ne_form_s=f"{t_ne:.2f}",
         rk_syncs_per_sweep=0, cg_syncs_per_iter=2,
         theory_factor=f"{float(theory.rk_factor(prob.A)):.6f}")
    return {
        "m": m, "n": n, "rhs": rhs, "sweeps": sweeps,
        "kappa": float(prob.kappa), "floor": floor,
        "rk_relresid": rk_r, "cg_ne_resid": cg_ne,
        "rk_final_relresid": float(rk_r[-1]), "cg_final_relresid": cg_final,
        "rk_sweeps_to_1e1": hits[1e-1], "rk_sweeps_to_1e2": hits[1e-2],
        "rk_wall_s": t_rk, "cg_wall_s": t_cg, "ne_form_wall_s": t_ne,
        "theory_factor": float(theory.rk_factor(prob.A)),
    }


def run_banded_rk(n: int = 2048, block: int = 64, bands: int = 2,
                  rhs: int = 8, rounds: int = 40, local_steps: int = 32,
                  beta: float = 0.9, seed: int = 0, workers: int = 0):
    """Block-banded Kaczmarz through the unified distributed driver — the
    Kaczmarz action × BlockBandedOp point of the engine's action×format
    grid (ISSUE 2 acceptance).  Each step reads/writes only (2*bands+1)
    MXU-shaped tiles, so the row action keeps the paper's Θ(nnz) cost on
    the TPU-native layout; sync is the RK-style delta psum with scheduled
    staleness local_steps - 1.
    """
    prob = block_banded_spd(n, block=block, bands=bands, n_rhs=rhs, seed=seed)
    op = BlockBandedOp.from_dense(prob.A, block=block, bands=bands)
    x0 = jnp.zeros_like(prob.x_star)
    workers = workers or len(jax.devices())
    mesh = make_host_mesh(workers)
    tau = scheduled_tau(workers, local_steps, shared_stream=True)

    t0 = time.perf_counter()
    res = solve_distributed(op, prob.b, x0, prob.x_star, action="rk",
                            key=jax.random.key(1), mesh=mesh, rounds=rounds,
                            local_steps=local_steps, beta=beta)
    jax.block_until_ready(res.x)
    wall = time.perf_counter() - t0
    r = np.linalg.norm(np.asarray(res.resid), axis=1)
    bn = float(jnp.linalg.norm(prob.b))
    rel = float(jnp.linalg.norm(prob.b - prob.A @ res.x)) / bn
    emit("bench_lsq_banded_rk", n=n, block=block, bands=bands, rhs=rhs,
         workers=workers, rounds=rounds, local_steps=local_steps, tau=tau,
         beta=beta, nnz_frac=f"{op.nnz_cost() / (n * n):.4f}",
         relresid_first=f"{r[0] / bn:.3e}", relresid_last=f"{r[-1] / bn:.3e}",
         final_relresid=f"{rel:.3e}", wall_s=f"{wall:.2f}")
    return {
        "n": n, "block": block, "bands": bands, "rhs": rhs,
        "workers": workers, "rounds": rounds, "local_steps": local_steps,
        "tau": tau, "beta": beta, "nnz_frac": op.nnz_cost() / (n * n),
        "relresid_first": float(r[0] / bn), "relresid_last": float(r[-1] / bn),
        "final_relresid": rel, "wall_s": wall,
    }


def run_csr_rk(m: int = 2048, n: int = 512, row_nnz: int = 16, rhs: int = 8,
               rounds: int = 60, local_steps: int = 32, beta: float = 0.9,
               seed: int = 0, workers: int = 0):
    """General-sparse Kaczmarz through the unified distributed driver — the
    Kaczmarz action × CsrOp point (ISSUE 3 tentpole): per-worker *local*
    row sampling (each worker draws from its own slab ∝ its row norms, so
    every step is a useful update — wall-clock-faithful, unlike the global
    masked stream) with delta-psum sync; the shared-stream scheduled
    staleness applies to the round's interleaved P*local_steps stream
    (tau = workers*local_steps - 1).
    """
    prob = random_sparse_lsq(m, n, row_nnz=row_nnz, n_rhs=rhs, seed=seed)
    op = CsrOp.from_dense(prob.A)
    x0 = jnp.zeros_like(prob.x_star)
    workers = workers or len(jax.devices())
    mesh = make_host_mesh(workers)
    # local sampling: the round's interleaved shared stream has
    # workers*local_steps picks (every worker's step is useful work)
    tau = scheduled_tau(workers, local_steps, shared_stream=True,
                        local_sampling=True)

    t0 = time.perf_counter()
    res = solve_distributed(op, prob.b, x0, prob.x_star, action="rk",
                            key=jax.random.key(1), mesh=mesh, rounds=rounds,
                            local_steps=local_steps, beta=beta)
    jax.block_until_ready(res.x)
    wall = time.perf_counter() - t0
    r = np.linalg.norm(np.asarray(res.resid), axis=1)
    bn = float(jnp.linalg.norm(prob.b))
    rel = float(jnp.linalg.norm(prob.b - prob.A @ res.x)) / bn
    emit("bench_lsq_csr_rk", m=m, n=n, row_nnz=row_nnz, rhs=rhs,
         workers=workers, rounds=rounds, local_steps=local_steps, tau=tau,
         beta=beta, nnz_frac=f"{op.nnz_cost() / (m * n):.4f}",
         relresid_first=f"{r[0] / bn:.3e}", relresid_last=f"{r[-1] / bn:.3e}",
         final_relresid=f"{rel:.3e}", wall_s=f"{wall:.2f}")
    return {
        "m": m, "n": n, "row_nnz": row_nnz, "rhs": rhs,
        "workers": workers, "rounds": rounds, "local_steps": local_steps,
        "tau": tau, "beta": beta, "nnz_frac": op.nnz_cost() / (m * n),
        "relresid_first": float(r[0] / bn), "relresid_last": float(r[-1] / bn),
        "final_relresid": rel, "wall_s": wall,
    }


def run_partitioned_rk(m: int = 2048, n: int = 512, row_nnz: int = 16,
                       rhs: int = 8, rounds: int = 60, local_steps: int = 32,
                       beta: float = 0.9, skew: float = 20.0, seed: int = 0,
                       workers: int = 0):
    """Contiguous vs norm-balanced slab assignment on a skewed design
    (ISSUE 4 tentpole): the first quarter of rows is scaled by ``skew``, so
    contiguous slabs concentrate the norm mass on one worker — biasing the
    stationary row law of per-worker local sampling away from the global
    Strohmer–Vershynin distribution and skewing per-round work.  Reports
    per-slab norm mass (max/uniform) and the convergence trajectory under
    both assignments.
    """
    from repro.core import partition as pt

    base = random_sparse_lsq(m, n, row_nnz=row_nnz, n_rhs=rhs, seed=seed)
    A = np.array(base.A)
    A[: m // 4] *= skew
    rng = np.random.default_rng(seed + 1)
    xt = rng.standard_normal((n, rhs)).astype(np.float32)
    Aj = jnp.asarray(A)
    bj = jnp.asarray(A @ xt)
    op = CsrOp.from_dense(Aj)
    workers = workers or len(jax.devices())
    mesh = make_host_mesh(workers)
    # Partition quality is a property of the matrix, not of this run's
    # device count: report the slab-mass imbalance at >= 4 slabs so a
    # single-device container run still records the contrast — but only
    # when that slab count divides m (the solver only requires workers to).
    stats_slabs = max(workers, 4)
    if m % stats_slabs:
        stats_slabs = workers
    rn = np.asarray(op.row_norms_sq())
    uniform = rn.sum() / stats_slabs
    labels_bal = pt.balanced_labels(op, stats_slabs)
    rp = pt.partition_permutation(labels_bal, stats_slabs)
    labels_cont = np.arange(m) // (m // stats_slabs)
    mass = {
        "contiguous": float(
            pt.slab_norm_mass(rn, np.arange(m), stats_slabs).max()
            / uniform),
        "balanced": float(
            pt.slab_norm_mass(rn, np.asarray(rp.perm), stats_slabs).max()
            / uniform),
    }
    # Cross-slab reach: how many stored nonzeros each assignment leaves
    # outside the owner slab — the wire-volume cost the norm-balanced
    # bin-packing is free to inflate, and the quantity a future
    # reach-aware packing would minimize jointly with the norm mass.
    cross = None
    if n % stats_slabs == 0:
        total_nnz = int(op.nnz_cost())
        cross = {
            "contiguous": pt.cross_slab_edges(op, labels_cont, stats_slabs),
            "balanced": pt.cross_slab_edges(op, labels_bal, stats_slabs),
            "total_nnz": total_nnz,
        }
        emit("bench_lsq_partitioned_rk", stats_slabs=stats_slabs,
             cross_edges_contiguous=cross["contiguous"],
             cross_edges_balanced=cross["balanced"], total_nnz=total_nnz)

    out = {"m": m, "n": n, "row_nnz": row_nnz, "rhs": rhs, "skew": skew,
           "workers": workers, "stats_slabs": stats_slabs, "rounds": rounds,
           "local_steps": local_steps, "beta": beta,
           "slab_mass_max_over_uniform": mass,
           "cross_slab_edges": cross}
    x0 = jnp.zeros((n, rhs))
    bn = float(jnp.linalg.norm(bj))
    for part in ("contiguous", "balanced"):
        t0 = time.perf_counter()
        res = solve_distributed(op, bj, x0, jnp.asarray(xt), action="rk",
                                key=jax.random.key(1), mesh=mesh,
                                rounds=rounds, local_steps=local_steps,
                                beta=beta, partition=part)
        jax.block_until_ready(res.x)
        wall = time.perf_counter() - t0
        rel = float(jnp.linalg.norm(bj - Aj @ res.x)) / bn
        r = np.linalg.norm(np.asarray(res.resid), axis=1)
        emit("bench_lsq_partitioned_rk", partition=part,
             slab_mass_max_over_uniform=f"{mass[part]:.2f}",
             relresid_first=f"{r[0] / bn:.3e}",
             relresid_last=f"{r[-1] / bn:.3e}", final_relresid=f"{rel:.3e}",
             wall_s=f"{wall:.2f}")
        out[part] = {"final_relresid": rel,
                     "relresid_first": float(r[0] / bn),
                     "relresid_last": float(r[-1] / bn), "wall_s": wall}
    return out


def run_overlap_tau(n: int = 256, row_nnz: int = 8, rhs: int = 4,
                    rounds: int = 30, local_steps: int = 8,
                    beta: float = 0.9, seed: int = 2, workers: int = 4):
    """Scheduled vs measured staleness for the overlapped-sync variants:
    sparse GS / sparse RK lockstep and overlapped on a
    ``workers``-device mesh, reporting the per-round lag trace,
    the empirical tau it implies, and the theory quantities
    (``beta_opt``, ``nu_tau``) at both the scheduled bound and the
    measured staleness.  The scheduled bound must dominate the measured
    trace — that is the contract the overlap term of ``scheduled_tau``
    encodes.
    """
    P, L = workers, local_steps
    mesh = worker_mesh(P)
    prob = random_sparse_spd(n, row_nnz=row_nnz, n_rhs=rhs, seed=seed)
    op = CsrOp.from_dense(prob.A)
    x0 = jnp.zeros_like(prob.x_star)
    rho = float(theory.rho(prob.A))
    out = {"workers": P, "local_steps": L, "rounds": rounds, "rho": rho}
    for action in ("gs", "rk"):
        tau_lock = scheduled_tau(P, L, local_sampling=action == "rk")
        res = {}
        for overlap in (False, True):
            r = solve_distributed(op, prob.b, x0, prob.x_star, action=action,
                                  key=jax.random.key(1), mesh=mesh,
                                  rounds=rounds, local_steps=L, beta=beta,
                                  fused=True, overlap=overlap)
            jax.block_until_ready(r.x)
            rec = {"tau_scheduled": int(r.tau),
                   "err_first": float(r.err_sq[0].max()),
                   "err_last": float(r.err_sq[-1].max()),
                   "beta_opt": theory.beta_opt(rho, int(r.tau))}
            if overlap:
                lag = np.asarray(r.lag)
                # measured staleness: in-flight payload + lockstep interleave
                rec["lag_trace_head"] = lag[:4].tolist()
                rec["lag_steady"] = int(lag[1:].max()) if rounds > 1 else 0
                rec["tau_empirical"] = int(lag.max()) + tau_lock
                rec["bound_holds"] = rec["tau_empirical"] <= int(r.tau)
                rec["beta_opt_empirical"] = theory.beta_opt(
                    rho, rec["tau_empirical"])
                rec["nu_tau_at_beta"] = theory.nu_tau(
                    rho, rec["tau_empirical"], beta)
            res["overlap" if overlap else "lockstep"] = rec
        out[action] = res
    for action in ("gs", "rk"):
        ov, lk = out[action]["overlap"], out[action]["lockstep"]
        if not ov["bound_holds"]:
            raise RuntimeError(
                f"measured tau {ov['tau_empirical']} exceeds scheduled "
                f"bound {ov['tau_scheduled']} for {action}")
        emit("bench_lsq_overlap_tau", action=action, workers=workers,
             local_steps=local_steps, tau_lockstep=lk["tau_scheduled"],
             tau_overlap=ov["tau_scheduled"],
             tau_empirical=ov["tau_empirical"],
             lag_steady=ov["lag_steady"],
             beta_opt_scheduled=f"{ov['beta_opt']:.4f}",
             beta_opt_empirical=f"{ov['beta_opt_empirical']:.4f}",
             nu_tau_at_beta=f"{ov['nu_tau_at_beta']:.4f}",
             err_last_lockstep=f"{lk['err_last']:.3e}",
             err_last_overlap=f"{ov['err_last']:.3e}")
    return out


def run_precision(m: int = 512, n: int = 256, row_nnz: int = 6, rhs: int = 2,
                  rounds: int = 60, local_steps: int = 16, beta: float = 1.0,
                  tol: float = 0.05, seed: int = 3, workers: int = 4,
                  sweeps: int = 8):
    """The precision trade-off, measured.

    Wire: sparse-RK delta psum on a ``workers``-device mesh with
    ``compress`` ∈ {none, bf16, int8_ef} — per-mode bytes-per-round (the
    engine's analytic payload model), rounds to reach ``tol`` × the
    round-1 error, and the round inflation vs the exact f32 wire (the
    acceptance gate: int8+EF within 1.3×).  Storage: the same design
    solved sequentially with f32 vs bf16 coefficient panels, reporting
    sweeps to the low-accuracy target.  Theory: the perturbed-rate
    prediction from ``theory.iteration_inflation`` — storage rounding and
    wire quantization are RELATIVE perturbations (error proportional to
    the step, not the iterate), so the per-step contraction moves from
    ``c`` to ``c + eps*(1-c)`` and the predicted inflation stays finite.
    """
    P, L = workers, local_steps
    mesh = worker_mesh(P)
    prob = random_sparse_lsq(m, n, row_nnz=row_nnz, n_rhs=rhs, seed=seed)
    op = CsrOp.from_dense(prob.A)
    x0 = jnp.zeros_like(prob.x_star)
    out = {"workers": P, "local_steps": L, "rounds": rounds, "tol": tol}
    for compress in ("none", "bf16", "int8_ef"):
        r = solve_distributed(op, prob.b, x0, prob.x_star, action="rk",
                              key=jax.random.key(1), mesh=mesh, rounds=rounds,
                              local_steps=L, beta=beta, sync="psum",
                              compress=compress)
        err = np.asarray(r.err_sq).max(axis=1)
        hit = np.nonzero(err <= tol * err[0])[0]
        out[compress] = {
            "bytes_per_round": float(r.bytes_per_round),
            "err_first": float(err[0]), "err_last": float(err[-1]),
            "rounds_to_tol": int(hit[0]) + 1 if hit.size else 0,
        }
    base = out["none"]["rounds_to_tol"]
    if base == 0:
        raise RuntimeError(f"f32 wire never reached tol {tol}")
    for c in ("none", "bf16", "int8_ef"):
        rec = out[c]
        if rec["rounds_to_tol"] == 0:
            raise RuntimeError(f"compress={c} never reached tol {tol}")
        rec["round_inflation_vs_f32"] = rec["rounds_to_tol"] / base
        rec["bytes_to_tol"] = rec["bytes_per_round"] * rec["rounds_to_tol"]
        emit("bench_lsq_precision", compress=c,
             bytes_per_round=f"{rec['bytes_per_round']:.0f}",
             rounds_to_tol=rec["rounds_to_tol"],
             round_inflation=f"{rec['round_inflation_vs_f32']:.2f}",
             bytes_to_tol=f"{rec['bytes_to_tol']:.0f}",
             err_last=f"{rec['err_last']:.3e}")
    if out["int8_ef"]["round_inflation_vs_f32"] > 1.3:
        raise RuntimeError(
            f"int8+EF round inflation "
            f"{out['int8_ef']['round_inflation_vs_f32']:.2f} exceeds the "
            f"1.3x acceptance bound")

    # storage: f32 vs bf16 coefficient panels, sequential RK, equal work
    bn = float(jnp.linalg.norm(prob.b))
    floor = float(jnp.linalg.norm(prob.b - prob.A @ prob.x_star)) / bn
    storage = {}
    for dt in ("float32", "bfloat16"):
        r = solve(prob, key=jax.random.key(1), format="csr",
                  storage_dtype=dt,
                  schedule=Schedule(num_iters=sweeps * m, record_every=m))
        rel = np.linalg.norm(np.asarray(r.resid), axis=1) / bn
        hits = _first_at(rel, (1e-1,), floor)
        storage[dt] = {"final_relresid": float(rel[-1]),
                       "sweeps_to_1e1": hits[1e-1]}
        emit("bench_lsq_precision", storage_dtype=dt,
             final_relresid=f"{rel[-1]:.3e}", sweeps_to_1e1=hits[1e-1])
    out["storage"] = storage

    # theory: predicted inflation from the measured perturbation bounds
    f = float(theory.rk_factor(prob.A))
    A = np.asarray(prob.A)
    Ar = np.asarray(jnp.asarray(prob.A).astype(jnp.bfloat16)
                    .astype(jnp.float32))
    eps_bf16 = float(np.abs(A - Ar).max() / np.abs(A).max())
    eps_int8 = 1.0 / 254.0            # half a quantization step, relative
    c = float(np.sqrt(f))
    pred = {
        "exact_factor": f,
        "eps_bf16_storage": eps_bf16,
        "eps_int8_wire": eps_int8,
        "inflation_bf16": theory.iteration_inflation(f, eps_bf16 * (1 - c)),
        "inflation_int8": theory.iteration_inflation(f, eps_int8 * (1 - c)),
    }
    out["theory"] = pred
    emit("bench_lsq_precision", exact_factor=f"{f:.6f}",
         predicted_inflation_bf16=f"{pred['inflation_bf16']:.3f}",
         predicted_inflation_int8=f"{pred['inflation_int8']:.3f}")
    return out


if __name__ == "__main__":
    payload = {
        "lsq": run(),
        "banded_rk": run_banded_rk(),
        "csr_rk": run_csr_rk(),
        "partitioned_rk": run_partitioned_rk(),
        "overlap_tau": run_overlap_tau(),
        "precision": run_precision(),
    }
    write_json("lsq", payload)
