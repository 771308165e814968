"""Least-squares launcher — the paper's Sec. 7 algorithm as a CLI, on the
unified API.

``python -m repro.launch.lsq --m 4096 --n 512 --rhs 8 --workers 8 --sweeps 6``
builds an overdetermined regression system and solves it four ways through
``repro.core.solve(problem, schedule=...)``:
(a) sequential randomized Kaczmarz on the rows of A (no normal equations),
(b) the bounded-delay asynchronous variant with the theory step size
    (``Schedule(tau=...)`` routes to the engine's ring-buffer simulator),
(c) the distributed variant (shard_map over row slabs),
(d) CG on the normal equations A^T A x = A^T b — the baseline that squares
the condition number and pays two blocking all-reduces per iteration.

Work accounting: one RK "sweep" = m row updates = O(mn) flops, the same as
one CG-on-normal-equations iteration (two A matvecs), so per-sweep residual
comparisons are equal-work comparisons.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import (Schedule, cg_solve, random_lsq, random_sparse_lsq,
                        solve, theory, to_unit_diagonal)
from repro.core.engine import (COMPRESS_MODES, PARTITIONS, scheduled_tau,
                               supported_syncs)
from repro.core.operators import STORAGE_DTYPES
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.solve import add_fused_flag

#: operator class names this CLI can build (--format dense/csr); the
#: --rk-sync choices are derived from the dispatch table narrowed to these
_CLI_FORMATS = ("DenseOp", "CsrOp")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=4096)
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--rhs", type=int, default=8)
    ap.add_argument("--noise", type=float, default=0.01)
    ap.add_argument("--col-scale", type=float, default=0.5,
                    help="exponential column-scale skew (0 = isotropic)")
    ap.add_argument("--format", choices=("dense", "csr"), default="dense",
                    help="operator format; csr additionally switches the "
                         "design to the sparse reference scenario and the "
                         "distributed pass to per-worker local sampling")
    ap.add_argument("--row-nnz", type=int, default=16,
                    help="nonzeros per row of the sparse design "
                         "(--format csr)")
    ap.add_argument("--sweeps", type=int, default=6)
    ap.add_argument("--tau", type=int, default=32,
                    help="delay bound for the async simulator")
    ap.add_argument("--rk-sync",
                    choices=("auto", *supported_syncs("rk", _CLI_FORMATS)),
                    default="auto",
                    help="distributed RK delta sync: a2a = two-phase "
                         "exchange over the column-slab neighbor graph "
                         "(csr format; bitwise-identical to psum, falls "
                         "back when the graph is dense)")
    ap.add_argument("--partition", choices=PARTITIONS,
                    default="contiguous",
                    help="distributed slab assignment: 'balanced' bin-packs "
                         "rows by norm mass and nnz into the P slabs via a "
                         "row permutation (csr format), restoring the "
                         "global Strohmer-Vershynin row law under "
                         "per-worker local sampling")
    add_fused_flag(ap, "csr format: the whole record chunk in one "
                       "launch, iterate VMEM-resident")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffered delta sync for the distributed "
                         "pass: install round r-1's deltas while sweeping "
                         "round r (csr format; dense falls back to lockstep "
                         "with a warning), at the cost of one extra round "
                         "of scheduled staleness")
    ap.add_argument("--storage-dtype", choices=STORAGE_DTYPES,
                    default=None,
                    help="precision the operator's coefficients are stored "
                         "in (row norms, iterate and accumulation stay "
                         "f32); default keeps the input dtype bitwise")
    ap.add_argument("--compress", choices=COMPRESS_MODES,
                    default="none",
                    help="wire format of the distributed RK delta sync "
                         "(csr format, psum wire; a2a falls back to psum "
                         "with a warning under compression)")
    ap.add_argument("--workers", type=int, default=0,
                    help="0 = all local devices")
    ap.add_argument("--local-steps", type=int, default=0,
                    help="updates between synchronizations (0 -> m/workers)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.format != "csr":
        if args.rk_sync == "a2a":
            ap.error("--rk-sync a2a needs --format csr")
        if args.partition == "balanced":
            ap.error("--partition balanced needs --format csr")
        if args.compress != "none":
            ap.error("--compress needs --format csr (the dense delta psum "
                     "has no compressed wire)")

    if args.format == "csr":
        prob = random_sparse_lsq(args.m, args.n, row_nnz=args.row_nnz,
                                 n_rhs=args.rhs, noise=args.noise,
                                 seed=args.seed)
    else:
        prob = random_lsq(args.m, args.n, n_rhs=args.rhs, noise=args.noise,
                          col_scale=args.col_scale, seed=args.seed)
    m, n = prob.shape
    bn = float(jnp.linalg.norm(prob.b))
    # residual at the LSQ optimum: the floor every solver is chasing
    floor = float(jnp.linalg.norm(prob.b - prob.A @ prob.x_star)) / bn
    print(f"[lsq] m={m} n={n} rhs={args.rhs} kappa(A)={float(prob.kappa):.1f} "
          f"kappa(A^T A)={float(prob.kappa)**2:.1f} optimum relresid={floor:.3e}")

    iters = args.sweeps * m
    t0 = time.time()
    res = solve(prob, key=jax.random.key(1), format=args.format,
                storage_dtype=args.storage_dtype,
                schedule=Schedule(num_iters=iters, record_every=m,
                                  fused=args.fused))
    jax.block_until_ready(res.x)
    print(f"  seq RK     : {args.sweeps} sweeps, fused={args.fused} "
          f"relresid {float(jnp.linalg.norm(res.resid[-1]))/bn:.3e} "
          f"({time.time()-t0:.1f}s)")

    rho_rk = float(theory.rk_rho(prob.A))
    beta = theory.beta_opt_rk(rho_rk, args.tau)
    t0 = time.time()
    ares = solve(prob, key=jax.random.key(1), delay_key=jax.random.key(2),
                 beta=beta, format=args.format,
                 storage_dtype=args.storage_dtype,
                 schedule=Schedule(num_iters=iters, tau=args.tau,
                                   record_every=m))
    jax.block_until_ready(ares.x)
    print(f"  async RK   : tau={args.tau} beta~={beta:.3f} relresid "
          f"{float(jnp.linalg.norm(ares.resid[-1]))/bn:.3e} "
          f"({time.time()-t0:.1f}s)")

    workers = args.workers or len(jax.devices())
    mesh = make_host_mesh(workers)
    local_steps = args.local_steps or max(1, m // workers)
    # csr runs per-worker local sampling: every worker's step is a useful
    # update, so a round applies workers*local_steps row actions (the
    # equal-work accounting) and the staleness bound follows suit.
    local_sampling = args.format == "csr"
    upd_per_round = local_steps * (workers if local_sampling else 1)
    rounds = max(1, iters // upd_per_round)
    ptau = scheduled_tau(workers, local_steps, shared_stream=True,
                         local_sampling=local_sampling,
                         overlap=args.overlap)
    pbeta = theory.beta_opt_rk(rho_rk, ptau)
    t0 = time.time()
    pres = solve(prob, key=jax.random.key(1), mesh=mesh, beta=pbeta,
                 format=args.format, sync=args.rk_sync,
                 storage_dtype=args.storage_dtype,
                 schedule=Schedule(rounds=rounds, local_steps=local_steps,
                                   partition=args.partition,
                                   fused=args.fused, overlap=args.overlap,
                                   compress=args.compress))
    jax.block_until_ready(pres.x)
    sampling = "local" if args.format == "csr" else "global-stream"
    print(f"  par RK     : P={workers} tau={ptau} beta~={pbeta:.3f} "
          f"sampling={sampling} sync={args.rk_sync} "
          f"partition={args.partition} overlap={args.overlap} "
          f"compress={args.compress} "
          f"({pres.bytes_per_round:.0f} B/round) "
          f"{rounds} rounds, relresid "
          f"{float(jnp.linalg.norm(pres.resid[-1]))/bn:.3e} "
          f"({time.time()-t0:.1f}s)")
    if pres.lag is not None:
        lag = jnp.asarray(pres.lag)
        tau_lock = scheduled_tau(workers, local_steps, shared_stream=True,
                                 local_sampling=local_sampling)
        print(f"  staleness  : measured lag max={int(lag.max())} "
              f"(round 1: {int(lag[0])}) -> empirical tau "
              f"{int(lag.max()) + tau_lock} <= scheduled bound {ptau}")

    # Baseline: CG on the Jacobi-rescaled normal equations (Sec. 2.3) —
    # kappa is still squared relative to A, and each iteration pays two
    # blocking all-reduces.
    x0 = jnp.zeros_like(prob.x_star)
    An, dn = to_unit_diagonal(prob.A.T @ prob.A)
    bn_eq = dn[:, None] * (prob.A.T @ prob.b)
    t0 = time.time()
    cres = cg_solve(An, bn_eq, x0, prob.x_star / dn[:, None],
                    num_iters=args.sweeps)
    jax.block_until_ready(cres.x)
    x_cg = dn[:, None] * cres.x
    print(f"  CG (A^T A) : {args.sweeps} iters, relresid "
          f"{float(jnp.linalg.norm(prob.b - prob.A @ x_cg))/bn:.3e} "
          f"({time.time()-t0:.1f}s)")

    f_sync = float(theory.rk_factor(prob.A))
    f_async = float(theory.async_rk_factor(prob.A, args.tau, beta,
                                           rho_rk=rho_rk))
    print(f"  theory     : rho_rk={rho_rk:.4f} per-iter factor "
          f"sync={f_sync:.6f} async(tau={args.tau})={f_async:.6f}")


if __name__ == "__main__":
    main()
