"""Solver launcher — the paper's algorithm as a CLI, on the unified API.

``python -m repro.launch.solve --n 4096 --rhs 8 --workers 8 --sweeps 10``
builds a reference-scenario SPD system and solves it through
``repro.core.solve(problem, format=..., schedule=...)``:
(a) sequential randomized Gauss-Seidel, (b) the distributed asynchronous
variant (shard_map over a worker mesh), (c) CG — printing residual
trajectories, the paper's theoretical rate factors, and the chosen step
size beta~.  ``--format ell`` runs the sequential pass through the ELL
operator (Θ(nnz) row reads) instead of dense rows.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import (Schedule, cg_solve, random_sparse_spd, solve, theory)
from repro.core.engine import (COMPRESS_MODES, PARTITIONS, scheduled_tau,
                               supported_syncs)
from repro.core.operators import STORAGE_DTYPES
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh

#: operator class names this CLI can build (--format dense/ell/csr); the
#: --sync choices are derived from the dispatch table narrowed to these
_CLI_FORMATS = ("DenseOp", "EllOp", "CsrOp")

#: the --format flag values, shared with the serve launcher so the two
#: CLIs' choices cannot drift
FORMAT_CHOICES = ("dense", "ell", "csr")


def add_fused_flag(ap: argparse.ArgumentParser, detail: str) -> None:
    """The tri-state ``--fused`` flag every launcher shares: absent ->
    False (the scan engine, today's default), bare ``--fused`` -> True
    (forced, an error where no fused kernel exists), ``--fused auto`` ->
    the tuning table's measured fused-vs-scan winner per strategy row
    (``repro.tune``; missing entries run the scan, silently)."""

    def value(s: str):
        if s != "auto":
            raise argparse.ArgumentTypeError(
                f"--fused takes no value or 'auto' (got {s!r})")
        return s

    ap.add_argument("--fused", nargs="?", const=True, default=False,
                    type=value, metavar="auto",
                    help="run inner loops as fused Pallas sweep kernels "
                         f"({detail}); bare --fused forces it (an error "
                         "where no sweep kernel exists), '--fused auto' "
                         "runs the tuning table's measured winner per "
                         "strategy row")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--rhs", type=int, default=8)
    ap.add_argument("--row-nnz", type=int, default=16)
    ap.add_argument("--offdiag", type=float, default=0.9)
    ap.add_argument("--sweeps", type=int, default=10)
    ap.add_argument("--format", choices=FORMAT_CHOICES,
                    default="dense",
                    help="operator format (sequential AND distributed)")
    ap.add_argument("--ell-width", type=int, default=64)
    ap.add_argument("--sync",
                    choices=("auto", *supported_syncs("gs", _CLI_FORMATS)),
                    default="auto",
                    help="distributed sync strategy (a2a = sparsity-derived "
                         "neighbor all-to-all, CSR/ELL formats; the halo "
                         "strategy belongs to the banded format, which this "
                         "CLI does not build)")
    ap.add_argument("--partition", choices=PARTITIONS,
                    default="contiguous",
                    help="distributed slab assignment: 'balanced' bin-packs "
                         "rows by norm mass and nnz into the P slabs via a "
                         "symmetric row permutation (CSR/ELL formats)")
    add_fused_flag(ap, "iterate VMEM-resident, picks scalar-prefetched, "
                       "where the action x format has one")
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffered sync: each round installs the "
                         "PREVIOUS round's exchange while sweeping, hiding "
                         "sync latency behind local work at the cost of one "
                         "extra round of scheduled staleness (sparse/halo "
                         "strategies; others fall back to lockstep with a "
                         "warning)")
    ap.add_argument("--storage-dtype", choices=STORAGE_DTYPES,
                    default=None,
                    help="precision the operator's coefficients are stored "
                         "in (row norms, iterate and accumulation stay "
                         "f32); default keeps the input dtype bitwise")
    ap.add_argument("--compress", choices=COMPRESS_MODES,
                    default="none",
                    help="wire format of the distributed sync payload; the "
                         "GS allgather/a2a exchanges are bitwise-pinned and "
                         "have no compressed wire, so a non-'none' value "
                         "falls back to the exact exchange with a warning "
                         "(the knob compresses the sparse-RK delta psum and "
                         "banded halo strategies)")
    ap.add_argument("--workers", type=int, default=0,
                    help="0 = all local devices")
    ap.add_argument("--local-steps", type=int, default=0,
                    help="async steps between synchronizations "
                         "(0 -> one sweep split evenly)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.sync == "a2a" and args.format == "dense":
        ap.error("--sync a2a needs a sparse format (--format csr or ell)")
    if args.partition == "balanced" and args.format == "dense":
        ap.error("--partition balanced needs a sparse format "
                 "(--format csr or ell)")

    prob = random_sparse_spd(args.n, row_nnz=args.row_nnz,
                             offdiag=args.offdiag, n_rhs=args.rhs,
                             seed=args.seed)
    if args.format == "ell":
        # ell_from_dense keeps only the width largest entries per row — a
        # too-small width silently solves a truncated system.  Widen to the
        # true max row occupancy so the ELL operator is exact.
        max_nnz = int((jnp.abs(prob.A) > 0).sum(axis=1).max())
        if args.ell_width < max_nnz:
            print(f"  [warn] --ell-width {args.ell_width} < max nnz/row "
                  f"{max_nnz}; widening to keep the operator exact")
            args.ell_width = max_nnz
    x0 = jnp.zeros_like(prob.x_star)
    rho = float(theory.rho(prob.A))
    n = prob.n
    print(f"[solve] n={n} rhs={args.rhs} kappa={float(prob.kappa):.1f} "
          f"rho={rho:.4f} format={args.format}")

    iters = args.sweeps * n
    t0 = time.time()
    res = solve(prob, key=jax.random.key(1), format=args.format,
                width=args.ell_width, storage_dtype=args.storage_dtype,
                schedule=Schedule(num_iters=iters, record_every=n,
                                  fused=args.fused))
    jax.block_until_ready(res.x)
    print(f"  sync RGS   : {args.sweeps} sweeps, fused={args.fused} "
          f"resid {float(res.resid[-1,0]):.3e} "
          f"({time.time()-t0:.1f}s)")

    workers = args.workers or len(jax.devices())
    mesh = make_host_mesh(workers)
    local_steps = args.local_steps or max(1, n // workers)
    tau = scheduled_tau(workers, local_steps, overlap=args.overlap)
    beta = theory.beta_opt(rho, tau)
    rounds = max(1, iters // (workers * local_steps))
    t0 = time.time()
    pres = solve(prob, key=jax.random.key(2), mesh=mesh, beta=beta,
                 format=args.format, width=args.ell_width, sync=args.sync,
                 storage_dtype=args.storage_dtype,
                 schedule=Schedule(rounds=rounds, local_steps=local_steps,
                                   partition=args.partition,
                                   fused=args.fused, overlap=args.overlap,
                                   compress=args.compress))
    jax.block_until_ready(pres.x)
    bpr = ("" if pres.bytes_per_round is None
           else f"({pres.bytes_per_round:.0f} B/round) ")
    print(f"  async RGS  : P={workers} tau={tau} beta~={beta:.3f} "
          f"format={args.format} sync={args.sync} "
          f"partition={args.partition} overlap={args.overlap} "
          f"compress={args.compress} {bpr}"
          f"{rounds} rounds, resid {float(pres.resid[-1,0]):.3e} "
          f"({time.time()-t0:.1f}s)")
    if pres.lag is not None:
        lag = jnp.asarray(pres.lag)
        tau_emp = int(lag.max()) + scheduled_tau(workers, local_steps)
        print(f"  staleness  : measured lag max={int(lag.max())} "
              f"(round 1: {int(lag[0])}) -> empirical tau {tau_emp} "
              f"<= scheduled bound {tau}")

    t0 = time.time()
    cres = cg_solve(prob.A, prob.b, x0, prob.x_star,
                    num_iters=args.sweeps)
    jax.block_until_ready(cres.x)
    print(f"  CG         : {args.sweeps} iters, resid {float(cres.resid[-1,0]):.3e} "
          f"({time.time()-t0:.1f}s)")
    nu = theory.nu_tau(rho, tau, beta)
    print(f"  theory     : nu_tau(beta~)={nu:.3f} "
          f"epoch factor <= {theory.thm41a_factor(rho, tau, float(prob.kappa), beta):.5f}")


if __name__ == "__main__":
    main()
