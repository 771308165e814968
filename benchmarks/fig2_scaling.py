"""Paper Figure 2 analogue: scaling of Asynchronous Randomized Gauss-Seidel
with worker count (10 sweeps wall time, speedup vs 1 worker), against CG.

All worker counts run in this process, each on a mesh over its first P
devices (a chip belongs to one process, so no point starts a child); a
count above the devices this process holds fails.  On the CPU backend
start with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, where
the devices share the host's cores, so *wall-clock* speedups are not
observable — we report the per-worker iteration counts and the
communication rounds (the quantities that scale), plus wall time for
completeness."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, worker_mesh
from repro.core import parallel_rgs_solve, random_sparse_spd, theory


def _point(prob, P: int, sweeps: int) -> dict:
    n = prob.A.shape[0]
    mesh = worker_mesh(P)
    local = n // P
    rho = float(theory.rho(prob.A))
    tau = (P - 1) * local
    beta = theory.beta_opt(rho, tau)
    x0 = jnp.zeros_like(prob.x_star)
    kw = dict(key=jax.random.key(0), mesh=mesh, local_steps=local, beta=beta)
    # warmup (compile)
    jax.block_until_ready(parallel_rgs_solve(
        prob.A, prob.b, x0, prob.x_star, rounds=1, **kw).x)
    t0 = time.time()
    r = parallel_rgs_solve(prob.A, prob.b, x0, prob.x_star, rounds=sweeps,
                           **kw)
    jax.block_until_ready(r.x)
    dt = time.time() - t0
    resid = float(jnp.linalg.norm(r.resid[-1]) / jnp.linalg.norm(prob.b))
    return dict(P=P, tau=tau, beta=beta, wall_s=dt, resid=resid,
                iters_per_worker=sweeps * local, sync_rounds=sweeps)


def run(n: int = 1024, sweeps: int = 10, workers=(1, 2, 4, 8)):
    prob = random_sparse_spd(n, row_nnz=16, offdiag=0.95, n_rhs=4, seed=0)
    results = []
    for P in workers:
        rec = _point(prob, P, sweeps)
        results.append(rec)
        emit("fig2_scaling", P=rec["P"], tau=rec["tau"],
             beta=f"{rec['beta']:.3f}", wall_s=f"{rec['wall_s']:.2f}",
             resid_10sweeps=f"{rec['resid']:.3e}",
             iters_per_worker=rec["iters_per_worker"],
             sync_rounds=rec["sync_rounds"])
    base = results[0]
    for rec in results:
        emit("fig2_scaling_derived", P=rec["P"],
             work_speedup=f"{base['iters_per_worker']/rec['iters_per_worker']:.2f}",
             resid_ratio_vs_P1=f"{rec['resid']/max(base['resid'],1e-30):.2f}")
    return results


if __name__ == "__main__":
    run()
