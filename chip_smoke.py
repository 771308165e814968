"""Chip smoke test: the solver's main path, once, on a TPU.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # the distributed phase on four chips

Every phase runs through the entry points a user calls (``solve``,
``SolverService`` + ``open_loop_load``) at the paper's reference scale the
repo can build today (n = 16384 unknowns, k = 51 right-hand sides), with
the fused Pallas sweeps forced on, and checks its answer three ways:

* against the scan engine on the same chip, run with f32-exact matmuls
  (``jax_default_matmul_precision="highest"``) so it is a sharp reference;
* against a float64 host recomputation of ``||b - A x||`` from the
  operator's own arrays (scipy), which must equal the reported residual;
* the residual went down.

Warnings are errors, so a fused-to-scan fallback or an interpret-mode
kernel fails the run.  With no TPU the script exits non-zero and prints no
result; it never falls back to the CPU.  The last line of standard output
is one JSON object: ``{"ok": true, "device": {"platform", "kind",
"count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))

#: The paper's reference scenario at the largest size the repo builds
#: today (dense host construction caps n near 2e4).
N, ROW_NNZ, K = 16384, 16, 51
SEED = 0

#: Sweeps per phase: few enough that every phase stops at a residual
#: near 1e-2..1e-1 ||b||, far above the f32 floor, so the residual checks
#: below stay sharp.
SWEEPS, BLOCK_SWEEPS = 4, 2

#: fused vs the f32-exact scan: both apply the same pick stream in f32 and
#: differ only in the order each row's <= row_cap (~50) products are
#: summed — <= 50 * 2^-24 ~ 3e-6 relative per step.  A GS sweep on an SPD
#: system is an A-norm contraction, so a step's perturbation is never
#: amplified: after 4 sweeps each coordinate carries ~4 such perturbations
#: (random-walk ~ 2 * 6e-6 ~ 1e-5 relative).  1e-4 leaves 8x room and
#: still fails any wrong row, column or write base (O(1) differences).
TOL_SCAN = 1e-4
#: banded block GS contracts 128x128 tiles on the MXU inside the kernel,
#: at Mosaic's default f32 contraction precision; if that rounds operands
#: to bf16 (2^-9 relative per product) the same contraction argument gives
#: ~sqrt(2) * 4e-3 ~ 6e-3 relative after 2 sweeps, so the bound is 5e-2 —
#: still far below the O(1) difference of a wrong tile or block row.
TOL_SCAN_MXU = 5e-2
#: reported f32 residual vs the float64 host recomputation, relative to
#: ||b||: the device forms each row of b - A x from <= 641 f32 terms
#: (banded; <= 52 for CSR/ELL) whose magnitudes sum to O(|b_i|), so its
#: rounding is ~sqrt(641) * 2^-24 ~ 1.5e-6 of ||b|| (record_metrics runs
#: its matmuls at f32 precision).  1e-4 leaves 60x room and is still 1% of
#: the residual the phases stop at, so a wrong residual fails.
TOL_HOST = 1e-4
#: the residual must at least halve from x0 = 0 (||b||) — the small-size
#: runs reach ~0.14 ||b|| after 4 coordinate sweeps.
RESID_DROP = 0.5


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def f32_matmuls(jax):
    """f32-exact matmuls process-wide (also in the service thread)."""
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", old)


def timed(fn):
    t = time.perf_counter()
    out = fn()
    import jax
    jax.block_until_ready(out)
    return out, time.perf_counter() - t


def host_matrix(op):
    """The operator's stored coefficients as a float64 scipy matrix."""
    import numpy as np
    import scipy.sparse as sp
    from repro.core.operators import BlockBandedOp, CsrOp, EllOp
    m, n = op.shape
    if isinstance(op, CsrOp):
        rows, cols = np.asarray(op.row_id), np.asarray(op.indices)
        vals = np.asarray(op.data, np.float64)
    elif isinstance(op, EllOp):
        w = op.width
        rows = np.repeat(np.arange(m), w)
        cols = np.asarray(op.cols).reshape(-1)
        vals = np.asarray(op.vals, np.float64).reshape(-1)
    elif isinstance(op, BlockBandedOp):
        Ab = np.asarray(op.A_bands, np.float64)
        nb, width, blk, _ = Ab.shape
        bi, d, i, j = np.meshgrid(np.arange(nb), np.arange(width),
                                  np.arange(blk), np.arange(blk),
                                  indexing="ij")
        cb = bi + d - op.bands
        ok = (cb >= 0) & (cb < nb)
        rows = (bi * blk + i)[ok]
        cols = (cb * blk + j)[ok]
        vals = Ab[ok]
    else:
        raise TypeError(type(op).__name__)
    return sp.csr_matrix((vals, (rows, cols)), shape=(m, n))


def host_resid(M, b, x):
    import numpy as np
    b64, x64 = np.asarray(b, np.float64), np.asarray(x, np.float64)
    return np.linalg.norm(b64 - M @ x64, axis=0)


def rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) or 1.0))


def check(name: str, cond: bool, detail: str) -> None:
    log(f"  check {name}: {'ok' if cond else 'FAILED'} ({detail})")
    if not cond:
        raise AssertionError(f"{name}: {detail}")


def check_answer(phase, M, b, x, resid, x_ref, tol_ref):
    """The three comparisons every phase makes."""
    import numpy as np
    bn = np.linalg.norm(np.asarray(b, np.float64), axis=0)
    hr = host_resid(M, b, x)
    d_ref = rel(x, x_ref)
    check(f"{phase} vs scan", d_ref <= tol_ref,
          f"rel |x - x_scan| = {d_ref:.3e} <= {tol_ref:g}")
    d_host = float(np.max(np.abs(np.asarray(resid) - hr) / bn))
    check(f"{phase} vs host f64", d_host <= TOL_HOST,
          f"max |resid - host| / ||b|| = {d_host:.3e} <= {TOL_HOST:g}")
    drop = float(np.max(hr / bn))
    check(f"{phase} residual down", drop <= RESID_DROP,
          f"max ||b - Ax|| / ||b|| = {drop:.4e} <= {RESID_DROP:g}")
    return drop


def phase_solve(jax, label, problem, fmt, num_iters, record_every, tol_ref,
                **op_kw):
    """One sequential ``solve`` with the fused sweep forced, timed twice
    (cold: compile + run; warm: run), plus the f32-exact scan reference."""
    from repro.core import Schedule, as_operator, solve
    sched = Schedule(num_iters=num_iters, record_every=record_every)
    kw = dict(key=jax.random.key(SEED), format=fmt, schedule=sched, **op_kw)
    res, cold = timed(lambda: solve(problem, fused=True, **kw))
    res, warm = timed(lambda: solve(problem, fused=True, **kw))
    with f32_matmuls(jax):
        ref, _ = timed(lambda: solve(problem, fused=False, **kw))
    op = as_operator(problem.A, fmt, **op_kw)
    log(f"phase {label}: format={fmt} A={op.shape} nnz={op.nnz_cost()} "
        f"k={problem.b.shape[1]} steps={num_iters} "
        f"compile_s={cold - warm:.3f} run_s={warm:.3f}")
    drop = check_answer(label, host_matrix(op), problem.b, res.x,
                        res.resid[-1], ref.x, tol_ref)
    log(f"phase {label}: final rel resid {drop:.4e}")
    return op


def phase_service(jax, problem):
    """(d) a SolverService on the phase (a) operator answering 8 requests
    of mixed RHS widths through open_loop_load; every request runs the
    full SWEEPS sweeps (rtol is unreachable), so fused and scan services apply
    identical streams and their answers compare directly."""
    from repro.serve import SolverService, open_loop_load
    load = dict(requests=8, rate_hz=20.0, rhs_widths=(1, 4, 13), rtol=1e-9,
                seed=SEED, timeout_s=600.0)

    def run(fused):
        with SolverService(num_iters=SWEEPS * N, record_every=N,
                           fused=fused) as svc:
            reg = svc.register("spd", problem.A, format="csr", seed=SEED)
            t = time.perf_counter()
            report = open_loop_load(svc, "spd", **load)
            return reg.op, report, time.perf_counter() - t, svc.stats

    op, rep, secs, stats = run(True)
    with f32_matmuls(jax):
        _, rep_ref, _, _ = run(False)
    log(f"phase d: service csr A={op.shape} requests={rep.requests} "
        f"widths={[b.shape[1] for b in rep.rhs]} batches={stats.batches} "
        f"chunk_launches={stats.chunk_launches} wall_s={secs:.3f} "
        f"p50_ms={rep.p50_ms:.1f} p99_ms={rep.p99_ms:.1f}")
    M = host_matrix(op)
    worst = 0.0
    for i, (b, r, rr) in enumerate(zip(rep.rhs, rep.results,
                                       rep_ref.results)):
        worst = max(worst, check_answer(f"d[{i}]", M, b, r.x, r.resid,
                                        rr.x, TOL_SCAN))
    log(f"phase d: final rel resid (worst request) {worst:.4e}")


def phase_distributed(jax, problem, chips):
    """The paper's multi-worker asynchronous solver on a ``chips``-device
    mesh: slab GS with the fused local phase vs the scan local phase."""
    from repro.core import Schedule, as_operator, solve
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(chips)
    slab = N // chips
    sched = Schedule(rounds=SWEEPS, local_steps=slab)
    kw = dict(key=jax.random.key(SEED), format="csr", mesh=mesh,
              schedule=sched)
    res, cold = timed(lambda: solve(problem, fused=True, **kw))
    res, warm = timed(lambda: solve(problem, fused=True, **kw))
    with f32_matmuls(jax):
        ref, _ = timed(lambda: solve(problem, fused=False, **kw))
    devs = {s.device for s in res.x.addressable_shards}
    log(f"phase dist: csr A=({N}, {N}) k={K} workers={chips} "
        f"rounds={sched.rounds} local_steps={slab} tau={int(res.tau)} "
        f"compile_s={cold - warm:.3f} run_s={warm:.3f}")
    check("dist shards", len(devs) == chips,
          f"x sharded over {len(devs)} distinct devices, want {chips}")
    op = as_operator(problem.A, "csr")
    drop = check_answer("dist", host_matrix(op), problem.b, res.x,
                        res.resid[-1], ref.x, TOL_SCAN)
    log(f"phase dist: final rel resid {drop:.4e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed phase, on 4 chips")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "tpu")   # never the CPU
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              "run on another backend", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    from repro.core import block_banded_spd, random_sparse_spd
    from repro.kernels.ops import interpret_default
    assert not interpret_default(), "Pallas kernels would run interpreted"
    warnings.simplefilter("error")
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    failed = []

    def run(name, fn):
        t = time.perf_counter()
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
        log(f"phase {name} wall_s={time.perf_counter() - t:.3f}"
            f"{' FAILED' if name in failed else ''}")

    t = time.perf_counter()
    spd = random_sparse_spd(N, row_nnz=ROW_NNZ, n_rhs=K, seed=SEED)
    jax.block_until_ready(spd.b)
    log(f"setup: random_sparse_spd({N}, row_nnz={ROW_NNZ}, n_rhs={K}) "
        f"in {time.perf_counter() - t:.3f}s")

    if args.chips > 1:
        run("dist", lambda: phase_distributed(jax, spd, args.chips))
    else:
        run("a", lambda: phase_solve(jax, "a", spd, "csr", SWEEPS * N, N,
                                     TOL_SCAN))
        width = int(max(1, (jax.device_get(spd.A) != 0).sum(axis=1).max()))
        run("b", lambda: phase_solve(jax, "b", spd, "ell", SWEEPS * N, N,
                                     TOL_SCAN, width=width))

        def banded():
            t0 = time.perf_counter()
            prob = block_banded_spd(N, block=128, bands=2, n_rhs=K,
                                    seed=SEED)
            jax.block_until_ready(prob.b)
            log(f"setup: block_banded_spd({N}, block=128, bands=2) in "
                f"{time.perf_counter() - t0:.3f}s")
            nb = N // 128
            phase_solve(jax, "c", prob, "banded", BLOCK_SWEEPS * nb, nb,
                        TOL_SCAN_MXU, block=128, bands=2)

        run("c", banded)
        run("d", lambda: phase_service(jax, spd))

    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
