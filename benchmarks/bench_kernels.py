"""Kernel-layer microbenchmarks.

NOTE: on the CPU backend Pallas runs in interpret mode, so wall times there
are NOT TPU predictions — a device time comes only from a run on the chip.
What this benchmark DOES establish on CPU:

* the blocked layout's arithmetic-intensity advantage, reported as modeled
  FLOPs-per-HBM-byte for bbmv (contiguous block-banded) vs spmv_ell
  (gather-based ELL) at equal nnz — the hardware-adaptation argument of
  DESIGN.md quantified structurally;
* the CSR matvec overhaul (PR 5): the sliced-ELL gather-accumulate kernel
  (``csr_sliced``, the ``CsrOp.matvec`` default) vs the retired one-hot
  segment-sum layout (``csr_segsum``), plus both on a half-empty matrix
  where the prefetch-predicated variant skips empty panels
  (``csr_skip_empty``);
* fused sweep kernels vs the per-step scan engine (the ``sweeps``
  section): whole GS/RK inner loops in one Pallas launch for the
  banded/CSR/ELL formats, parity-checked against the scan iterates;
* correctness spot checks: every layout row carries a ``check`` value
  (max abs deviation from the dense oracle) so a wrong kernel cannot hide
  behind a fast wall time.

Timing is min-of-``--repeats`` (one-sided noise), so BENCH_kernels.json
deltas between PRs are trustworthy.

    PYTHONPATH=src python -m benchmarks.bench_kernels [--n 1024] [--repeats 3]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed, write_json
from repro.core import (BlockBandedOp, CsrOp, EllOp, block_banded_spd,
                        random_sparse_spd)
from repro.core.engine import solve_sequential
from repro.kernels import ops, ref


def run(n: int = 1024, block: int = 128, bands: int = 1, k: int = 64,
        repeats: int = 3, storage_dtype=None, tuned: bool = False):
    prob = block_banded_spd(n, block=block, bands=bands, n_rhs=k, seed=0)
    bop = BlockBandedOp.from_dense(prob.A, block=block, bands=bands,
                                   storage_dtype=storage_dtype)
    width = int((np.asarray(prob.A) != 0).sum(1).max())
    width = -(-width // 8) * 8
    eop = EllOp.from_dense(prob.A, width=width, storage_dtype=storage_dtype)
    cop = CsrOp.from_dense(prob.A, storage_dtype=storage_dtype)
    # oracle convention (tests/test_operators.py): low-precision storage is
    # checked against the ROUNDED dense matrix, so `check` stays at kernel
    # roundoff for every storage dtype.
    A_ref = (prob.A if storage_dtype is None
             else prob.A.astype(storage_dtype).astype(jnp.float32))
    y_d = A_ref @ prob.x_star

    # Modeled arithmetic intensity on the A-stream (FLOPs per byte of matrix
    # read): blocked tiles amortize k RHS columns per element; ELL/CSR pay
    # the same matrix bytes plus a gathered row of x per nonzero
    # (uncoalesced).  csr_segsum additionally streams a row id per slot and
    # burns a dense one-hot MXU matmul per panel; csr_sliced (the matvec
    # default since PR 5) drops both — per-row windows make the segment sum
    # free — at the cost of per-row (not per-panel) padding.
    # Byte models are derived from the dtypes actually stored, so
    # ``--storage-dtype bfloat16`` (2-byte values, int16 gather indices)
    # shows up directly in the modeled AI; the iterate/RHS stream stays f32.
    ev, ec = eop.vals.dtype.itemsize, eop.cols.dtype.itemsize
    cv, ci = cop.data.dtype.itemsize, cop.indices.dtype.itemsize
    bbmv_bytes = bop.nnz_cost() * bop.A_bands.dtype.itemsize
    bbmv_flops = 2 * bop.nnz_cost() * k
    ell_bytes = eop.nnz_cost() * (ev + ec) + eop.nnz_cost() * k * 4
    ell_flops = 2 * eop.nnz_cost() * k
    csr_slots = cop.panel_width * (-(-n // cop.rows_per_panel))
    csr_bytes = csr_slots * (cv + ci + 4) + csr_slots * k * 4
    csr_flops = 2 * cop.nnz_cost() * k
    sl_slots = int(np.prod(cop.sliced_rows()[0].shape))
    sliced_bytes = sl_slots * (cv + ci) + sl_slots * k * 4
    sliced_flops = 2 * cop.nnz_cost() * k

    # Empty-panel-skip variant (scalar-prefetched per-panel nnz counts):
    # on a "patchy" matrix — half the row panels zeroed, the shape a
    # norm-balanced partition of a banded-structure matrix produces — the
    # predicated grid skips the gather + contraction of every empty panel,
    # so its modeled A-stream bytes shrink by the empty fraction.
    A_patchy = np.array(prob.A)
    R = cop.rows_per_panel
    for p in range(0, n // R, 2):
        A_patchy[p * R:(p + 1) * R] = 0.0
    Ap = jnp.asarray(A_patchy)
    pop = CsrOp.from_dense(Ap, storage_dtype=storage_dtype)
    pn = np.asarray(pop.panel_nnz())
    empty_frac = float((pn == 0).mean())
    x_p = prob.x_star
    y_p = (Ap if storage_dtype is None
           else Ap.astype(storage_dtype).astype(jnp.float32)) @ x_p
    pv, pi = pop.data.dtype.itemsize, pop.indices.dtype.itemsize
    patchy_slots = pop.panel_width * pn.size
    patchy_bytes = patchy_slots * (pv + pi + 4) + patchy_slots * k * 4
    patchy_flops = 2 * pop.nnz_cost() * k
    skip_slots = (int(pop.sliced_rows()[0].shape[1]) * pop.rows_per_panel
                  * int((pn > 0).sum()))
    skip_bytes = (skip_slots * (pv + pi) + skip_slots * k * 4 + pn.size * 4)
    skip_flops = 2 * pop.nnz_cost() * k

    # Every layout row: modeled AI and byte/FLOP counts, min-of-N wall
    # time, and a check value against the dense oracle (uniform — a
    # fast-but-wrong kernel fails loudly here and in the CI smoke job).
    layouts = {}
    for name, nbytes, flops, want, fn in (
        ("block_banded", bbmv_bytes, bbmv_flops, y_d,
         lambda: bop.matvec(prob.x_star)),
        ("ell_gather", ell_bytes, ell_flops, y_d,
         lambda: eop.matvec(prob.x_star)),
        ("csr_segsum", csr_bytes, csr_flops, y_d,
         lambda: cop.matvec_segsum(prob.x_star)),
        ("csr_sliced", sliced_bytes, sliced_flops, y_d,
         lambda: cop.matvec(prob.x_star, skip_empty=False)),
        ("csr_segsum_patchy", patchy_bytes, patchy_flops, y_p,
         lambda: pop.matvec_segsum(x_p)),
        ("csr_skip_empty", skip_bytes, skip_flops, y_p,
         lambda: pop.matvec(x_p, skip_empty=True)),
    ):
        ai = flops / nbytes
        check = float(jnp.abs(fn() - want).max())
        wall = timed(fn, iters=repeats, stat="min")
        emit("bench_kernels", layout=name, ai_flops_per_byte=f"{ai:.1f}",
             wall_us=f"{wall*1e6:.0f}", check=f"{check:.2e}")
        layouts[name] = {"ai_flops_per_byte": ai, "wall_us": wall * 1e6,
                         "model_bytes": int(nbytes), "model_flops": int(flops),
                         "check": check}
    layouts["csr_skip_empty"]["empty_panel_frac"] = empty_frac
    emit("bench_kernels", empty_panel_frac=f"{empty_frac:.2f}")
    tuned_rows = (run_tuned(layouts, cop, pop, prob.x_star, x_p,
                            repeats=repeats)
                  if tuned else None)

    # fused block-GS sweep kernel vs oracle (dense layout)
    nb = bop.nb
    blocks = jax.random.randint(jax.random.key(1), (nb,), 0, nb)
    x0 = jnp.zeros_like(prob.b)
    out = ops.block_gs_sweep(prob.A, prob.b, x0, blocks, block=block, beta=1.0)
    want = ref.block_gs_sweep_ref(prob.A, prob.b, x0, blocks, block=block,
                                  beta=1.0)
    check_block_gs = float(jnp.abs(out - want).max())
    sweep_wall = timed(lambda: ops.block_gs_sweep(prob.A, prob.b, x0, blocks,
                                                  block=block),
                       iters=repeats, stat="min")
    emit("bench_kernels", check_block_gs=f"{check_block_gs:.2e}",
         sweep_wall_us=f"{sweep_wall*1e6:.0f}")
    payload = {
        "n": n, "block": block, "bands": bands, "k": k, "repeats": repeats,
        "storage_dtype": storage_dtype,
        "check_block_gs": check_block_gs,
        "layouts": layouts, "sweep_wall_us": sweep_wall * 1e6,
        "sweeps": run_sweeps(repeats=repeats, n=min(n, 512)),
        "precision": run_precision(repeats=repeats, n=min(n, 512)),
    }
    if tuned_rows is not None:
        payload["tuned"] = tuned_rows
    return payload


#: layout row -> the variant family its operator's tuned dispatch chooses
#: among (None = the entry point has a single pinned kernel, so the tuned
#: path IS the default)
_TUNED_FAMILIES = {
    "csr_segsum": "csr_dense_panels",
    "csr_sliced": "csr_dense_panels",
    "csr_segsum_patchy": "csr_patchy",
    "csr_skip_empty": "csr_patchy",
    "block_banded": None,
    "ell_gather": None,
}


def run_tuned(layouts, cop, pop, x_d, x_p, *, repeats: int = 3):
    """The ``--tuned`` section: time the table-driven dispatch against the
    best hardcoded default on every recorded layout row.

    The tuned path is the operator's bare ``matvec`` — whatever variant
    the active ``TUNE_<backend>.json`` picks for this shape bucket — and
    ``best_default_us`` is the fastest forced-variant row of the same
    operator (for single-variant rows the tuned path is trivially the
    default).  ``ok`` grants a 1.25x noise slack: the tuned path launches
    one of the measured variants, so equality up to timer noise is the
    expected outcome and a miss means the table picked a loser."""
    from repro.tune import runtime as tune_runtime
    table = tune_runtime.active_table()
    tuned_fns = {"csr_dense_panels": (cop, x_d), "csr_patchy": (pop, x_p)}
    family_best = {
        fam: min(layouts[r]["wall_us"] for r, f in _TUNED_FAMILIES.items()
                 if f == fam)
        for fam in tuned_fns}
    out = {"table_loaded": table is not None,
           "table_backend": getattr(table, "backend", None)}
    for name, fam in _TUNED_FAMILIES.items():
        if fam is None:
            op_wall = layouts[name]["wall_us"]
            row = {"tuned_us": op_wall, "best_default_us": op_wall,
                   "variant": "single", "ok": True}
        else:
            op, x = tuned_fns[fam]
            wall = timed(lambda: op.matvec(x), iters=repeats,
                         stat="min") * 1e6
            best = family_best[fam]
            row = {"tuned_us": wall, "best_default_us": best,
                   "variant": tune_runtime.matvec_variant(op) or "(auto)",
                   "ok": wall <= best * 1.25}
        out[name] = row
        emit("bench_kernels_tuned", layout=name,
             tuned_us=f"{row['tuned_us']:.0f}",
             best_default_us=f"{row['best_default_us']:.0f}",
             variant=row["variant"], ok=row["ok"])
    return out


def run_precision(n: int = 512, k: int = 8, row_nnz: int = 16,
                  steps: int = 256, repeats: int = 3, seed: int = 0):
    """Per-dtype bytes-per-iteration rows for the CSR/ELL matvec + sweep.

    The quantity ``storage_dtype`` controls is the coefficient stream the
    kernels read each iteration — values plus gather indices (bf16 storage
    also narrows ELL/CSR column indices to int16 when ``n`` fits), while the
    iterate, RHS and accumulation stay f32.  For each format the row records
    the modeled matvec A-stream bytes, the per-row sweep-step bytes, the
    measured wall time, and a check against the ROUNDED dense oracle; the
    bf16 row adds the reduction vs f32 (the acceptance number: >= 40%).
    """
    prob = random_sparse_spd(n, row_nnz=row_nnz, n_rhs=k, seed=seed)
    width = int((np.asarray(prob.A) != 0).sum(1).max())
    makers = {
        "csr": lambda dt: CsrOp.from_dense(prob.A, storage_dtype=dt),
        "ell": lambda dt: EllOp.from_dense(prob.A, width=width,
                                           storage_dtype=dt),
    }
    out = {"n": n, "k": k, "row_nnz": row_nnz, "steps": steps}
    for fmt, make in makers.items():
        rows = {}
        for dt in ("float32", "bfloat16"):
            op = make(dt)
            if fmt == "csr":
                vsz = op.data.dtype.itemsize
                isz = op.indices.dtype.itemsize
                slots = int(np.prod(op.sliced_rows()[0].shape))
                row_slots = op.row_cap
            else:
                vsz = op.vals.dtype.itemsize
                isz = op.cols.dtype.itemsize
                slots = int(op.nnz_cost())
                row_slots = op.vals.shape[1]
            matvec_bytes = slots * (vsz + isz)
            sweep_step_bytes = row_slots * (vsz + isz)
            A_ref = prob.A.astype(dt).astype(jnp.float32)
            y_ref = A_ref @ prob.x_star
            check = float(jnp.abs(op.matvec(prob.x_star) - y_ref).max())
            mv_wall = timed(lambda: op.matvec(prob.x_star),
                            iters=repeats, stat="min")
            x0 = jnp.zeros_like(prob.b)
            sweep_wall = timed(
                lambda: solve_sequential(op, prob.b, x0, prob.x_star,
                                         action="gs", key=jax.random.key(2),
                                         num_iters=steps, record_every=steps,
                                         fused=True).x,
                iters=repeats, stat="min")
            rows[dt] = {"matvec_bytes": matvec_bytes,
                        "sweep_step_bytes": sweep_step_bytes,
                        "matvec_wall_us": mv_wall * 1e6,
                        "sweep_wall_us": sweep_wall * 1e6,
                        "vals_dtype": str(op.data.dtype if fmt == "csr"
                                          else op.vals.dtype),
                        "idx_dtype": str(op.indices.dtype if fmt == "csr"
                                         else op.cols.dtype),
                        "check": check}
            emit("bench_kernels_precision", fmt=fmt, dtype=dt,
                 matvec_bytes=matvec_bytes,
                 sweep_step_bytes=sweep_step_bytes,
                 matvec_us=f"{mv_wall*1e6:.0f}",
                 sweep_us=f"{sweep_wall*1e6:.0f}", check=f"{check:.2e}")
        f32, bf16 = rows["float32"], rows["bfloat16"]
        for key_ in ("matvec_bytes", "sweep_step_bytes"):
            red = 1.0 - bf16[key_] / f32[key_]
            bf16[f"{key_}_reduction_vs_f32"] = red
            emit("bench_kernels_precision", fmt=fmt,
                 **{f"{key_}_reduction": f"{red:.2f}"})
        out[fmt] = rows
    return out


def run_sweeps(n: int = 512, block: int = 64, bands: int = 1, k: int = 8,
               row_nnz: int = 16, steps: int = 256, repeats: int = 3,
               seed: int = 0):
    """Fused sweep kernels vs the per-step scan engine (PR 5 tentpole).

    Times one full inner loop (``steps`` sequential row/block updates +
    one metric record) through ``solve_sequential`` both ways for the
    banded GS action and the CSR/ELL GS and RK actions, and records the
    parity deviation (``check``; exact 0 expected for GS — identical
    update order — and roundoff for RK).  CPU-interpret caveat applies to
    the absolute numbers; what the section pins is the parity and the
    per-PR trajectory of both paths.
    """
    bprob = block_banded_spd(n, block=block, bands=bands, n_rhs=k, seed=seed)
    bop = BlockBandedOp.from_dense(bprob.A, block=block, bands=bands)
    sprob = random_sparse_spd(n, row_nnz=row_nnz, n_rhs=k, seed=seed + 1)
    ewidth = int((np.asarray(sprob.A) != 0).sum(1).max())
    cases = {
        "banded_gs": (bop, bprob, "gs"),
        "csr_gs": (CsrOp.from_dense(sprob.A), sprob, "gs"),
        "csr_rk": (CsrOp.from_dense(sprob.A), sprob, "rk"),
        "ell_gs": (EllOp.from_dense(sprob.A, width=ewidth), sprob, "gs"),
        "ell_rk": (EllOp.from_dense(sprob.A, width=ewidth), sprob, "rk"),
    }
    out = {"n": n, "block": block, "bands": bands, "k": k, "steps": steps}
    for name, (op, prob, action) in cases.items():
        x0 = jnp.zeros_like(prob.b)
        kw = dict(action=action, key=jax.random.key(2), num_iters=steps,
                  record_every=steps)

        def scan():
            return solve_sequential(op, prob.b, x0, prob.x_star, **kw).x

        def fused():
            return solve_sequential(op, prob.b, x0, prob.x_star, fused=True,
                                    **kw).x

        check = float(jnp.abs(scan() - fused()).max())
        scan_wall = timed(scan, iters=repeats, stat="min")
        fused_wall = timed(fused, iters=repeats, stat="min")
        emit("bench_kernels_sweeps", case=name, steps=steps,
             scan_us=f"{scan_wall*1e6:.0f}", fused_us=f"{fused_wall*1e6:.0f}",
             check=f"{check:.2e}")
        out[name] = {"scan_us": scan_wall * 1e6,
                     "fused_us": fused_wall * 1e6, "check": check}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--bands", type=int, default=1)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timing repetitions; wall times are min-of-N")
    ap.add_argument("--storage-dtype", choices=("float32", "bfloat16"),
                    default=None,
                    help="coefficient storage precision for the layout "
                         "section's operators (checks run against the "
                         "rounded dense oracle); the per-dtype `precision` "
                         "section always reports both")
    ap.add_argument("--no-write", action="store_true",
                    help="print records without persisting BENCH_kernels"
                         ".json (the CI smoke job runs a tiny shape)")
    ap.add_argument("--tuned", action="store_true",
                    help="also time the tuning-table-driven dispatch "
                         "(repro.tune) against the best hardcoded default "
                         "on every layout row (the `tuned` section)")
    args = ap.parse_args(argv)
    payload = run(n=args.n, block=args.block, bands=args.bands, k=args.k,
                  repeats=args.repeats, storage_dtype=args.storage_dtype,
                  tuned=args.tuned)
    if not args.no_write:
        write_json("kernels", payload)
    return payload


if __name__ == "__main__":
    main()
