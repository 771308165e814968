"""The plain reference that decides ``correct``.

It takes the matrix as the benchmark made it (``ctx.A_host``: the float32
values the program was given, from the seed) and nothing the program
made, and recomputes each sampled answer's residual ``||b - A x||_2`` per
column in float64.  Three numbers are compared, each with its limit:

* ``resid_over_target`` — the worst column's float64 relative residual
  over the residual the request asked for.  The configuration states the
  guarantee, so the limit is 1.
* ``resid_gap`` — the worst column's gap between the residual the program
  reported and the float64 one, relative to ``||b||``.  This covers the
  engine's own residual and what it decides from it.  Limit: set between
  the program's readings and the control's (PERF.md).
* ``failed`` — solves that never completed, or that the program itself
  reported as not converged.  Limit 0.
"""
from __future__ import annotations

import numpy as np

#: sound solves read at most 1.849e-8 (both cells, 42 seeds); the
#: bfloat16-storage control at least 1.662e-4 (9 seeds); PERF.md section 2
GAP_LIMIT = 4e-6


def residuals(A64, b, x):
    b = np.asarray(b, np.float64)
    x = np.asarray(x, np.float64)
    return (np.linalg.norm(b - A64 @ x, axis=0),
            np.linalg.norm(b, axis=0))


def check(ctx, out) -> dict:
    A64 = ctx.A_host.astype(np.float64)
    ratio = gap = None
    for ans in ctx.sample.items:
        true, bn = residuals(A64, ans["b"], ans["x"])
        rep = np.asarray(ans["reported"], np.float64)
        r = float(np.max(true / (np.asarray(ans["rtol"]) * bn)))
        g = float(np.max(np.abs(rep - true) / bn))
        ratio = r if ratio is None else max(ratio, r)
        gap = g if gap is None else max(gap, g)
    return {
        "resid_over_target": {"value": ratio, "limit": 1.0,
                              "ok": ratio is not None and ratio <= 1.0},
        "resid_gap": {"value": gap, "limit": GAP_LIMIT,
                      "ok": gap is not None and gap <= GAP_LIMIT},
        "failed": {"value": int(out["failed"]), "limit": 0,
                   "ok": int(out["failed"]) == 0},
        "answers_checked": {"value": len(ctx.sample.items), "limit": 1,
                            "ok": len(ctx.sample.items) >= 1},
    }
