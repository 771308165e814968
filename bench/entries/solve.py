"""Closed loop, one caller: each call solves one block of ``k``
right-hand sides, then the next call starts.

A call is ``solve_batched`` to the configuration's residual target on
every column (``tol = rtol * ||b_j||``), with a cap of ``max_sweeps``
sweeps, fused sweeps, ``x0 = 0`` and a fresh pick key.

Right-hand sides are ``b = A x*``.  The configuration's ``exact_solution``
says what ``x*`` is: ``"normal"`` draws ``x* ~ N(0, 1)`` from (seed,
block); ``"ones"`` is the all-ones vector of every column.  ``rhs_pool``
blocks are made in set-up and the calls cycle through them.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import seed_key
from bench.spans import span
from repro.core.engine import as_operator, sequential_chunk, solve_batched


#: the pick-stream index of the untimed call in set-up
WARM = 1 << 30


@functools.partial(jax.jit, static_argnames=("width", "exact"))
def make_rhs(A, key, i, *, width, exact):
    """``A x*`` for block ``i``, at full float32 precision."""
    shape = (A.shape[0], width)
    xs = (jnp.ones(shape, jnp.float32) if exact == "ones" else
          jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32))
    return jnp.matmul(A, xs, precision=jax.lax.Precision.HIGHEST)


def rhs_blocks(ctx, count: int, width: int):
    """``count`` device blocks of ``width`` columns and their host copies."""
    key = jax.random.fold_in(seed_key(ctx.seed), 1)
    exact = ctx.config["exact_solution"]
    dev = [make_rhs(ctx.A_dev, key, i, width=width, exact=exact)
           for i in range(count)]
    return dev, [np.asarray(b) for b in dev]


def target_tol(b_host, rtol, margin):
    """Absolute per-column targets a hair under the guarantee, so that the
    float32 residual the program compares never lets a column out above
    it (the reference checks the guarantee itself)."""
    bn = np.linalg.norm(b_host.astype(np.float64), axis=0)
    return (rtol * (1.0 - margin) * bn).astype(np.float32)


def setup(ctx):
    cfg = ctx.config
    storage = None if ctx.storage_dtype == "float32" else ctx.storage_dtype
    op = as_operator(ctx.A_dev, cfg["format"],
                     rows_per_panel=cfg["program"]["rows_per_panel"],
                     storage_dtype=storage)
    k = int(cfg["k"])
    b_dev, b_host = rhs_blocks(ctx, int(ctx.traffic["rhs_pool"]), k)
    rtol = float(cfg["guarantee"]["relative_residual"])
    tols = [target_tol(b, rtol, cfg["request_margin"]) for b in b_host]
    st = {"op": op, "b_dev": b_dev, "b_host": b_host, "tols": tols,
          "rtol": rtol, "pick_key": jax.random.fold_in(seed_key(ctx.seed), 2)}
    st["call"] = _sequential(ctx, st)
    st["call"](WARM)   # one untimed call: every shape the window uses
    return st


def _sequential(ctx, st):
    prog = ctx.config["program"]
    n = ctx.A_host.shape[0]
    rec = int(prog["record_every"])
    chunk = functools.partial(sequential_chunk, action="gs", beta=1.0,
                              block=1, fused=prog["fused"])

    def chunk_fn(op, b, x, picks):
        with span("chunk"):
            return chunk(op, b, x, picks)

    def on_record(c, x, resid, conv):
        with span("on_record"):
            return True

    def call(i):
        j = i % len(st["b_dev"])
        with span("solve_batched"):
            res = solve_batched(
                st["op"], st["b_dev"][j], action="gs",
                key=jax.random.fold_in(st["pick_key"], i),
                num_iters=int(prog["max_sweeps"]) * n, tol=st["tols"][j],
                record_every=rec, fused=prog["fused"], chunk_fn=chunk_fn,
                on_record=on_record)
            jax.block_until_ready(res.x)
        rounds = np.asarray(res.rounds)
        return {"block": j, "x": res.x, "reported": res.resid,
                "converged": bool(np.asarray(res.converged).all()),
                "sweeps": float(rounds.max()) * rec / n,
                "updates": int(res.iters_run)}

    return call


def window(ctx, st, seconds):
    call = st["call"]
    done = []
    t0 = time.perf_counter()
    while True:
        out = call(len(done))
        done.append(out)
        ctx.sample.offer({"b": st["b_host"][out["block"]], "x": out["x"],
                          "reported": out["reported"],
                          "rtol": np.full((st["b_host"][0].shape[1],),
                                          st["rtol"])})
        out["x"] = None
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    failed = sum(not d["converged"] for d in done)
    counters = {"solves": len(done), "matrix": ctx.A_host,
                "k": int(ctx.config["k"]),
                "storage_dtype": ctx.storage_dtype,
                "sweeps_to_tol": [d["sweeps"] for d in done],
                "updates": sum(d["updates"] for d in done)}
    notes = [f"solves {len(done)} in {wall:.3f}s; sweeps per solve "
             f"{sorted(set(d['sweeps'] for d in done))}"]
    return {"e2e": {"solve_s": wall / len(done)}, "attempted": len(done),
            "failed": failed, "counters": counters, "notes": notes}


def teardown(ctx, st):
    st.clear()
