"""The single solver engine behind the RGS/RK stack (DESIGN.md §4).

The paper's algorithms are one family: pick a random direction (coordinate,
aligned block, or row), compute the residual along it from a possibly-stale
iterate, apply a damped update, and synchronize periodically so the
staleness stays bounded by a *scheduled* tau.  Pre-refactor, that family
was six divergent hand-rolled loops; this module is the one implementation,
parameterized along three orthogonal axes:

* **action**  — what a local update does: ``"gs"`` (coordinate / block
  Gauss-Seidel on an SPD system) or ``"rk"`` (Kaczmarz row action on a
  square or rectangular system);
* **format**  — how the matrix is stored and read, via the operator layer
  (``repro.core.operators``: dense, block-banded, ELL);
* **schedule** — when updates become visible: sequential (tau = 0), the
  bounded-delay *simulator* of Secs. 4/6 (ring-buffer exact stale reads),
  or the distributed periodic-synchronization scheme of Thm 4.1(a) over a
  shard_map worker mesh, with the sync collective (all-gather vs neighbor
  halo exchange vs delta psum) chosen from the operator's halo width.

The legacy entry points (``rgs_solve``, ``block_gs_solve``,
``parallel_rgs_solve``/``_banded``/``_halo``, ``rk_solve``,
``parallel_rk_solve``, ``async_rgs_solve``, ``async_rk_solve``) are thin
wrappers over this engine and reproduce their pre-refactor iterates
bit-for-bit given the same PRNG keys — the update arithmetic below is
transplanted verbatim, operation order included, and the equivalence is
pinned by tests/test_engine_equivalence.py against frozen legacy copies.
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import pvary, shard_map
from repro.core.operators import (
    BlockBandedOp,
    CsrOp,
    DenseOp,
    EllOp,
    as_operator,
    banded_panel_residual,
    banded_panel_residual_window,
    banded_rows_matvec,
    banded_window_matvec,
)
from repro.optim import compression
from repro.tune import runtime as tune_runtime


# ---------------------------------------------------------------------------
# Capability vocabularies — the single source every validation error, CLI
# ``choices=``, and repro-lint's dispatch checker (DX4) read from.  The
# strategy-level capability sets (_FUSED_/_OVERLAP_/_COMPRESS_STRATEGIES)
# live next to _DISTRIBUTED_STRATEGIES below.
# ---------------------------------------------------------------------------

#: distributed slab-assignment policies understood by ``Schedule.partition``
PARTITIONS = ("contiguous", "balanced")

#: wire codecs understood by ``Schedule.compress`` (error-feedback int8 and
#: round-to-nearest bf16; "none" is the exact f32 wire)
COMPRESS_MODES = ("none", "bf16", "int8_ef")


def supported_syncs(action, formats=None):
    """Sync modes ``_DISTRIBUTED_STRATEGIES`` has a row for.

    ``formats`` (operator class names) narrows the answer to the formats a
    caller can actually build — launchers use this for ``choices=`` so the
    CLI surface can never drift from the dispatch table.
    """
    return tuple(sorted({
        s for (a, f, s) in _DISTRIBUTED_STRATEGIES
        if a == action and (formats is None or f in formats)}))


# ---------------------------------------------------------------------------
# Result types (re-exported by repro.core.rgs / repro.core.parallel_rgs)
# ---------------------------------------------------------------------------

class SolveResult(NamedTuple):
    x: jax.Array           # (n, k) final iterate
    err_sq: jax.Array      # (records, k) squared error at each record point
    resid: jax.Array       # (records, k) ||b - A x_m||_2 at each record point
    iters: jax.Array       # (records,) iteration index of each record


class ParallelSolveResult(NamedTuple):
    x: jax.Array        # (n, k)
    err_sq: jax.Array   # (rounds, k)
    resid: jax.Array    # (rounds, k)
    tau: int            # effective staleness bound of the schedule
    #: per-round measured exchange lag (overlap=True only, else None):
    #: lag[r] = max over workers of the foreign updates committed by the end
    #: of round r-1 that the worker's round-r reads do NOT see.  The
    #: empirical staleness of a run is ``max(lag) + scheduled_tau(...)``
    #: with ``overlap=False`` (the in-round term), which the schedule
    #: guarantees is <= ``scheduled_tau(..., overlap=True)`` == ``tau``.
    lag: jax.Array | None = None
    #: analytic per-round wire volume (bytes one worker contributes to the
    #: sync collective each round, averaged over workers for the
    #: participation-asymmetric a2a exchanges), computed host-side from the
    #: dispatched strategy, sync and compress mode — None outside
    #: ``solve_distributed``.  This is the model quantity the compressed
    #: syncs shrink; benchmarks report it next to iterations-to-tolerance.
    bytes_per_round: float | None = None


# ---------------------------------------------------------------------------
# Schedule layer
# ---------------------------------------------------------------------------

def scheduled_tau(num_workers: int, local_steps: int, *,
                  shared_stream: bool = False,
                  local_sampling: bool = False,
                  overlap: bool = False) -> int:
    """Staleness bound of the periodic-synchronization schedule.

    ``shared_stream=False`` (per-worker direction streams, the RGS scheme):
    a worker's read can miss every other worker's in-round updates, so
    tau = (P - 1) * local_steps — the paper's Thm 4.1(a) bound.

    ``shared_stream=True`` (one global i.i.d. pick stream partitioned by
    owner, the RK scheme): within a round a pick misses at most the other
    workers' *earlier* in-round updates, so tau = local_steps - 1 (and 0 at
    P = 1, where every pick is owned and nothing is ever stale).

    ``local_sampling=True`` (per-worker local sampling, the sparse-RK
    scheme): every worker's ``local_steps`` picks are useful updates, so
    the round's interleaved shared stream carries P * local_steps picks
    and the shared-stream bound applies to that length —
    tau = P * local_steps - 1.  This is the single source of truth for the
    rule; the engine, CLIs, and benchmarks all route through it.

    ``overlap=True`` (double-buffered sync, ``Schedule(overlap=True)``):
    round r's exchange is issued concurrently with round r+1's local
    sweep, so a worker's reads additionally miss the *previous round's*
    foreign updates — the bound grows by exactly that payload:

    * per-worker streams: the other P-1 workers' full previous round,
      + (P - 1) * local_steps;
    * shared stream: the whole previous round of the stream,
      + local_steps;
    * local sampling: the other workers' previous-round picks,
      + (P - 1) * local_steps.

    At P = 1 there is nothing in flight and the term is 0.
    """
    extra = 0
    if overlap and num_workers > 1:
        if local_sampling or not shared_stream:
            extra = (num_workers - 1) * local_steps
        else:
            extra = local_steps
    if local_sampling:
        shared_stream = True
        local_steps = num_workers * local_steps
    if shared_stream:
        return extra + (0 if num_workers == 1 else local_steps - 1)
    return extra + (num_workers - 1) * local_steps


class Schedule(NamedTuple):
    """Execution plan for ``solve``: exactly one of the three modes.

    * sequential:  ``num_iters`` > 0, ``tau`` == 0, ``rounds`` == 0
    * async sim:   ``num_iters`` > 0, ``tau``  > 0  (bounded-delay model)
    * distributed: ``rounds`` > 0 and ``local_steps`` > 0 (needs a mesh)

    ``partition`` picks the distributed slab assignment: ``"contiguous"``
    (rows in index order — the default, and the only choice for the
    dense/banded layouts) or ``"balanced"`` (norm/nnz-balanced
    non-contiguous assignment via a row permutation, ``core.partition``;
    CsrOp/EllOp only).

    ``fused`` runs the inner loop (the per-record chunk in sequential
    mode, the local phase of a distributed round) as a single fused Pallas
    sweep kernel — the iterate VMEM-resident across all steps, the pick
    stream scalar-prefetched — instead of a per-step ``lax.scan``.
    An explicit ``fused=True`` on an action × format combination without a
    sweep kernel is an error; supported combinations produce iterates
    matching the scan engine to f32 roundoff.
    ``fused="auto"`` defers the pick to the active tuning table
    (``repro.tune``): the measured fused-vs-scan winner for this
    format × action × shape bucket on the current backend runs; with no
    table entry — or where the strategy has no fused kernel to pick —
    it resolves to the scan engine (today's default) with no warning,
    bitwise-unchanged.  Explicit booleans are never overridden.

    ``overlap`` (distributed only) double-buffers the sync: round r's
    halo / a2a / delta exchange is issued concurrently with round r+1's
    local sweep, so workers read one-round-staler remote slabs and the
    scheduled staleness grows by the quantified overlap term of
    ``scheduled_tau``.  Strategies without an overlapped variant fall
    back to lockstep rounds with a ``UserWarning`` (exact fallback).

    ``compress`` (distributed only) picks the wire format of the sync
    payload: ``"none"`` (f32, the default — bitwise-unchanged engine),
    ``"bf16"`` (payload rounded to bfloat16 on the wire), or
    ``"int8_ef"`` (int8 blocks + f32 scales via ``optim.compression``;
    the RK delta sync carries a per-worker error-feedback residual as
    loop state, flushed after the final round so the returned iterate
    contains every update).  Strategies without a compressed wire —
    everything but the RK delta psum and the banded halo exchange — fall
    back to the f32 payload with a ``UserWarning`` (exact fallback).
    """
    num_iters: int = 0
    rounds: int = 0
    local_steps: int = 0
    tau: int = 0
    record_every: int = 0
    partition: str = "contiguous"
    fused: bool | str = False
    overlap: bool = False
    compress: str = "none"

    @property
    def distributed(self) -> bool:
        return self.rounds > 0

    def validate(self) -> "Schedule":
        """Reject ambiguous mode mixtures with a message naming the fields.

        A Schedule carrying both ``num_iters > 0`` and ``rounds > 0`` has no
        single meaning (would it run 'num_iters' iterations, or 'rounds'
        synchronization rounds?), so it is an error rather than a silent
        choice.
        """
        if self.distributed and (self.num_iters > 0 or self.tau > 0):
            raise ValueError(
                "ambiguous Schedule: rounds/local_steps (distributed) "
                "cannot be combined with num_iters/tau (sequential / async "
                f"simulator) — got {self}")
        if self.distributed and self.local_steps <= 0:
            raise ValueError(
                f"a distributed Schedule needs local_steps > 0 (got {self})")
        if self.partition not in PARTITIONS:
            raise ValueError(
                f"unknown partition: {self.partition!r} (expected one of "
                f"{PARTITIONS})")
        if self.compress not in COMPRESS_MODES:
            raise ValueError(
                f"unknown compress: {self.compress!r} (expected one of "
                f"{COMPRESS_MODES})")
        if self.fused not in (False, True, "auto"):
            raise ValueError(
                f"unknown fused: {self.fused!r} (expected True, False or "
                f"'auto' — the tuning-table pick; got {self})")
        if not self.distributed:
            if self.num_iters <= 0:
                raise ValueError(
                    "a sequential/async Schedule needs num_iters > 0 "
                    f"(got {self})")
            if self.local_steps > 0:
                raise ValueError(
                    "local_steps without rounds is ambiguous — set rounds > 0 "
                    f"for distributed execution (got {self})")
            if self.partition != "contiguous":
                raise ValueError(
                    "partition='balanced' is a distributed-schedule option "
                    f"(slab assignment needs rounds/local_steps) — got {self}")
            if self.overlap:
                raise ValueError(
                    "overlap=True is a distributed-schedule option (the "
                    "double-buffered sync needs rounds/local_steps) — got "
                    f"{self}")
            if self.fused is True and self.tau > 0:
                # fused="auto" is fine here: the simulator has no fused
                # path for the table to pick, so auto resolves to the
                # per-step engine — nothing was forced, nothing to reject.
                raise ValueError(
                    "fused=True cannot run the bounded-delay simulator "
                    "(its ring-buffer stale reads are inherently per-step; "
                    "there is no fused sweep path to fall back from) — use "
                    f"fused=False or a different schedule mode; got {self}")
            if self.compress != "none":
                raise ValueError(
                    "compress is a distributed-schedule option (there is no "
                    "sync payload to compress without rounds/local_steps) — "
                    f"got {self}")
        return self

    def effective_tau(self, num_workers: int, *, shared_stream: bool = False,
                      local_sampling: bool = False) -> int:
        if self.distributed:
            return scheduled_tau(num_workers, self.local_steps,
                                 shared_stream=shared_stream,
                                 local_sampling=local_sampling,
                                 overlap=self.overlap)
        return self.tau


# ---------------------------------------------------------------------------
# Shared metrics/recording subsystem (replaces _record / _record_lsq /
# the inline banded metric blocks)
# ---------------------------------------------------------------------------

def record_metrics(op, b, x, x_star, *, norm: str):
    """(err_sq, resid) per RHS column.

    ``norm="A"``: ||x - x*||_A^2 (the SPD family's Lyapunov function);
    ``norm="euclid"``: ||x - x*||_2^2 (rectangular systems have no A-norm).
    ``resid`` is always ||b - A x||_2.

    ``x_star=None`` (a real workload: nobody knows the solution) yields
    NaN ``err_sq`` and the finite residual — the same convention the
    distributed strategies adopted in the PR-6 crash sweep.

    Matmuls here run at f32 precision on every backend: at the TPU's
    default (bf16 operand passes) the reported residual would carry
    ~1e-3 ||b|| of rounding, more than a converged solve's residual.
    """
    if norm not in ("A", "euclid"):
        raise ValueError(norm)
    mv = getattr(op, "matvec_ref", op.matvec)
    with jax.named_scope("repro.residual"), \
            jax.default_matmul_precision("highest"):
        if x_star is None:
            err = jnp.full((x.shape[1],), jnp.nan, jnp.float32)
        else:
            e = x - x_star
            if norm == "A":
                err = jnp.einsum("nk,nk->k", e, mv(e))
            else:
                err = jnp.einsum("nk,nk->k", e, e)
        return err, jnp.linalg.norm(b - mv(x), axis=0)


def resolve_record_every(num_iters: int, record_every: int) -> int:
    """The effective record-chunk length, validated in ONE place.

    ``record_every == 0`` means "record once, at the end".  The
    divisibility error used to exist in four near-identical copies across
    the sequential / fused / simulator bodies; the serving layer's
    deadline / early-exit logic reuses this same check for its chunk math,
    so the message can never drift between the library and the service.
    """
    rec = record_every or num_iters
    if num_iters % rec != 0:
        raise ValueError(
            f"num_iters ({num_iters}) must be divisible by record_every "
            f"({rec})")
    return rec


def draw_picks(op, action: str, key: jax.Array, num_iters: int, *,
               block: int = 1) -> jax.Array:
    """The sequential engine's direction stream, as one shared definition.

    GS picks are uniform over the action's direction count (block rows for
    ``BlockBandedOp``, coordinates at ``block == 1``, aligned panels
    otherwise); RK rows are sampled ∝ ||A_i||^2 via ``sample_rows``.  Both
    the one-shot sequential impls and the chunked batched entry
    (``solve_batched``) draw from here, which is what makes a chunked run
    bitwise-reproduce the one-shot pick stream.
    """
    if action == "gs":
        if isinstance(op, BlockBandedOp):
            hi = op.nb
        elif block == 1:
            hi = op.shape[0]
        else:
            hi = op.shape[0] // block
        return jax.random.randint(key, (num_iters,), 0, hi)
    if action == "rk":
        return sample_rows(key, op.row_norms_sq(), num_iters)
    raise ValueError(f"unknown action: {action!r}")


def sample_rows(key: jax.Array, rn: jax.Array, num: int) -> jax.Array:
    """``num`` i.i.d. row indices with P(i) ∝ rn_i (zero rows never picked).

    An all-zero ``rn`` (an empty shard after slab partitioning) would turn
    every logit into -inf and make ``categorical`` return garbage; the
    defined behavior here is *uniform* sampling instead — the callers guard
    the corresponding updates (zero rows make them no-ops), so distributed
    pick scheduling stays well-defined on degenerate slabs.
    """
    pos = rn > 0
    logits = jnp.where(pos, jnp.log(jnp.where(pos, rn, 1.0)), -jnp.inf)
    logits = jnp.where(jnp.any(pos), logits, jnp.zeros_like(logits))
    return jax.random.categorical(key, logits, shape=(num,))


# ---------------------------------------------------------------------------
# Sequential engine
# ---------------------------------------------------------------------------

def _fused_sweep_supported(op, action: str, block: int) -> bool:
    """Whether a fused sweep kernel exists for this action x format.

    The sweep layer covers the banded block-GS action (kernels/banded_gs),
    and the padded-row coordinate-GS / Kaczmarz actions for CsrOp / EllOp
    (kernels/sweep_csr).  Dense formats and block > 1 row-panel GS stay
    on the scan engine.
    """
    if action == "gs":
        if isinstance(op, BlockBandedOp):
            return True
        return block == 1 and isinstance(op, (CsrOp, EllOp))
    if action == "rk":
        return isinstance(op, (CsrOp, EllOp))
    return False


def _no_fused_kernel(op, action, detail=""):
    """The error for an explicit ``fused=True`` that no kernel can honor
    (``fused="auto"`` resolves to the scan engine instead)."""
    return NotImplementedError(
        f"fused=True: no fused sweep kernel for action={action!r} x "
        f"{type(op).__name__}{detail}; pass fused=False for the per-step "
        "scan engine, or fused='auto' to let the tuning table pick")


def _require_fused_kernel(op, action: str, block: int) -> None:
    if not _fused_sweep_supported(op, action, block):
        raise _no_fused_kernel(
            op, action, f" with block={block}" if block != 1 else "")


def _warn_overlap_fallback(op, action, kind):
    warnings.warn(
        f"overlap=True: the {kind!r} strategy (action={action!r} x "
        f"{type(op).__name__}) has no overlapped-sync variant; running "
        "lockstep rounds (exact fallback — iterates unchanged)",
        UserWarning, stacklevel=3)


def _warn_compress_fallback(op, action, kind, compress):
    warnings.warn(
        f"compress={compress!r}: the {kind!r} strategy (action={action!r} x "
        f"{type(op).__name__}) has no compressed wire format; running the "
        "f32 payload (exact fallback — iterates unchanged)",
        UserWarning, stacklevel=3)


def solve_sequential(
    op,
    b: jax.Array,
    x0: jax.Array,
    x_star: jax.Array,
    *,
    action: str,
    key: jax.Array,
    num_iters: int,
    beta: float = 1.0,
    block: int = 1,
    record_every: int = 0,
    fused: bool | str = False,
) -> SolveResult:
    """Sequential randomized solve: one local-update step per iteration.

    action "gs":  coordinate (block=1) or aligned-block Gauss-Seidel on a
                  unit-diagonal SPD system; directions uniform.
    action "rk":  Kaczmarz row action; rows sampled ∝ ||A_i||^2.

    ``fused=True`` executes each record chunk as one fused Pallas sweep
    (the operator's ``gs_sweep``/``rk_sweep`` entry point: iterate
    VMEM-resident, picks scalar-prefetched) instead of a per-step
    ``lax.scan``; the pick stream and update arithmetic are shared, so
    iterates match the scan engine to f32 roundoff (the kernels sum a
    row's inner product in slot order).  Formats without a sweep kernel
    raise ``NotImplementedError``.
    ``fused="auto"`` runs the tuning table's measured winner where a
    sweep kernel exists, the scan otherwise — silently, since nothing
    was forced (see ``Schedule``).
    """
    if fused == "auto":
        fused = (_fused_sweep_supported(op, action, block)
                 and tune_runtime.resolve_fused(fused, op, action))
    if fused:
        _require_fused_kernel(op, action, block)
        return _sequential_fused_impl(
            op, b, x0, x_star, action=action, key=key,
            num_iters=num_iters, beta=float(beta), block=block,
            record_every=record_every)
    return _sequential_scan_impl(
        op, b, x0, x_star, action=action, key=key, num_iters=num_iters,
        beta=beta, block=block, record_every=record_every)


@functools.partial(
    jax.jit,
    static_argnames=("action", "num_iters", "block", "record_every", "beta"))
def _sequential_fused_impl(
    op,
    b: jax.Array,
    x0: jax.Array,
    x_star: jax.Array,
    *,
    action: str,
    key: jax.Array,
    num_iters: int,
    beta: float = 1.0,
    block: int = 1,
    record_every: int = 0,
    picks: jax.Array | None = None,
) -> SolveResult:
    """Fused-sweep twin of ``_sequential_scan_impl``: identical pick
    streams and record points, but each record chunk runs as a single
    Pallas launch.  ``picks`` overrides the internally drawn direction
    stream (the chunked ``solve_batched`` entry feeds pre-drawn slices so
    a chunked run replays the one-shot stream bitwise).

    ``beta`` is DELIBERATELY static here (its scan twin traces it): the
    sweep kernels bake the step size into the kernel body as a
    compile-time constant — a scalar operand would ride the scalar-
    prefetch channel and change every kernel's signature for a value
    that is fixed for the lifetime of a solve.  The visible consequence
    is one recompilation per distinct ``beta``; solves sweep few betas
    (one, or theory.beta_opt per tau), so the cache stays small.  The
    contract is pinned by a compile-count test
    (tests/test_engine_overlap.py::test_fused_beta_static_recompiles).
    """
    rec = resolve_record_every(num_iters, record_every)

    if action == "gs":
        norm = "A"
        if picks is None:
            picks = draw_picks(op, action, key, num_iters, block=block)

        def sweep(x, ps):
            return op.gs_sweep(b, x, ps, beta=beta)
    elif action == "rk":
        norm = "euclid"
        rn = op.row_norms_sq()
        if picks is None:
            picks = draw_picks(op, action, key, num_iters, block=block)

        def sweep(x, ps):
            return op.rk_sweep(b, rn, x, ps, beta=beta)
    else:
        raise ValueError(f"unknown action: {action!r}")

    def chunk(x, ps):
        # The sweep entry points rebuild their loop-invariant operator
        # views (packed band tiles / padded row windows) per record chunk
        # — accepted: record chunks are few, the views are cheap relative
        # to a chunk's sweep, and keeping preparation inside the operator
        # method is what lets a new format plug in with one method.
        x = sweep(x, ps)
        return x, record_metrics(op, b, x, x_star, norm=norm)

    x, (errs, resids) = jax.lax.scan(chunk, x0, picks.reshape(-1, rec))
    iters = (1 + jnp.arange(num_iters // rec)) * rec
    return SolveResult(x=x, err_sq=errs, resid=resids, iters=iters)


@functools.partial(
    jax.jit, static_argnames=("action", "num_iters", "block", "record_every"))
def _sequential_scan_impl(
    op,
    b: jax.Array,
    x0: jax.Array,
    x_star: jax.Array,
    *,
    action: str,
    key: jax.Array,
    num_iters: int,
    beta: float = 1.0,
    block: int = 1,
    record_every: int = 0,
    picks: jax.Array | None = None,
) -> SolveResult:
    """The per-step scan engine (the pre-PR-5 ``solve_sequential`` body —
    the legacy bit-identity contract lives here; the pick draws now route
    through ``draw_picks``, same streams bitwise).  ``picks`` overrides
    the drawn stream — see ``_sequential_fused_impl``."""
    rec = resolve_record_every(num_iters, record_every)

    if action == "gs":
        norm = "A"
        if isinstance(op, BlockBandedOp):
            # Θ(nnz) block-GS on the banded format (new capability: the
            # sequential twin of the banded distributed path).
            bsz = op.block

            def step(x, bi):
                g = op.residual_panel(b, x, bi)
                cur = jax.lax.dynamic_slice_in_dim(x, bi * bsz, bsz, 0)
                return jax.lax.dynamic_update_slice_in_dim(
                    x, cur + beta * g, bi * bsz, 0), None
        elif block == 1:
            def step(x, r):
                gamma = b[r] - op.row_dot(r, x)
                return x.at[r].add(beta * gamma), None
        else:
            if not isinstance(op, (DenseOp, CsrOp)):
                raise NotImplementedError(
                    "block GS with block > 1 needs aligned row panels "
                    "(DenseOp/CsrOp) or BlockBandedOp")

            def step(x, bi):
                rows = bi * block + jnp.arange(block)
                Ab = op.row_panel(bi, block)
                gamma = b[rows] - Ab @ x
                return x.at[rows].add(beta * gamma), None
    elif action == "rk":
        if not isinstance(op, (DenseOp, EllOp, CsrOp)):
            raise NotImplementedError(
                "sequential RK needs per-row access (DenseOp/EllOp/CsrOp); "
                "the banded Kaczmarz path runs through solve_distributed")
        norm = "euclid"
        rn = op.row_norms_sq()

        def step(x, r):
            g = (b[r] - op.row_dot(r, x)) / rn[r]
            return op.rk_update(x, r, g, beta), None
    else:
        raise ValueError(f"unknown action: {action!r}")

    if picks is None:
        picks = draw_picks(op, action, key, num_iters, block=block)

    def chunk(x, ps):
        x, _ = jax.lax.scan(step, x, ps)
        return x, record_metrics(op, b, x, x_star, norm=norm)

    x, (errs, resids) = jax.lax.scan(chunk, x0, picks.reshape(-1, rec))
    iters = (1 + jnp.arange(num_iters // rec)) * rec
    return SolveResult(x=x, err_sq=errs, resid=resids, iters=iters)


# ---------------------------------------------------------------------------
# Bounded-delay asynchronous simulator (the paper's Secs. 4/6 read models)
# ---------------------------------------------------------------------------

def solve_async_sim(
    op,
    b: jax.Array,
    x0: jax.Array,
    x_star: jax.Array,
    *,
    action: str,
    key: jax.Array,
    delay_key: jax.Array,
    num_iters: int,
    tau: int,
    beta: float = 1.0,
    read_model: str = "consistent",
    delay_mode: str = "fixed",
    miss_prob: float = 0.5,
    record_every: int = 0,
) -> SolveResult:
    """Faithful simulator of delays bounded by ``tau`` (consistent and
    inconsistent reads), for both the coordinate ("gs") and row ("rk")
    actions.  Mechanics: a ring buffer of the last ``tau`` applied updates
    (direction index, applied amount); the stale read is reconstructed
    exactly via

        A_r x_stale = A_r x - sum_{t invisible} c_t * w(r, r_t)

    where the correction weight ``w`` is the coupling ``A[r, r_t]`` for the
    coordinate action and the row inner product ``<A_r, A_{r_t}>`` for the
    row action.  Delay schedules are drawn from ``delay_key``, independent
    of the direction key (Assumption A-4).

    Sparse operators are **densified exactly** (and a ``UserWarning`` is
    emitted): the ring-buffer correction needs arbitrary ``A[r, r_t]``
    couplings and row inner products, so the simulator — a research tool
    for delay models, not a performance path — runs Θ(n) reads per step
    regardless of the format's ``nnz_cost()``.  Use ``solve_distributed``
    for the sparse-aware execution of the same schedules.
    """
    if not isinstance(op, DenseOp):
        if not hasattr(op, "to_dense"):
            raise NotImplementedError(
                f"the async simulator needs a densifiable operator "
                f"(got {type(op).__name__})")
        # to_dense() reconstructs the stored values bit-for-bit, so the
        # simulated iterates are exact — only the cost model changes.
        warnings.warn(
            f"solve_async_sim densifies {type(op).__name__} exactly: the "
            "bounded-delay simulator ignores the format's nnz_cost() and "
            "pays dense Θ(n) row reads per step (it is a research tool for "
            "delay models, not a sparse performance path — use "
            "solve_distributed for sparse-aware execution)",
            UserWarning, stacklevel=2)
        op = DenseOp(op.to_dense())
    return _async_sim_impl(
        op, b, x0, x_star, action=action, key=key, delay_key=delay_key,
        num_iters=num_iters, tau=tau, beta=beta, read_model=read_model,
        delay_mode=delay_mode, miss_prob=miss_prob,
        record_every=record_every)


@functools.partial(
    jax.jit,
    static_argnames=("action", "num_iters", "tau", "record_every",
                     "read_model", "delay_mode"),
)
def _async_sim_impl(
    op,
    b: jax.Array,
    x0: jax.Array,
    x_star: jax.Array,
    *,
    action: str,
    key: jax.Array,
    delay_key: jax.Array,
    num_iters: int,
    tau: int,
    beta: float = 1.0,
    read_model: str = "consistent",
    delay_mode: str = "fixed",
    miss_prob: float = 0.5,
    record_every: int = 0,
) -> SolveResult:
    A = op.A
    k = b.shape[1]
    rec = resolve_record_every(num_iters, record_every)
    t_buf = max(tau, 1)

    if action == "gs":
        norm = "A"
        picks = jax.random.randint(key, (num_iters,), 0, A.shape[0])
    elif action == "rk":
        norm = "euclid"
        rn = op.row_norms_sq()
        picks = sample_rows(key, rn, num_iters)
    else:
        raise ValueError(f"unknown action: {action!r}")

    if read_model == "consistent":
        if delay_mode == "fixed":
            aux = jnp.full((num_iters,), tau, jnp.int32)
        elif delay_mode == "uniform":
            aux = jax.random.randint(delay_key, (num_iters,), 0, tau + 1)
        elif delay_mode == "cyclic":
            aux = (jnp.arange(num_iters) % (tau + 1)).astype(jnp.int32)
        else:
            raise ValueError(delay_mode)
    elif read_model == "inconsistent":
        aux = jax.random.bernoulli(delay_key, miss_prob, (num_iters, t_buf))
    else:
        raise ValueError(read_model)

    ring_r0 = jnp.zeros((t_buf,), jnp.int32)
    ring_c0 = jnp.zeros((t_buf, k), x0.dtype)
    offsets = jnp.arange(t_buf)

    def step(carry, inp):
        x, ring_r, ring_c, j = carry
        r, a = inp
        it_idx = j - 1 - offsets              # iteration indices, newest first
        valid = it_idx >= 0
        if read_model == "consistent":
            invisible = (offsets < a) & valid  # suffix of length s_j
        else:
            invisible = a & valid & (offsets < tau)  # subset of last tau
        slots = jnp.mod(it_idx, t_buf)
        rs = ring_r[slots]                     # (t_buf,)
        cs = ring_c[slots]                     # (t_buf, k) applied amounts
        if action == "gs":
            w = jnp.where(invisible, A[r, rs], 0.0)
            corr = w @ cs
            gamma = b[r] - A[r] @ x + corr
            applied = beta * gamma
            x = x.at[r].add(applied)
        else:
            w = jnp.where(invisible, A[rs] @ A[r], 0.0)
            corr = w @ cs
            gamma = (b[r] - A[r] @ x + corr) / rn[r]
            applied = beta * gamma
            x = x + A[r][:, None] * applied[None, :]
        ring_r = ring_r.at[jnp.mod(j, t_buf)].set(r)
        ring_c = ring_c.at[jnp.mod(j, t_buf)].set(applied)
        return (x, ring_r, ring_c, j + 1), None

    def chunk(carry, inp):
        carry, _ = jax.lax.scan(step, carry, inp)
        return carry, record_metrics(op, b, carry[0], x_star, norm=norm)

    inps = (picks.reshape(-1, rec), aux.reshape((-1, rec) + aux.shape[1:]))
    carry = (x0, ring_r0, ring_c0, jnp.array(0, jnp.int32))
    carry, (errs, resids) = jax.lax.scan(chunk, carry, inps)
    iters = (1 + jnp.arange(num_iters // rec)) * rec
    return SolveResult(x=carry[0], err_sq=errs, resid=resids, iters=iters)


# ---------------------------------------------------------------------------
# Distributed driver (shard_map): one skeleton, five action×format×sync
# strategies.  The update arithmetic of the four legacy strategies is
# transplanted verbatim — bit-identity, not mere closeness, is tested.
# ---------------------------------------------------------------------------

def solve_distributed(
    op,
    b: jax.Array,
    x0: jax.Array,
    x_star: jax.Array | None = None,
    *,
    action: str = "gs",
    key: jax.Array,
    mesh: jax.sharding.Mesh,
    axis: str = "workers",
    rounds: int,
    local_steps: int,
    block: int = 1,
    beta: float = 1.0,
    sync: str = "auto",
    partition: str = "contiguous",
    fused: bool | str = False,
    overlap: bool = False,
    compress: str = "none",
    unroll: bool = False,
    with_metrics: bool = True,
) -> ParallelSolveResult:
    """P-way asynchronous solve under the periodic-synchronization schedule.

    ``fused=True`` runs each round's local phase (the ``local_steps``
    sequential updates between synchronizations) as one fused Pallas sweep
    on the banded strategies — banded GS under both the all-gather and
    halo syncs (``kernels/banded_gs.banded_gs_sweep``, bitwise-identical
    iterates) and banded RK (``banded_rk_sweep``, the masked
    Cimmino-within-panel action over VMEM-resident window + delta
    carries) — and on the sparse strategies: sparse slab GS
    (``sweep_rows_gs`` with the slab's traced write base scalar-
    prefetched) and sparse local-sampling RK (``sweep_rows_rk_delta``, the
    two-carry replica+delta sweep); the sparse phases match the scan to
    f32 roundoff.  An explicit ``fused=True`` on a strategy without a
    fused local phase raises ``NotImplementedError``.

    ``overlap=True`` double-buffers the synchronization: round r's
    exchange payload (halo edges / slab rotations / round delta) is the
    one captured at the END of round r-1, so the collective has no data
    dependency on round r's sweep and XLA is free to run them
    concurrently — workers read remote state that is one round staler,
    and the scheduled tau grows by ``scheduled_tau``'s overlap term.
    Overlapped variants exist for the ``halo_gs``, ``sparse_gs`` and
    ``sparse_rk`` strategies (``_OVERLAP_STRATEGIES``); others fall back
    to lockstep rounds with a ``UserWarning`` (exact fallback).  The
    result's ``lag`` field then carries the measured per-round staleness
    trace (see ``ParallelSolveResult``).

    The sync collective is chosen from the operator's layout metadata when
    ``sync="auto"``: a finite halo (block-banded) means neighbor halo
    exchange suffices for the GS action; unstructured-but-sparse formats
    that answer slab-neighbor queries (CSR, ELL) get the neighbor
    all-to-all for both actions; unbounded reach (dense) needs an
    all-gather of slab deltas for GS and a delta psum for RK.

    ``sync="a2a"`` with the GS action exchanges each worker's slab only
    along the row-slab neighbor graph derived from the sparsity pattern
    (one masked ppermute rotation per distinct slab offset); when the graph
    is dense — every worker reads every slab — it falls back to the
    all-gather, which moves the same bytes with one collective.  With the
    RK action it replaces the dense delta psum with a two-phase exchange
    over the *column-slab* neighbor graph (reduce each column slab's deltas
    to its owner, then broadcast the sum back to the slab's readers),
    bitwise-identical to the psum; it falls back to the psum when the
    column graph is dense or the column count does not divide by P.

    ``partition="balanced"`` replaces the contiguous slab assignment with
    the norm/nnz-balanced row permutation of ``core.partition`` (CsrOp /
    EllOp): the operator, b (and, for the coordinate action, the iterate
    vectors) are permuted up front, every downstream slab is contiguous
    again, and the returned iterate is un-permuted.

    ``compress`` shrinks the sync payload on the wire (see ``Schedule``):
    the RK delta psum sends the round delta as bf16 or int8+error-feedback
    (``sparse_rk``; foreign replicas see compressed deltas, a worker's own
    updates stay exact, and the int8 residual is flushed after the last
    round so the returned iterate misses nothing), and the banded halo
    exchange sends its edge payloads quantized per round (``halo_gs``; the
    edges are *state*, re-sent fresh every round, so the error does not
    compound and no feedback term is needed — and the owned slab the
    worker returns is never compressed).  Strategies without a compressed
    wire fall back to f32 with a ``UserWarning``; ``sparse_rk`` under
    ``sync="a2a"`` falls back to the psum wire (with a warning), because
    the a2a exchange's bitwise-psum invariant cannot survive a lossy
    payload.  The analytic per-round wire volume of the dispatched
    combination is returned in ``ParallelSolveResult.bytes_per_round``.
    """
    num_workers = mesh.shape[axis]
    row_perm = None
    if partition == "balanced":
        from repro.core import partition as partition_lib
        op, b, x0, x_star, row_perm = partition_lib.apply_partition(
            op, b, x0, x_star, action=action, num_slabs=num_workers)
    elif partition != "contiguous":
        raise ValueError(
            f"unknown partition: {partition!r} (expected one of "
            f"{PARTITIONS})")

    if sync == "auto":
        if action == "rk":
            sync = "a2a" if hasattr(op, "slab_neighbors") else "psum"
        elif op.halo_width is not None:
            sync = "halo"
        elif hasattr(op, "slab_neighbors"):
            sync = "a2a"
        else:
            sync = "allgather"

    # Dispatch first, on the *requested* combination, so unsupported
    # action x format x sync asks fail with the enumerating error rather
    # than a wrong-layer message from the a2a precompute.
    kind = _DISTRIBUTED_STRATEGIES.get(
        (action, type(op).__name__, sync))
    if kind is None:
        supported = "\n  ".join(
            f"action={a!r} x format={f} x sync={s!r}"
            for (a, f, s) in sorted(_DISTRIBUTED_STRATEGIES))
        raise NotImplementedError(
            f"no distributed strategy for action={action!r}, "
            f"format={type(op).__name__}, sync={sync!r}; supported "
            f"combinations:\n  {supported}")
    if kind == "sparse_gs" and block != 1:
        raise NotImplementedError(
            f"distributed block GS with block={block} is not supported for "
            f"{type(op).__name__}; the sparse slab strategies run "
            "coordinate GS (block=1)")
    if fused == "auto":
        # Per-strategy-row resolution: the table's measured winner runs
        # where the strategy has a fused local phase; elsewhere auto
        # silently means scan — nothing was forced, so no warning (the
        # warning below is for an explicit fused=True that cannot be
        # honored).
        fused = (kind in _FUSED_STRATEGIES
                 and tune_runtime.resolve_fused(fused, op, action))
    if fused and kind not in _FUSED_STRATEGIES:
        raise _no_fused_kernel(op, action, f" under the {kind!r} strategy")
    if overlap and kind not in _OVERLAP_STRATEGIES:
        _warn_overlap_fallback(op, action, kind)
        overlap = False
    if compress not in COMPRESS_MODES:
        raise ValueError(
            f"unknown compress: {compress!r} (expected one of "
            f"{COMPRESS_MODES})")
    if compress != "none" and kind not in _COMPRESS_STRATEGIES:
        _warn_compress_fallback(op, action, kind, compress)
        compress = "none"
    if compress != "none" and kind == "sparse_rk" and sync == "a2a":
        warnings.warn(
            f"compress={compress!r}: the a2a delta exchange is pinned "
            "bitwise to the psum reduction, which a lossy payload cannot "
            "preserve; running the compressed psum wire instead",
            UserWarning, stacklevel=2)
        sync = "psum"

    a2a_schedule, a2a_masks = (), None
    if sync == "a2a" and kind == "sparse_gs":
        need = op.slab_neighbors(num_workers)
        if num_workers > 1 and bool(need.all()):
            # Truly dense graph — every worker reads every slab: the masked
            # rotations would move exactly the all-gather's bytes over P-1
            # sequential collectives, so one all-gather wins.  (A graph
            # that merely covers all P-1 offsets with few pairs stays on
            # a2a: its perms only carry the needed sender->reader pairs.)
            # The strategy is unchanged — sparse_gs serves both syncs.
            sync = "allgather"
        else:
            # One rotation per distinct slab offset; each rotation's perm
            # only includes the (sender -> reader) pairs the sparsity
            # pattern demands, and masks[w, si] says whether worker w
            # accepts the slab arriving over rotation si.
            shifts = sorted({(w - v) % num_workers
                             for w in range(num_workers)
                             for v in range(num_workers)
                             if need[w, v] and w != v})
            a2a_schedule = tuple(
                (s, tuple((v, (v + s) % num_workers)
                          for v in range(num_workers)
                          if need[(v + s) % num_workers, v]))
                for s in shifts)
            a2a_masks = jnp.asarray(
                [[bool(need[w, (w - s) % num_workers]) for s in shifts]
                 for w in range(num_workers)]).reshape(num_workers,
                                                       len(shifts))
    elif sync == "a2a" and kind == "sparse_rk":
        # The RK delta sync runs over the COLUMN-slab neighbor graph:
        # need[w, c] says worker w's rows reference (read *and* write)
        # columns in slab c — the same matrix slab_neighbors() answers,
        # read column-wise.  Phase 1 reduces each column slab's deltas to
        # its owner (worker c owns column slab c) over one masked ppermute
        # rotation per shift; phase 2 broadcasts each owner's summed slab
        # back to its readers.  The owner accumulates contributions in
        # device order, which reproduces the psum's left-to-right
        # reduction bit-for-bit (pinned by test on the forced-4-device
        # host mesh).
        n_cols = op.shape[1]
        need = op.slab_neighbors(num_workers)
        if num_workers > 1 and (bool(need.all())
                                or n_cols % num_workers != 0):
            # Dense column graph: every rotation would carry every slab —
            # the single fused psum moves the same bytes with one
            # collective.  Indivisible column count: there is no equal
            # column-slab ownership to reduce onto.  Both fall back to the
            # delta psum, which is bitwise what a2a would have computed.
            sync = "psum"
        else:
            reduce_scheds = tuple(
                tuple((v, (v + s) % num_workers)
                      for v in range(num_workers)
                      if need[v, (v + s) % num_workers])
                for s in range(1, num_workers))
            bcast_scheds = tuple(
                tuple((c, (c + s) % num_workers)
                      for c in range(num_workers)
                      if need[(c + s) % num_workers, c] and
                      (c + s) % num_workers != c)
                for s in range(1, num_workers))
            a2a_schedule = (reduce_scheds, bcast_scheds)
            # accept masks for phase 2: masks[w, s-1] <=> worker w reads
            # column slab (w - s) mod P.
            a2a_masks = jnp.asarray(
                [[bool(need[w, (w - s) % num_workers])
                  for s in range(1, num_workers)]
                 for w in range(num_workers)]).reshape(
                     num_workers, max(num_workers - 1, 0))

    res = _distributed_impl(
        kind, op, b, x0, x_star, key, mesh=mesh, axis=axis, rounds=rounds,
        local_steps=local_steps, block=block, beta=beta, unroll=unroll,
        with_metrics=with_metrics, sync=sync, a2a_schedule=a2a_schedule,
        a2a_masks=a2a_masks, fused=fused, overlap=overlap, compress=compress)
    res = res._replace(bytes_per_round=_sync_bytes_per_round(
        kind, sync, compress, op=op, n=x0.shape[0], k=b.shape[1],
        num_workers=num_workers, a2a_schedule=a2a_schedule))
    if row_perm is not None and action == "gs":
        # Undo the symmetric permutation on the returned iterate (the "rk"
        # iterate lives in column space and was never permuted).
        res = res._replace(x=res.x[row_perm.inv])
    return res


#: action x format x sync -> strategy implementation.  The sparse strategies
#: are format-generic: any operator exposing ``padded_rows()`` (per-row
#: value/column windows with global column ids) slots in.
_DISTRIBUTED_STRATEGIES = {
    ("gs", "DenseOp", "allgather"): "dense_gs",
    ("gs", "BlockBandedOp", "allgather"): "banded_gs",
    ("gs", "BlockBandedOp", "halo"): "halo_gs",
    ("gs", "EllOp", "allgather"): "sparse_gs",
    ("gs", "EllOp", "a2a"): "sparse_gs",
    ("gs", "CsrOp", "allgather"): "sparse_gs",
    ("gs", "CsrOp", "a2a"): "sparse_gs",
    ("rk", "DenseOp", "psum"): "dense_rk",
    ("rk", "BlockBandedOp", "psum"): "banded_rk",
    ("rk", "EllOp", "psum"): "sparse_rk",
    ("rk", "EllOp", "a2a"): "sparse_rk",
    ("rk", "CsrOp", "psum"): "sparse_rk",
    ("rk", "CsrOp", "a2a"): "sparse_rk",
}

#: strategies whose local phase has a fused Pallas sweep.
_FUSED_STRATEGIES = frozenset(
    {"banded_gs", "halo_gs", "banded_rk", "sparse_gs", "sparse_rk"})

#: strategies with a double-buffered (overlapped) sync variant: the round-r
#: exchange payload is captured at the end of round r-1, so the collective
#: carries no data dependency on round r's local sweep.
_OVERLAP_STRATEGIES = frozenset({"halo_gs", "sparse_gs", "sparse_rk"})

#: strategies with a compressed wire format (``Schedule.compress``): the RK
#: delta psum and the banded halo exchange.  The slab all-gathers stay f32 —
#: a gathered slab IS the iterate (not an additive correction), so lossy
#: gathers would overwrite owned state with rounded values.
_COMPRESS_STRATEGIES = frozenset({"sparse_rk", "halo_gs"})


def _payload_bytes(size: int, compress: str) -> float:
    """Wire bytes of one ``size``-element f32 payload under a codec."""
    if compress == "bf16":
        return 2.0 * size
    if compress == "int8_ef":
        blocks = -(-size // compression.BLOCK)
        return float(blocks * compression.BLOCK + 4 * blocks)  # q + scales
    return 4.0 * size


def _sync_bytes_per_round(kind, sync, compress, *, op, n, k, num_workers,
                          a2a_schedule=()):
    """Analytic per-round sync payload: bytes ONE worker sends per round.

    Derived from the dispatch row, not measured — the model quantity the
    compressed wire formats shrink.  For the participation-asymmetric a2a
    exchanges the per-worker send count varies with the neighbor graph, so
    the total across workers is averaged over P.  At P = 1 every strategy
    skips its collective: 0 bytes.
    """
    if num_workers <= 1:
        return 0.0
    slab = n // num_workers
    if kind in ("dense_gs", "banded_gs"):
        return 4.0 * slab * k                      # all-gather of slab delta
    if kind == "halo_gs":
        halo = op.bands * op.block
        return 2.0 * _payload_bytes(halo * k, compress)   # two edges
    if kind == "sparse_gs":
        if sync == "a2a":
            sends = sum(len(pairs) for _, pairs in a2a_schedule)
            return 4.0 * slab * k * sends / num_workers
        return 4.0 * slab * k                      # all-gather of slab
    if kind in ("dense_rk", "banded_rk"):
        return _payload_bytes(n * k, compress)     # full-delta psum
    if kind == "sparse_rk":
        if sync == "a2a":
            reduce_scheds, bcast_scheds = a2a_schedule
            sends = (sum(len(p) for p in reduce_scheds)
                     + sum(len(p) for p in bcast_scheds))
            return 4.0 * slab * k * sends / num_workers
        return _payload_bytes(n * k, compress)     # full-delta psum
    raise ValueError(kind)  # pragma: no cover - guarded by dispatch


def _fused_band_tiles(op):
    """Zero-padded border tiles for the fused banded sweeps (one packing
    definition: ``BlockBandedOp.packed_band_tiles``)."""
    return op.packed_band_tiles()


@functools.partial(
    jax.jit,
    static_argnames=("kind", "mesh", "axis", "rounds", "local_steps", "block",
                     "beta", "unroll", "with_metrics", "sync",
                     "a2a_schedule", "fused", "overlap", "compress"),
)
def _distributed_impl(kind, op, b, x0, xs, key, *, mesh, axis, rounds,
                      local_steps, block, beta, unroll, with_metrics,
                      sync="allgather", a2a_schedule=(), a2a_masks=None,
                      fused=False, overlap=False, compress="none"):
    num_workers = mesh.shape[axis]
    k = b.shape[1]
    zero_m = (jnp.zeros((k,), jnp.float32), jnp.zeros((k,), jnp.float32))

    def local_scan(step, carry, picks):
        return jax.lax.scan(step, carry, picks,
                            unroll=local_steps if unroll else 1)

    def round_scan(body, carry, per_round):
        return jax.lax.scan(body, carry, per_round,
                            unroll=rounds if unroll else 1)

    tau = scheduled_tau(num_workers, local_steps,
                        shared_stream=kind.endswith("_rk"),
                        local_sampling=kind == "sparse_rk",
                        overlap=overlap)

    lag = None
    if kind == "dense_gs":
        x, errs, resids = _dense_gs(
            op.A, b, x0, xs, key, mesh=mesh, axis=axis, rounds=rounds,
            local_steps=local_steps, block=block, beta=beta,
            with_metrics=with_metrics, num_workers=num_workers,
            zero_m=zero_m, local_scan=local_scan, round_scan=round_scan)
    elif kind == "banded_gs":
        x, errs, resids = _banded_gs(
            op, b, x0, xs, key, mesh=mesh, axis=axis, rounds=rounds,
            local_steps=local_steps, beta=beta, with_metrics=with_metrics,
            num_workers=num_workers, zero_m=zero_m, local_scan=local_scan,
            round_scan=round_scan, fused=fused)
    elif kind == "halo_gs":
        x, errs, resids, lag = _halo_gs(
            op, b, x0, xs, key, mesh=mesh, axis=axis, rounds=rounds,
            local_steps=local_steps, beta=beta, with_metrics=with_metrics,
            num_workers=num_workers, zero_m=zero_m, local_scan=local_scan,
            round_scan=round_scan, fused=fused, overlap=overlap,
            compress=compress)
    elif kind == "dense_rk":
        x, errs, resids = _dense_rk(
            op.A, b, x0, xs, key, mesh=mesh, axis=axis, rounds=rounds,
            local_steps=local_steps, beta=beta, with_metrics=with_metrics,
            num_workers=num_workers, zero_m=zero_m, local_scan=local_scan,
            round_scan=round_scan)
    elif kind == "banded_rk":
        x, errs, resids = _banded_rk(
            op, b, x0, xs, key, mesh=mesh, axis=axis, rounds=rounds,
            local_steps=local_steps, beta=beta, with_metrics=with_metrics,
            num_workers=num_workers, zero_m=zero_m, local_scan=local_scan,
            round_scan=round_scan, fused=fused)
    elif kind == "sparse_gs":
        x, errs, resids, lag = _sparse_gs(
            op, b, x0, xs, key, mesh=mesh, axis=axis, rounds=rounds,
            local_steps=local_steps, beta=beta, with_metrics=with_metrics,
            num_workers=num_workers, zero_m=zero_m, local_scan=local_scan,
            round_scan=round_scan, sync=sync, a2a_schedule=a2a_schedule,
            a2a_masks=a2a_masks, fused=fused, overlap=overlap)
    elif kind == "sparse_rk":
        x, errs, resids, lag = _sparse_rk(
            op, b, x0, xs, key, mesh=mesh, axis=axis, rounds=rounds,
            local_steps=local_steps, beta=beta, with_metrics=with_metrics,
            num_workers=num_workers, zero_m=zero_m, local_scan=local_scan,
            round_scan=round_scan, sync=sync, a2a_schedule=a2a_schedule,
            a2a_masks=a2a_masks, fused=fused, overlap=overlap,
            compress=compress)
    else:  # pragma: no cover - guarded by solve_distributed
        raise ValueError(kind)

    return ParallelSolveResult(x=x, err_sq=errs, resid=resids, tau=tau,
                               lag=lag)


def _dense_gs(A, b, x0, xs, key, *, mesh, axis, rounds, local_steps, block,
              beta, with_metrics, num_workers, zero_m, local_scan, round_scan):
    """Dense slab-partitioned block GS; all-gather of slab deltas.

    x* is consumed fully replicated — the pre-refactor implementation
    sharded it and re-all-gathered it every round inside the metric path,
    a pure-waste collective (ISSUE 2 satellite).  The metric values are
    bitwise unchanged (the gather reconstructed exactly this replica).
    """
    n = A.shape[0]
    slab = n // num_workers
    assert slab * num_workers == n and slab % block == 0
    round_keys = jax.random.split(key, rounds)

    def worker(A_sh, b_sh, xs_full, x0_full, keys):
        # A_sh: (slab, n), b_sh: (slab, k); xs_full/x0_full replicated.
        w = jax.lax.axis_index(axis)
        col0 = w * slab

        def round_body(x, rkey):
            rkey = jax.random.fold_in(rkey, w)
            picks = jax.random.randint(rkey, (local_steps,), 0, slab // block)
            # Mark as device-varying: each worker accumulates its own deltas.
            delta = pvary(
                jnp.zeros((slab, b_sh.shape[1]), x.dtype), (axis,)
            )

            def step(delta, bi):
                rows = bi * block + jnp.arange(block)
                Ar = A_sh[rows]                          # (block, n)
                stale = Ar @ x                           # stale replica read
                # own-slab columns see the *fresh* local updates:
                own = jax.lax.dynamic_slice(Ar, (0, col0), (block, slab))
                g = b_sh[rows] - stale - own @ delta
                return delta.at[rows].add(beta * g), None

            delta, _ = local_scan(step, delta, picks)
            # Periodic synchronization (the paper's Thm 4.1(a) scheme).
            x2 = x + jax.lax.all_gather(delta, axis, axis=0, tiled=True)
            if not with_metrics:
                return x2, zero_m
            if xs_full is not None:
                e_local = (jax.lax.dynamic_slice_in_dim(x2, col0, slab, 0)
                           - jax.lax.dynamic_slice_in_dim(xs_full, col0,
                                                          slab, 0))
                err = jax.lax.psum(
                    jnp.einsum("sk,sk->k", e_local, A_sh @ (x2 - xs_full)),
                    axis)
            else:
                err = jnp.full((b_sh.shape[1],), jnp.nan, jnp.float32)
            r_local = b_sh - A_sh @ x2
            rsq = jax.lax.psum(jnp.einsum("sk,sk->k", r_local, r_local), axis)
            return x2, (err, jnp.sqrt(rsq))

        x, (errs, resids) = round_scan(round_body, pvary(x0_full, (axis,)),
                                       keys)
        # Every worker's x is identical after the final all-gather, but the
        # VMA type system cannot prove it; return the owned slab (the honest
        # sharding) and let the out_spec reassemble the global vector.
        x_slab = jax.lax.dynamic_slice_in_dim(x, col0, slab, 0)
        return x_slab, errs, resids

    mapped = shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(None, None), P(None, None),
                  P(None)),
        out_specs=(P(axis, None), P(None, None), P(None, None)),
    )
    return mapped(A, b, xs, x0, round_keys)


def _banded_gs(op, b, x0, xs, key, *, mesh, axis, rounds, local_steps, beta,
               with_metrics, num_workers, zero_m, local_scan, round_scan,
               fused=False):
    """Block-banded slab GS; per-round all-gather of the owned slab.

    ``fused=True`` replaces the local-phase scan with one
    ``banded_gs_sweep`` launch per round: the worker's halo-padded window
    of the replica stays VMEM-resident across all ``local_steps`` updates,
    and border validity moves from the scan's ``where(valid, ...)`` masks
    into zero-padded tiles (``pack_bands_local``) — exact zeros either
    way, so the iterates are bitwise identical.
    """
    block, bands, nb = op.block, op.bands, op.nb
    n = b.shape[0]
    slab = n // num_workers
    nb_local = slab // block
    assert nb * block == n and nb_local * block == slab
    round_keys = jax.random.split(key, rounds)
    Ab = _fused_band_tiles(op) if fused else op.A_bands
    halo = bands * block

    def worker(Ab_sh, b_sh, keys, x0_full, xs_full):
        # Ab_sh: (nb_local, width, block, block); b_sh: (slab, k).
        w = jax.lax.axis_index(axis)
        row0 = w * slab

        def round_body(x, rkey):
            rkey = jax.random.fold_in(rkey, w)
            picks = jax.random.randint(rkey, (local_steps,), 0, nb_local)
            xw = x   # working replica: own rows fresh, remote rows stale

            def step(xw, bi):
                g = banded_panel_residual(Ab_sh, b_sh, xw, bi,
                                          w * nb_local + bi, nb, block, bands)
                rows0 = row0 + bi * block
                cur = jax.lax.dynamic_slice_in_dim(xw, rows0, block, 0)
                return jax.lax.dynamic_update_slice_in_dim(
                    xw, cur + beta * g, rows0, 0), None

            if fused:
                from repro.kernels import ops
                xpad = jnp.pad(xw, ((halo, halo), (0, 0)))
                win = jax.lax.dynamic_slice_in_dim(
                    xpad, row0, slab + 2 * halo, 0)
                win = ops.banded_gs_sweep(Ab_sh, b_sh, win, picks,
                                          block=block, bands=bands,
                                          beta=beta)
                own = jax.lax.dynamic_slice_in_dim(win, halo, slab, 0)
            else:
                xw, _ = local_scan(step, xw, picks)
                own = jax.lax.dynamic_slice_in_dim(xw, row0, slab, 0)
            x2 = jax.lax.all_gather(own, axis, axis=0, tiled=True)
            if not with_metrics:
                return x2, zero_m
            # metrics (slab-local residual psum)
            r_local = b_sh - banded_rows_matvec(Ab_sh, x2, w, nb, nb_local,
                                                block, bands)
            rsq = jax.lax.psum(jnp.einsum("sk,sk->k", r_local, r_local), axis)
            if xs_full is not None:
                e_own = own - jax.lax.dynamic_slice_in_dim(xs_full, row0, slab, 0)
                esq = jax.lax.psum(
                    jnp.einsum("sk,sk->k", e_own,
                               -r_local + (b_sh - banded_rows_matvec(
                                   Ab_sh, xs_full, w, nb, nb_local, block,
                                   bands))),
                    axis)
            else:
                esq = jnp.full((b_sh.shape[1],), jnp.nan, jnp.float32)
            return x2, (esq, jnp.sqrt(rsq))

        x, (errs, resids) = round_scan(round_body, pvary(x0_full, (axis,)),
                                       keys)
        x_slab = jax.lax.dynamic_slice_in_dim(x, row0, slab, 0)
        return x_slab, errs, resids

    mapped = shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(axis, None, None, None), P(axis, None), P(None),
                  P(None, None), P(None, None)),
        out_specs=(P(axis, None), P(None, None), P(None, None)),
    )
    return mapped(Ab, b, round_keys, x0, xs)


def _halo_gs(op, b, x0, xs, key, *, mesh, axis, rounds, local_steps, beta,
             with_metrics, num_workers, zero_m, local_scan, round_scan,
             fused=False, overlap=False, compress="none"):
    """Block-banded slab GS; neighbor halo exchange instead of all-gather.

    Iterates are IDENTICAL to the all-gather strategy — the gathered entries
    outside the halo were never read.  ``err_sq`` is the A-norm error when
    ``xs`` is provided (computed slab-locally from the halo window) and NaN
    otherwise — pre-refactor this slot silently carried the squared
    residual (ISSUE 2 satellite).

    ``fused=True`` hands the halo-padded window — already exactly the
    sweep kernel's working-set shape — to one ``banded_gs_sweep`` launch
    per round in place of the local-phase scan (bitwise-identical
    iterates; border validity baked into zero-padded tiles).

    ``overlap=True`` double-buffers the halo exchange: the edges installed
    during round r are the ones CAPTURED at the end of round r-1 (carried
    through the round scan), so the two ppermutes have no data dependency
    on round r's sweep and XLA can run them concurrently — the halos a
    sweep reads are one round staler, and staleness counters measure the
    resulting lag (see ``ParallelSolveResult.lag``).

    ``compress`` quantizes the edge payloads on the wire (bf16 round, or
    absolute int8 blocks + scales).  No error feedback: an edge is *state*
    — the neighbor's current boundary rows, re-sent fresh every round — so
    per-round quantization error never accumulates across rounds the way a
    compressed additive delta would.  Only the halo copies are perturbed;
    the owned slab each worker returns is never compressed, and the
    metrics exchange of ``xs`` stays exact so the recorded A-norm error
    measures the true trajectory of the compressed run.
    """
    block, bands, nb = op.block, op.bands, op.nb
    n, k = b.shape
    slab = n // num_workers
    nb_local = slab // block
    halo = bands * block
    assert halo <= slab, "halo exchange needs bands*block <= slab"
    round_keys = jax.random.split(key, rounds)
    Ab = _fused_band_tiles(op) if fused else op.A_bands
    down = [(i, i + 1) for i in range(num_workers - 1)]
    up = [(i + 1, i) for i in range(num_workers - 1)]
    have_xs = xs is not None

    def worker(Ab_sh, b_sh, x0_sh, keys, *maybe_xs):
        w = jax.lax.axis_index(axis)

        def wire_edge(e):
            # What the compressed wire does to an outgoing edge.  Applied
            # sender-side before the ppermute so the collective carries the
            # narrow payload; identity under compress="none".
            if compress == "bf16":
                return compression.bf16_roundtrip_array(e)
            if compress == "int8_ef":
                return compression.roundtrip_array(e)
            return e

        def install(xw, lo_edge, hi_edge, *, codec=True):
            # lo/hi_edge: my top/bottom owned rows -> neighbors' halos.
            if codec:
                lo_edge, hi_edge = wire_edge(lo_edge), wire_edge(hi_edge)
            from_prev = jax.lax.ppermute(hi_edge, axis, down)   # w-1's bottom
            from_next = jax.lax.ppermute(lo_edge, axis, up)     # w+1's top
            xw = jax.lax.dynamic_update_slice_in_dim(xw, from_prev, 0, 0)
            return jax.lax.dynamic_update_slice_in_dim(
                xw, from_next, halo + slab, 0)

        def exchange(xw, *, codec=True):
            own = jax.lax.dynamic_slice_in_dim(xw, halo, slab, 0)
            return install(xw, own[:halo], own[-halo:], codec=codec)

        if have_xs:
            # Metrics-only exchange: x* halos travel exact so the recorded
            # error norm is not itself perturbed by the codec.
            xs_w = exchange(jnp.pad(maybe_xs[0], ((halo, halo), (0, 0))),
                            codec=False)

        def local_phase(xw, rkey):
            rkey = jax.random.fold_in(rkey, w)
            picks = jax.random.randint(rkey, (local_steps,), 0, nb_local)

            def step(xw, bi):
                g = banded_panel_residual_window(
                    Ab_sh, b_sh, xw, bi, w * nb_local + bi, nb, slab, block,
                    bands)
                r0 = halo + bi * block
                cur = jax.lax.dynamic_slice_in_dim(xw, r0, block, 0)
                return jax.lax.dynamic_update_slice_in_dim(
                    xw, cur + beta * g, r0, 0), None

            if fused:
                from repro.kernels import ops
                return ops.banded_gs_sweep(Ab_sh, b_sh, xw, picks,
                                           block=block, bands=bands,
                                           beta=beta)
            xw, _ = local_scan(step, xw, picks)
            return xw

        def metrics(xw):
            if not with_metrics:
                return zero_m
            # Vectorized residual: vmap the per-panel window residual, then
            # accumulate the per-panel squared sums LEFT-TO-RIGHT via scan —
            # bitwise the old Python loop's grouping (a fused jnp.sum would
            # reassociate), with O(1) trace size instead of O(nb_local).
            r_all = jax.vmap(
                lambda bi: banded_panel_residual_window(
                    Ab_sh, b_sh, xw, bi, w * nb_local + bi, nb, slab, block,
                    bands).astype(jnp.float32))(jnp.arange(nb_local))
            part = jnp.einsum("nbk,nbk->nk", r_all, r_all)
            resid2, _ = jax.lax.scan(
                lambda acc, p: (acc + p, None),
                jnp.zeros((k,), jnp.float32), part)
            rsq = jax.lax.psum(resid2, axis)
            if have_xs:
                # A-norm error from the window: e^T A e = sum over owned
                # rows of e_own * (A e)_own, psum'd across workers.
                ew = xw - xs_w
                Ae_own = banded_window_matvec(Ab_sh, ew, w, nb, nb_local,
                                              block, bands)
                e_own = jax.lax.dynamic_slice_in_dim(ew, halo, slab, 0)
                esq = jax.lax.psum(
                    jnp.einsum("sk,sk->k", e_own, Ae_own), axis)
            else:
                esq = jnp.full((k,), jnp.nan, jnp.float32)
            return esq, jnp.sqrt(rsq)

        xw0 = jnp.pad(x0_sh, ((halo, halo), (0, 0)))
        xw0 = exchange(xw0)

        if overlap:
            foreign = jnp.arange(num_workers) != w

            def round_body(carry, rkey):
                xw, lo_prev, hi_prev, cnt, seen = carry
                # cnt carried in == updates committed by the end of the
                # previous round == the count of the in-flight payload, so
                # one all_gather serves both the payload's bookkeeping and
                # the lag measurement.
                cnt_all = jax.lax.all_gather(cnt, axis)
                lag = jax.lax.pmax(
                    jnp.sum(jnp.where(foreign, cnt_all - seen, 0)), axis)
                seen = jnp.where(foreign, cnt_all, seen)
                cnt = cnt + local_steps
                xw = local_phase(xw, rkey)          # halos one round stale
                xw = install(xw, lo_prev, hi_prev)  # in-flight edges land
                own = jax.lax.dynamic_slice_in_dim(xw, halo, slab, 0)
                return ((xw, own[:halo], own[-halo:], cnt, seen),
                        (metrics(xw), lag))

            own0 = jax.lax.dynamic_slice_in_dim(xw0, halo, slab, 0)
            cnt0 = pvary(jnp.zeros((), jnp.int32), (axis,))
            seen0 = pvary(jnp.zeros((num_workers,), jnp.int32), (axis,))
            carry0 = (xw0, own0[:halo], own0[-halo:], cnt0, seen0)
            (xw, *_), ((errs, resids), lags) = round_scan(
                round_body, carry0, keys)
            return (jax.lax.dynamic_slice_in_dim(xw, halo, slab, 0),
                    errs, resids, lags)

        def round_body(xw, rkey):
            xw = local_phase(xw, rkey)
            xw = exchange(xw)
            return xw, metrics(xw)

        xw, (errs, resids) = round_scan(round_body, xw0, keys)
        return jax.lax.dynamic_slice_in_dim(xw, halo, slab, 0), errs, resids

    in_specs = [P(axis, None, None, None), P(axis, None), P(axis, None),
                P(None)]
    args = [Ab, b, x0, round_keys]
    if have_xs:
        in_specs.append(P(axis, None))
        args.append(xs)
    out_specs = [P(axis, None), P(None, None), P(None, None)]
    if overlap:
        out_specs.append(P(None))
    mapped = shard_map(
        worker,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
    )
    out = mapped(*args)
    return out if overlap else out + (None,)


def _dense_rk(A, b, x0, xs, key, *, mesh, axis, rounds, local_steps, beta,
              with_metrics, num_workers, zero_m, local_scan, round_scan):
    """Row-slab Kaczmarz; one global i.i.d. pick stream, delta psum sync."""
    m = A.shape[0]
    slab = m // num_workers
    assert slab * num_workers == m, (
        f"worker count ({num_workers}) must divide the row count ({m})")
    rn = jnp.einsum("mn,mn->m", A, A)
    picks = sample_rows(key, rn, rounds * local_steps).reshape(
        rounds, local_steps)

    def worker(A_sh, b_sh, rn_sh, x0_full, xs_full, picks):
        # A_sh: (slab, n); b_sh: (slab, k); rn_sh: (slab,); x0/xs replicated.
        w = jax.lax.axis_index(axis)
        row0 = w * slab

        def round_body(xw, picks_r):
            delta = pvary(jnp.zeros_like(xw), (axis,))

            def step(carry, p):
                xw, delta = carry
                li = p - row0
                mine = (li >= 0) & (li < slab)
                lic = jnp.clip(li, 0, slab - 1)
                Ar = A_sh[lic]                               # (n,)
                g = (b_sh[lic] - Ar @ xw) / rn_sh[lic]       # (k,)
                # Arithmetic mirrors the sequential step exactly
                # (bit-identity at P=1): scalar coefficient times row outer
                # product.
                upd = jnp.where(mine, beta, 0.0) * Ar[:, None] * g[None, :]
                return (xw + upd, delta + upd), None

            (xw, delta), _ = local_scan(step, (xw, delta), picks_r)
            if num_workers > 1:
                # Periodic synchronization: pull in the other workers'
                # updates.  Skipped entirely at P=1 — it would be a bitwise
                # no-op in exact arithmetic, but XLA folds the single-device
                # psum away and reassociates xw + (delta - delta), costing
                # an ulp per round and breaking the exact-degeneracy
                # guarantee the consistency tests rely on.
                xw = xw + (jax.lax.psum(delta, axis) - delta)
            if not with_metrics:
                return xw, zero_m
            # xw is a full replica, so the error is local; residual rows are
            # sharded, so the squared norm needs a psum.
            if xs_full is not None:
                err = jnp.einsum("nk,nk->k", xw - xs_full, xw - xs_full)
            else:
                err = jnp.full((b_sh.shape[1],), jnp.nan, jnp.float32)
            r_local = b_sh - A_sh @ xw
            rsq = jax.lax.psum(jnp.einsum("sk,sk->k", r_local, r_local), axis)
            return xw, (err, jnp.sqrt(rsq))

        xw, (errs, resids) = round_scan(round_body, pvary(x0_full, (axis,)),
                                        picks)
        return xw, errs, resids

    mapped = shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis), P(None, None),
                  P(None, None), P(None, None)),
        out_specs=(P(None, None), P(None, None), P(None, None)),
    )
    return mapped(A, b, rn, x0, xs, picks)


def _banded_rk(op, b, x0, xs, key, *, mesh, axis, rounds, local_steps, beta,
               with_metrics, num_workers, zero_m, local_scan, round_scan,
               fused=False):
    """Block-banded Kaczmarz — the new point in the action×format grid.

    The row panel of a random block-row is sampled ∝ its squared Frobenius
    norm (the block extension of Strohmer-Vershynin sampling); the update is
    the damped simultaneous-row (Cimmino-within-panel) action

        x += beta * A_B^T diag(1/||a_i||^2) (b - A x)_B

    whose writes reach only ``bands`` block columns either side of the
    panel — the same finite reach the banded GS strategies exploit.  Like
    the dense RK strategy, the pick stream is global (one i.i.d. sequence
    partitioned by owner), each worker carries its own updates fresh within
    a round, and synchronization is a delta psum with scheduled staleness
    ``local_steps - 1``.

    ``fused=True`` runs the local phase as one ``banded_rk_sweep`` launch
    per round: the worker's halo-padded windows of the replica AND of the
    round delta stay VMEM-resident across all steps, the global pick
    stream is pre-localized (clipped local id + ownership gate, both
    scalar-prefetched), and foreign picks apply the same exact-zero
    updates the scan's masked arithmetic does.
    """
    block, bands, nb = op.block, op.bands, op.nb
    width = op.width
    n = b.shape[0]
    slab = n // num_workers
    nb_local = slab // block
    halo = bands * block
    assert nb * block == n and nb_local * block == slab
    rn = op.row_norms_sq()                                  # (nb, block)
    panel_w = jnp.sum(rn, axis=1)                           # (nb,) — raw
    # norms: a zero panel must keep sampling weight 0 (log 0 = -inf).
    rn = jnp.where(rn > 0, rn, 1.0)                         # divisor guard only
    picks = sample_rows(key, panel_w, rounds * local_steps).reshape(
        rounds, local_steps)
    Ab = _fused_band_tiles(op) if fused else op.A_bands

    def worker(Ab_sh, b_sh, rn_sh, x0_full, xs_full, picks):
        # Ab_sh: (nb_local, width, block, block); rn_sh: (nb_local, block).
        w = jax.lax.axis_index(axis)

        def add_at(v, off, contrib):
            cur = jax.lax.dynamic_slice_in_dim(v, off, block, 0)
            return jax.lax.dynamic_update_slice_in_dim(v, cur + contrib, off, 0)

        def apply_panel(xw, delta, tiles, gb, upd):
            """Scatter A_B^T upd into the band columns of both carries,
            computing each (block, block) @ (block, k) contribution once."""
            for d in range(width):
                cb = gb + d - bands
                cbc = jnp.clip(cb, 0, nb - 1)
                valid = (cb >= 0) & (cb < nb)
                contrib = jnp.dot(tiles[d].T, upd,
                                  preferred_element_type=jnp.float32)
                contrib = jnp.where(valid, contrib, 0.0).astype(xw.dtype)
                xw = add_at(xw, cbc * block, contrib)
                delta = add_at(delta, cbc * block, contrib)
            return xw, delta

        def round_body(xw, picks_r):
            delta = pvary(jnp.zeros_like(xw), (axis,))

            def step(carry, p):
                xw, delta = carry
                li = p - w * nb_local
                mine = (li >= 0) & (li < nb_local)
                lic = jnp.clip(li, 0, nb_local - 1)
                gb = w * nb_local + lic
                g = banded_panel_residual(Ab_sh, b_sh, xw, lic, gb, nb,
                                          block, bands)          # (block, k)
                rnp = jax.lax.dynamic_slice_in_dim(rn_sh, lic, 1, 0)[0]
                gn = (jnp.where(mine, beta, 0.0) * g
                      / rnp[:, None]).astype(jnp.float32)
                tiles = jax.lax.dynamic_slice_in_dim(Ab_sh, lic, 1, 0)[0]
                return apply_panel(xw, delta, tiles, gb, gn), None

            if fused:
                from repro.kernels import ops
                li = picks_r - w * nb_local
                mine = (li >= 0) & (li < nb_local)
                lic = jnp.clip(li, 0, nb_local - 1)
                row0 = w * slab
                xpad = jnp.pad(xw, ((halo, halo), (0, 0)))
                dpad = jnp.pad(delta, ((halo, halo), (0, 0)))
                xwin = jax.lax.dynamic_slice_in_dim(
                    xpad, row0, slab + 2 * halo, 0)
                dwin = jax.lax.dynamic_slice_in_dim(
                    dpad, row0, slab + 2 * halo, 0)
                xwin, dwin = ops.banded_rk_sweep(
                    Ab_sh, b_sh, rn_sh, xwin, dwin, lic,
                    mine.astype(jnp.int32), block=block, bands=bands,
                    beta=beta)
                xpad = jax.lax.dynamic_update_slice_in_dim(
                    xpad, xwin, row0, 0)
                dpad = jax.lax.dynamic_update_slice_in_dim(
                    dpad, dwin, row0, 0)
                xw = xpad[halo:halo + n]
                delta = dpad[halo:halo + n]
            else:
                (xw, delta), _ = local_scan(step, (xw, delta), picks_r)
            if num_workers > 1:
                xw = xw + (jax.lax.psum(delta, axis) - delta)
            if not with_metrics:
                return xw, zero_m
            r_local = b_sh - banded_rows_matvec(Ab_sh, xw, w, nb, nb_local,
                                                block, bands)
            rsq = jax.lax.psum(jnp.einsum("sk,sk->k", r_local, r_local), axis)
            if xs_full is not None:
                err = jnp.einsum("nk,nk->k", xw - xs_full, xw - xs_full)
            else:
                err = jnp.full((b_sh.shape[1],), jnp.nan, jnp.float32)
            return xw, (err, jnp.sqrt(rsq))

        xw, (errs, resids) = round_scan(round_body, pvary(x0_full, (axis,)),
                                        picks)
        return xw, errs, resids

    mapped = shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(axis, None, None, None), P(axis, None), P(axis, None),
                  P(None, None), P(None, None), P(None, None)),
        out_specs=(P(None, None), P(None, None), P(None, None)),
    )
    return mapped(Ab, b, rn, x0, xs, picks)


def _sparse_gs(op, b, x0, xs, key, *, mesh, axis, rounds, local_steps, beta,
               with_metrics, num_workers, zero_m, local_scan, round_scan,
               sync, a2a_schedule, a2a_masks, fused=False, overlap=False):
    """Row-sparse slab GS (CsrOp / EllOp) — the format-generic strategy.

    Each worker owns a slab of rows in padded-row form (fixed-width
    value/column windows with *global* column ids — ``op.padded_rows()``),
    keeps a full-length working replica whose own slab is fresh within a
    round, and refreshes at round end either by all-gather or by the
    sparsity-derived neighbor all-to-all (``sync="a2a"``): one masked
    ppermute rotation per distinct slab offset in the neighbor graph,
    sending a worker's slab only to the workers whose rows actually read
    it.  Iterates are IDENTICAL to the all-gather strategy — the slabs a2a
    leaves stale are never read.

    ``fused=True`` runs the local phase as one ``sweep_rows_gs`` launch
    per round: the replica stays VMEM-resident across all ``local_steps``
    updates and the slab offset rides the scalar-prefetch channel as the
    kernel's write base (it is traced — ``axis_index`` under shard_map).
    The kernel sums each row's inner product in slot order, so iterates
    match the scan step to f32 roundoff.

    ``overlap=True`` exchanges the own slab captured at the END of round
    r-1 (carried through the round scan) while round r's sweep runs on
    remote slabs that are one round staler; the a2a rotations never write
    the own slab, and the all-gather path splices the fresh own rows back
    over the stale gather.  Staleness counters measure the per-round lag.
    """
    n, k = b.shape
    if n % num_workers:
        raise ValueError(
            f"worker count ({num_workers}) must divide the row count ({n})")
    slab = n // num_workers
    vals, cols = op.padded_rows()
    round_keys = jax.random.split(key, rounds)
    if a2a_masks is None:
        a2a_masks = jnp.zeros((num_workers, len(a2a_schedule)), bool)

    def worker(vals_sh, cols_sh, b_sh, masks_sh, keys, x0_full, xs_full):
        # vals_sh/cols_sh: (slab, width); b_sh: (slab, k); x0/xs replicated.
        w = jax.lax.axis_index(axis)
        row0 = w * slab

        def refresh(xw, own_prev=None):
            """own_prev=None: lockstep (exchange this round's own slab);
            otherwise install the in-flight previous-round payload."""
            own = (jax.lax.dynamic_slice_in_dim(xw, row0, slab, 0)
                   if own_prev is None else own_prev)
            if sync == "allgather":
                x2 = jax.lax.all_gather(own, axis, axis=0, tiled=True)
                if own_prev is None:
                    return x2
                fresh = jax.lax.dynamic_slice_in_dim(xw, row0, slab, 0)
                return jax.lax.dynamic_update_slice_in_dim(x2, fresh, row0, 0)
            # a2a rotations only ever write remote slabs (shift != 0), so
            # the fresh own slab survives either way.
            for si, (shift, perm) in enumerate(a2a_schedule):
                recv = jax.lax.ppermute(own, axis, perm)
                src0 = ((w - shift) % num_workers) * slab
                cur = jax.lax.dynamic_slice_in_dim(xw, src0, slab, 0)
                upd = jnp.where(masks_sh[0, si], recv, cur)
                xw = jax.lax.dynamic_update_slice_in_dim(xw, upd, src0, 0)
            return xw

        def local_phase(xw, rkey):
            rkey = jax.random.fold_in(rkey, w)
            picks = jax.random.randint(rkey, (local_steps,), 0, slab)
            if fused:
                from repro.kernels import ops
                return ops.sweep_rows_gs(vals_sh, cols_sh, b_sh, xw, picks,
                                         beta=beta, write_base=row0)

            def step(xw, li):
                g = b_sh[li] - jnp.einsum("w,wk->k", vals_sh[li],
                                          xw[cols_sh[li]])
                return xw.at[row0 + li].add(beta * g), None

            xw, _ = local_scan(step, xw, picks)
            return xw

        def metrics(xw):
            if not with_metrics:
                return zero_m
            # Both metric reductions only read the slabs this worker's rows
            # reference, so they are exact under a2a as well.
            r_local = b_sh - jnp.einsum("sw,swk->sk", vals_sh, xw[cols_sh])
            rsq = jax.lax.psum(jnp.einsum("sk,sk->k", r_local, r_local), axis)
            if xs_full is not None:
                e = xw - xs_full
                Ae_own = jnp.einsum("sw,swk->sk", vals_sh, e[cols_sh])
                e_own = jax.lax.dynamic_slice_in_dim(e, row0, slab, 0)
                esq = jax.lax.psum(jnp.einsum("sk,sk->k", e_own, Ae_own),
                                   axis)
            else:
                esq = jnp.full((k,), jnp.nan, jnp.float32)
            return esq, jnp.sqrt(rsq)

        if overlap:
            foreign = jnp.arange(num_workers) != w

            def round_body(carry, rkey):
                xw, own_prev, cnt, seen = carry
                cnt_all = jax.lax.all_gather(cnt, axis)
                lag = jax.lax.pmax(
                    jnp.sum(jnp.where(foreign, cnt_all - seen, 0)), axis)
                seen = jnp.where(foreign, cnt_all, seen)
                cnt = cnt + local_steps
                xw = local_phase(xw, rkey)   # remote slabs one round stale
                xw = refresh(xw, own_prev)   # in-flight payload lands
                own = jax.lax.dynamic_slice_in_dim(xw, row0, slab, 0)
                return (xw, own, cnt, seen), (metrics(xw), lag)

            xw0 = pvary(x0_full, (axis,))
            own0 = jax.lax.dynamic_slice_in_dim(xw0, row0, slab, 0)
            cnt0 = pvary(jnp.zeros((), jnp.int32), (axis,))
            seen0 = pvary(jnp.zeros((num_workers,), jnp.int32), (axis,))
            (xw, *_), ((errs, resids), lags) = round_scan(
                round_body, (xw0, own0, cnt0, seen0), keys)
            x_slab = jax.lax.dynamic_slice_in_dim(xw, row0, slab, 0)
            return x_slab, errs, resids, lags

        def round_body(xw, rkey):
            xw = local_phase(xw, rkey)
            xw = refresh(xw)
            return xw, metrics(xw)

        xw, (errs, resids) = round_scan(round_body, pvary(x0_full, (axis,)),
                                        keys)
        x_slab = jax.lax.dynamic_slice_in_dim(xw, row0, slab, 0)
        return x_slab, errs, resids

    out_specs = [P(axis, None), P(None, None), P(None, None)]
    if overlap:
        out_specs.append(P(None))
    mapped = shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None),
                  P(axis, None), P(None), P(None, None), P(None, None)),
        out_specs=tuple(out_specs),
    )
    out = mapped(vals, cols, b, a2a_masks, round_keys, x0, xs)
    return out if overlap else out + (None,)


def _sparse_rk(op, b, x0, xs, key, *, mesh, axis, rounds, local_steps, beta,
               with_metrics, num_workers, zero_m, local_scan, round_scan,
               sync="psum", a2a_schedule=(), a2a_masks=None, fused=False,
               overlap=False, compress="none"):
    """Row-sparse Kaczmarz with per-worker LOCAL sampling (CsrOp / EllOp).

    The wall-clock-faithful scheme: each worker samples its ``local_steps``
    rows ∝ its *own slab's* row norms, so every step is a useful update —
    contrast ``_dense_rk``, where each worker scans the global pick stream
    and masks out the (P-1)/P foreign picks.  Interleaving the P local
    streams round-robin gives a round-level shared stream of
    ``P * local_steps`` picks partitioned by owner, so the shared-stream
    bound applies to that stream length: ``scheduled_tau(P,
    P * local_steps, shared_stream=True) = P * local_steps - 1`` (a
    worker's read misses at most the other workers' (P-1)*local_steps
    current-round updates, which this bounds).  (The stationary row law is
    ∝ ||A_i||² *within* each slab; it matches Strohmer–Vershynin globally
    when the slabs carry equal norm mass — ``partition="balanced"`` makes
    that hold by construction.)  All-zero slabs are safe: ``sample_rows``
    falls back to uniform picks and the zero rows make the updates no-ops.

    Sync is the RK delta psum, or — ``sync="a2a"`` — the two-phase
    exchange over the column-slab neighbor graph: phase 1 reduces every
    column slab's per-worker deltas onto the slab's *owner* (worker c owns
    column slab c) with one (cs, k) ppermute rotation per shift, the owner
    accumulating in device order so the sum carries exactly the bits of the
    psum's left-to-right reduction; phase 2 broadcasts each owner's summed
    slab back to the workers whose rows reference it.  Slabs a worker never
    references stay stale — they are never read, and the returned iterate
    is assembled from the owners' slabs, so iterates and metrics are
    bitwise identical to the psum sync at a fraction of its wire volume.

    ``fused=True`` runs the local phase as one ``sweep_rows_rk_delta``
    launch per round: BOTH carries — the working replica and the round's
    delta — stay VMEM-resident across all ``local_steps`` updates (the
    ``banded_rk_sweep`` two-carry pattern on padded rows); iterates match
    the scan to roundoff (the kernel's per-column scatter is a sequence of
    row RMWs where the scan uses one segment scatter).

    ``overlap=True`` exchanges the delta ACCUMULATED IN round r-1 (carried
    through the round scan) while round r's sweep accumulates a fresh one,
    so foreign updates land one round late; the final round's delta is
    flushed with one trailing exchange after the scan so the returned
    iterate contains every update.  Staleness counters measure the
    per-round lag.

    ``compress`` shrinks the psum payload (a2a is forced back to psum by
    the caller — its bitwise-psum invariant cannot survive lossy bits):
    each worker sends its round delta bf16-rounded or int8-quantized and
    applies ``psum(sent) - sent`` — its OWN updates stay exact in its
    replica, only the foreign contributions arrive rounded, so the scheme
    perturbs exactly what the wire carries.  ``int8_ef`` additionally
    carries a per-worker error-feedback residual through the round scan
    (quantize ``delta + residual``, keep the quantization error as the
    next residual — Karimireddy-style EF), so dropped bits are re-sent
    rather than lost; the residual is flushed with one exact trailing
    psum after the scan (after the overlap flush, when both compose), so
    the RETURNED iterate contains every update while the per-round
    metrics keep measuring the true compressed trajectory.
    """
    m, k = b.shape
    n = x0.shape[0]
    if m % num_workers:
        raise ValueError(
            f"worker count ({num_workers}) must divide the row count ({m})")
    vals, cols = op.padded_rows()
    rn = op.row_norms_sq()
    round_keys = jax.random.split(key, rounds)
    use_a2a = sync == "a2a"
    if use_a2a:
        assert n % num_workers == 0, (n, num_workers)  # caller fell back
        reduce_scheds, bcast_scheds = a2a_schedule
    cs = n // num_workers if n % num_workers == 0 else None
    if a2a_masks is None:
        a2a_masks = jnp.zeros((num_workers, max(num_workers - 1, 0)), bool)
    if num_workers == 1 or use_a2a:
        # P = 1 has no collective to compress; a2a was already forced back
        # to psum by the caller.  Normalizing here keeps the carries clean.
        compress = "none"
    use_ef = compress == "int8_ef"

    def worker(vals_sh, cols_sh, b_sh, rn_sh, masks_sh, keys, x0_full,
               xs_full):
        # vals_sh/cols_sh: (slab, width); rn_sh: (slab,); x0/xs replicated.
        w = jax.lax.axis_index(axis)
        rn_safe = jnp.where(rn_sh > 0, rn_sh, 1.0)

        def col_slab(v, c0):
            return jax.lax.dynamic_slice_in_dim(v, c0 * cs, cs, 0)

        def refresh(xw, delta):
            if num_workers == 1:
                return xw
            if not use_a2a:
                return xw + (jax.lax.psum(delta, axis) - delta)
            # Phase 1 — reduce-to-owner.  terms[s] is the slab-w delta of
            # worker (w - s) mod P (zeros when that worker never references
            # slab w: skipped pairs receive ppermute's zero fill, exactly
            # the all-zero delta the psum would have added).
            own = col_slab(delta, w)
            terms = [own]
            for si, perm in enumerate(reduce_scheds):
                sent = col_slab(delta, (w + si + 1) % num_workers)
                terms.append(jax.lax.ppermute(sent, axis, perm) if perm
                             else jnp.zeros_like(own))
            stack = jnp.stack(terms)               # indexed by shift s
            # Accumulate in DEVICE order v = 0..P-1 (term index (w - v) mod
            # P) — the order the psum reduces in, so S carries its bits.
            total = jnp.take(stack, jnp.mod(w, num_workers), axis=0)
            for v in range(1, num_workers):
                total = total + jnp.take(stack, jnp.mod(w - v, num_workers),
                                         axis=0)
            # Owner applies its summed slab locally...
            xw = jax.lax.dynamic_update_slice_in_dim(
                xw, col_slab(xw, w) + (total - own), w * cs, 0)
            # ...phase 2 — broadcast to the slab's readers, who apply the
            # same (S - own contribution) correction where accepted.
            for si, perm in enumerate(bcast_scheds):
                if not perm:
                    continue
                recv = jax.lax.ppermute(total, axis, perm)
                src = jnp.mod(w - si - 1, num_workers)
                cur = col_slab(xw, src)
                upd = cur + (recv - col_slab(delta, src))
                xw = jax.lax.dynamic_update_slice_in_dim(
                    xw, jnp.where(masks_sh[0, si], upd, cur), src * cs, 0)
            return xw

        def wire(payload, resid):
            """(bytes actually sent, next EF residual) for one payload.

            Identity under compress="none"; bf16 rounds the payload; int8
            EF quantizes (payload + residual) and keeps the quantization
            error as the next residual so no update is permanently lost.
            """
            if compress == "bf16":
                return compression.bf16_roundtrip_array(payload), resid
            if use_ef:
                corrected = payload + resid
                sent = compression.roundtrip_array(corrected)
                return sent, corrected - sent
            return payload, resid

        def local_phase(xw, rkey):
            rkey = jax.random.fold_in(rkey, w)
            picks = sample_rows(rkey, rn_sh, local_steps)
            delta = pvary(jnp.zeros_like(xw), (axis,))
            if fused:
                from repro.kernels import ops
                return ops.sweep_rows_rk_delta(
                    vals_sh, cols_sh, b_sh, rn_safe, xw, delta, picks,
                    beta=beta)

            def step(carry, li):
                xw, delta = carry
                vr, cr = vals_sh[li], cols_sh[li]
                g = (b_sh[li] - jnp.einsum("w,wk->k", vr, xw[cr])) / rn_safe[li]
                upd = beta * vr[:, None] * g[None, :]
                return (xw.at[cr].add(upd), delta.at[cr].add(upd)), None

            (xw, delta), _ = local_scan(step, (xw, delta), picks)
            return xw, delta

        def metrics(xw):
            if not with_metrics:
                return zero_m
            if xs_full is None:
                err = jnp.full((k,), jnp.nan, jnp.float32)
            elif cs is not None:
                # Column-slab-local error, psum'd: reads only the worker's
                # own (always fresh) slab, so it is exact — and bitwise
                # identical — under both syncs.
                e_own = col_slab(xw, w) - col_slab(xs_full, w)
                err = jax.lax.psum(jnp.einsum("sk,sk->k", e_own, e_own),
                                   axis)
            else:
                err = jnp.einsum("nk,nk->k", xw - xs_full, xw - xs_full)
            r_local = b_sh - jnp.einsum("sw,swk->sk", vals_sh, xw[cols_sh])
            rsq = jax.lax.psum(jnp.einsum("sk,sk->k", r_local, r_local), axis)
            return err, jnp.sqrt(rsq)

        if overlap:
            foreign = jnp.arange(num_workers) != w

            def round_body(carry, rkey):
                if use_ef:
                    xw, dprev, resid, cnt, seen = carry
                else:
                    (xw, dprev, cnt, seen), resid = carry, None
                cnt_all = jax.lax.all_gather(cnt, axis)
                lag = jax.lax.pmax(
                    jnp.sum(jnp.where(foreign, cnt_all - seen, 0)), axis)
                seen = jnp.where(foreign, cnt_all, seen)
                cnt = cnt + local_steps
                xw, delta = local_phase(xw, rkey)
                sent, resid = wire(dprev, resid)
                xw = refresh(xw, sent)       # previous round's deltas land
                carry = ((xw, delta, resid, cnt, seen) if use_ef
                         else (xw, delta, cnt, seen))
                return carry, (metrics(xw), lag)

            xw0 = pvary(x0_full, (axis,))
            d0 = pvary(jnp.zeros_like(xw0), (axis,))
            cnt0 = pvary(jnp.zeros((), jnp.int32), (axis,))
            seen0 = pvary(jnp.zeros((num_workers,), jnp.int32), (axis,))
            carry0 = ((xw0, d0, pvary(jnp.zeros_like(xw0), (axis,)), cnt0,
                       seen0) if use_ef else (xw0, d0, cnt0, seen0))
            (xw, dlast, *rest), ((errs, resids), lags) = round_scan(
                round_body, carry0, keys)
            # Flush the final round's in-flight delta — plus, under EF, the
            # outstanding residual — with one EXACT trailing exchange so
            # the returned iterate contains every update.
            xw = refresh(xw, dlast + rest[0] if use_ef else dlast)
            if use_a2a:
                return col_slab(xw, w), errs, resids, lags
            return xw, errs, resids, lags

        if use_ef:
            def round_body(carry, rkey):
                xw, resid = carry
                xw, delta = local_phase(xw, rkey)
                sent, resid = wire(delta, resid)
                xw = refresh(xw, sent)
                return (xw, resid), metrics(xw)

            xw0 = pvary(x0_full, (axis,))
            resid0 = pvary(jnp.zeros_like(xw0), (axis,))
            (xw, resid), (errs, resids) = round_scan(
                round_body, (xw0, resid0), keys)
            # Exact trailing flush of the outstanding residual: per-round
            # metrics above measured the compressed trajectory; the
            # returned iterate misses no update.
            return refresh(xw, resid), errs, resids

        def round_body(xw, rkey):
            xw, delta = local_phase(xw, rkey)
            sent, _ = wire(delta, None)
            xw = refresh(xw, sent)
            return xw, metrics(xw)

        xw, (errs, resids) = round_scan(round_body, pvary(x0_full, (axis,)),
                                        keys)
        if use_a2a:
            # Only the owners' slabs are globally consistent; reassemble
            # the full iterate from them (out_spec P(axis)).
            return col_slab(xw, w), errs, resids
        return xw, errs, resids

    out_specs = [P(axis, None) if use_a2a else P(None, None),
                 P(None, None), P(None, None)]
    if overlap:
        out_specs.append(P(None))
    mapped = shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P(axis),
                  P(axis, None), P(None), P(None, None), P(None, None)),
        out_specs=tuple(out_specs),
    )
    out = mapped(vals, cols, b, rn, a2a_masks, round_keys, x0, xs)
    return out if overlap else out + (None,)


# ---------------------------------------------------------------------------
# Batched-RHS chunked entry (the serving layer's engine surface)
# ---------------------------------------------------------------------------

_span = jax.profiler.TraceAnnotation


class BatchedSolveResult(NamedTuple):
    x: jax.Array          # (n, k) iterate after the last executed chunk
    resid: jax.Array      # (k,) ||b - A x||_2 at exit, per RHS column
    #: (k,) int32: record chunks each column needed to reach ITS tolerance
    #: (columns that never reached it report the chunks actually run)
    rounds: jax.Array
    converged: jax.Array  # (k,) bool, resid <= tol at some record point
    iters_run: int        # iterations actually executed (<= num_iters)


def sequential_chunk(op, b, x, picks, *, action: str, beta: float = 1.0,
                     block: int = 1, fused: bool | str = False):
    """One record chunk of the sequential engine: ``picks.shape[0]`` steps
    from iterate ``x``; returns ``(x_next, resid)`` with ``resid`` the
    per-column ``||b - A x_next||_2``.

    This is the unit the serving layer's executor cache compiles once and
    re-launches per record point: the same (operator layout, k bucket,
    chunk length, statics) always maps to the same executable.  The
    arithmetic is the one-shot impls' own — they are invoked with the
    pre-drawn pick slice — so chaining chunks over consecutive
    ``draw_picks`` slices bitwise-reproduces ``solve_sequential``.
    ``fused="auto"`` resolves through the tuning table, exactly as in
    ``solve_sequential``.
    """
    if fused == "auto":
        fused = (_fused_sweep_supported(op, action, block)
                 and tune_runtime.resolve_fused(fused, op, action))
    impl = _sequential_scan_impl
    if fused:
        _require_fused_kernel(op, action, block)
        impl = _sequential_fused_impl
    res = impl(op, b, x, None, action=action, key=jax.random.key(0),
               num_iters=picks.shape[0], beta=beta, block=block,
               record_every=0, picks=picks)
    return res.x, res.resid[-1]


def solve_batched(
    op,
    b: jax.Array,
    x0: jax.Array | None = None,
    *,
    action: str,
    key: jax.Array,
    num_iters: int,
    tol,
    record_every: int = 0,
    beta: float = 1.0,
    block: int = 1,
    fused: bool | str = False,
    chunk_fn=None,
    on_record=None,
) -> BatchedSolveResult:
    """Sequential solve over the multi-RHS axis with HETEROGENEOUS
    per-column tolerances and per-column round counts.

    ``tol`` is an absolute residual target — a scalar or a ``(k,)`` array,
    one entry per RHS column (the serving layer batches independent
    tenants' requests onto the columns, each with its own tolerance).
    The solve runs record chunk by record chunk (``record_every``
    iterations per chunk, validated by ``resolve_record_every``) and exits
    early once EVERY column has met its tolerance; a column's ``rounds``
    entry is the number of chunks it needed.  Columns are independent
    under both actions (the update ``gamma`` is computed per column), so
    each column's trajectory is bitwise the trajectory it would have had
    in any other batch with the same key — the property that makes
    cross-tenant batching safe.

    ``on_record(chunk_idx, x, resid, converged) -> bool`` is invoked at
    every record point (serving uses it to stream partial iterates and to
    enforce per-request deadlines); returning False stops the solve after
    that chunk.  ``chunk_fn`` overrides the chunk executor (the serving
    layer passes its cached executable); the default builds
    ``sequential_chunk`` with this call's statics.

    Under the JAX profiler the call shows as host spans on the calling
    thread: ``repro.solve_batched`` around ``repro.draw_picks``, one
    ``repro.chunk`` per chunk run — holding ``repro.picks_slice``,
    ``repro.launch``, ``repro.resid_wait`` (the one device-to-host copy)
    and ``repro.on_record`` — and ``repro.result``.
    """
    if num_iters <= 0:
        raise ValueError(f"num_iters must be > 0 (got {num_iters})")
    rec = resolve_record_every(num_iters, record_every)
    chunks = num_iters // rec
    k = b.shape[1]
    with _span("repro.solve_batched"):
        if x0 is None:
            n_x = op.shape[0] if action == "gs" else op.shape[1]
            x0 = jnp.zeros((n_x, k), b.dtype)
        tol_np = np.broadcast_to(np.asarray(tol, np.float32), (k,))
        with _span("repro.draw_picks"):
            picks = draw_picks(op, action, key, num_iters, block=block)
        if chunk_fn is None:
            chunk_fn = functools.partial(sequential_chunk, action=action,
                                         beta=beta, block=block, fused=fused)
        x, resid = x0, None
        rounds = np.zeros((k,), np.int32)
        conv = np.zeros((k,), bool)
        ran = 0
        for c in range(chunks):
            with _span("repro.chunk"):
                with _span("repro.picks_slice"):
                    ps = picks[c * rec:(c + 1) * rec]
                with _span("repro.launch"):
                    x, resid = chunk_fn(op, b, x, ps)
                ran = c + 1
                with _span("repro.resid_wait"):
                    resid_host = np.asarray(resid)
                newly = ~conv & (resid_host <= tol_np)
                rounds[newly] = ran
                conv |= newly
                with _span("repro.on_record"):
                    go = on_record is None or bool(
                        on_record(c, x, resid, conv.copy()))
            if conv.all() or not go:
                break
        rounds = np.where(conv, rounds, ran).astype(np.int32)
        with _span("repro.result"):
            return BatchedSolveResult(
                x=x, resid=resid, rounds=jnp.asarray(rounds),
                converged=jnp.asarray(conv), iters_run=ran * rec)


# ---------------------------------------------------------------------------
# Unified entry point: solve(problem, format=..., schedule=...)
# ---------------------------------------------------------------------------

def solve(
    problem,
    *,
    key: jax.Array,
    schedule: Schedule,
    format: str = "dense",
    action: str | None = None,
    mesh: jax.sharding.Mesh | None = None,
    axis: str = "workers",
    beta: float = 1.0,
    block: int = 128,
    bands: int = 2,
    width: int = 32,
    rows_per_panel: int | None = None,
    storage_dtype=None,
    gs_block: int = 1,
    x0: jax.Array | None = None,
    sync: str = "auto",
    fused: bool | str | None = None,
    unroll: bool = False,
    with_metrics: bool = True,
    delay_key: jax.Array | None = None,
    read_model: str = "consistent",
    delay_mode: str = "fixed",
    miss_prob: float = 0.5,
):
    """One front door for the whole solver family.

    ``problem`` is an ``SPDProblem`` (GS action by default) or an
    ``LSQProblem`` (Kaczmarz action by default).  ``format`` picks the
    operator ("dense", "banded", "ell", "csr"); ``schedule`` picks
    sequential / bounded-delay simulator / distributed execution (see
    ``Schedule``).  ``block``/``bands`` parameterize the banded format,
    ``width`` the ELL format, ``rows_per_panel`` the CSR panel layout
    (``None``, the default, asks the tuning table for the measured
    winner at this shape and falls back to 8 — the panel grouping never
    changes per-row summation order, so the choice is layout-only),
    ``storage_dtype`` the precision the operator's coefficients are held
    in (``None`` keeps the input dtype — bitwise-unchanged; the iterate,
    ``b`` and all accumulation stay f32 regardless), and ``gs_block`` the
    dense/CSR block-GS action granularity.  ``fused`` overrides
    ``schedule.fused`` (``None`` defers to the schedule): run inner loops
    as fused Pallas sweep kernels (an error where the action × format has
    none);
    ``"auto"`` runs the tuning table's measured fused-vs-scan winner
    (see ``Schedule``).
    """
    if action is None:
        action = "rk" if hasattr(problem, "sigma_min") else "gs"
    # Validate the EFFECTIVE configuration, once: the ``fused`` keyword
    # override is folded into the schedule BEFORE ``validate()``, so an
    # invalid effective combination (e.g. ``fused=True`` forced onto the
    # bounded-delay simulator) fails here with a schedule-level error
    # instead of surviving to a late warning path.
    schedule = schedule if fused is None else schedule._replace(fused=fused)
    schedule.validate()
    use_fused = schedule.fused
    if rows_per_panel is None:
        rows_per_panel = tune_runtime.tuned_rows_per_panel(
            problem.A.shape[0], storage_dtype) or 8
    op = as_operator(problem.A, format, block=block, bands=bands, width=width,
                     rows_per_panel=rows_per_panel,
                     storage_dtype=storage_dtype)
    if x0 is None:
        # Shape/dtype from b and the operator, NOT from x_star: real
        # workloads carry x_star=None (nobody knows the solution), and
        # the RK iterate lives in column space while b lives in row space.
        n_x = op.shape[0] if action == "gs" else op.shape[1]
        x0 = jnp.zeros((n_x, problem.b.shape[1]), problem.b.dtype)

    if schedule.distributed:
        if mesh is None:
            raise ValueError("a distributed Schedule needs a mesh")
        return solve_distributed(
            op, problem.b, x0, problem.x_star, action=action, key=key,
            mesh=mesh, axis=axis, rounds=schedule.rounds,
            local_steps=schedule.local_steps, block=gs_block, beta=beta,
            sync=sync, partition=schedule.partition, fused=use_fused,
            overlap=schedule.overlap, compress=schedule.compress,
            unroll=unroll, with_metrics=with_metrics)
    if schedule.tau > 0:
        if delay_key is None:
            raise ValueError("the bounded-delay simulator needs a delay_key")
        return solve_async_sim(
            op, problem.b, x0, problem.x_star, action=action, key=key,
            delay_key=delay_key, num_iters=schedule.num_iters,
            tau=schedule.tau, beta=beta, read_model=read_model,
            delay_mode=delay_mode, miss_prob=miss_prob,
            record_every=schedule.record_every)
    return solve_sequential(
        op, problem.b, x0, problem.x_star, action=action, key=key,
        num_iters=schedule.num_iters, beta=beta, block=gs_block,
        record_every=schedule.record_every, fused=use_fused)


__all__ = [
    "BatchedSolveResult",
    "BlockBandedOp",
    "CsrOp",
    "DenseOp",
    "EllOp",
    "ParallelSolveResult",
    "Schedule",
    "SolveResult",
    "as_operator",
    "draw_picks",
    "record_metrics",
    "resolve_record_every",
    "sample_rows",
    "scheduled_tau",
    "sequential_chunk",
    "solve",
    "solve_async_sim",
    "solve_batched",
    "solve_distributed",
    "solve_sequential",
]
