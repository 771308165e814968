"""Pallas TPU kernels: fused GS/RK sweeps over padded sparse rows.

The engine's sequential inner loop executes one row action per
``lax.scan`` step, round-tripping the whole iterate through HBM between
steps — per-update overhead the paper's cost model (per-nonzero, Sec. 4-5)
assumes away, and exactly what Chow et al.'s asynchronous-Richardson
argument says kills asynchronous methods in practice.  These kernels run an
*entire sweep* (``len(picks)`` sequential row updates) in a single Pallas
launch:

* the iterate ``x`` stays resident in VMEM across all steps: it enters as
  an HBM operand aliased to the output, is copied once into a single VMEM
  scratch at step 0 and copied back at the last step — one VMEM copy per
  carry, so step s+1 sees step s's update (sequential semantics, tau = 0);
* the pre-sampled pick sequence is **scalar-prefetched**, and the picked
  row's value/column window streams HBM->SMEM through prefetch-driven
  index maps (``(m, 1, width)`` views, so the block's last two dims are the
  array's own), its ``b`` row HBM->VMEM — the per-step HBM traffic is the
  picked row's Θ(width) window and nothing else;
* the gather ``x[cols_r]`` is ``width`` single-row VMEM reads addressed by
  the SMEM column ids (Mosaic has no vector gather from a VMEM ref), and
  ``<A_r, x>`` accumulates them in slot order with the SMEM values as
  scalar factors.

The row storage is the *padded-row* form shared by ``CsrOp.padded_rows()``
and ``EllOp`` (whose stored ``vals``/``cols`` already are it): per-row
fixed-width value/column windows with global column ids, padding slots
carrying value 0 so they contribute exact zeros.  SMEM holds 32-bit words,
so the wrappers widen low-precision values to f32 and compact int16 ids to
int32 before the launch (exact; the accumulation is f32 either way).

Actions (the update arithmetic of ``core.engine.solve_sequential``):

* GS  — ``gamma = b[r] - <A_r, x>``; ``x[base + r] += beta * gamma``;
* RK  — ``g = (b[r] - <A_r, x>) / ||A_r||²``; ``x[cols_r] += beta * A_r g``
  (the scatter runs as ``width`` sequential single-row VMEM
  read-modify-writes, not an HBM scatter).

The inner product sums in slot order where the scan engine's ``einsum``
picks its own order, so iterates match the scan to f32 roundoff, not
bitwise.

The GS **write base** is what lets the distributed local phases fuse: a
worker holds a *slab* of rows (local ids ``[0, slab)``) but updates a
full-length replica at global rows ``base + r``.  The base is a traced
scalar (``jax.lax.axis_index`` under shard_map), so it rides the scalar-
prefetch channel next to the pick stream rather than being baked into the
kernel.  ``base = 0`` recovers the sequential square-system sweep exactly.
The RK sibling ``sweep_rows_rk_delta`` needs no base — Kaczmarz writes
land at global *column* ids — but carries a second VMEM-resident output,
the round's delta window, so the distributed strategies can sync
``delta`` at round end (the ``banded_rk_sweep`` two-carry pattern).

VMEM budget: each carry costs ``n * roundup(k, 128) * 4`` bytes (the lane
axis pads to 128), and the scoped VMEM limit is raised to fit the carries
(``resident_vmem_limit``).  DESIGN.md §4 records the largest ``n * k`` the
v5e compiler accepts per kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM left for the pipeline's row/b buffers and Mosaic's own scratch on
#: top of the resident carries.
_VMEM_HEADROOM = 4 * 1024 * 1024

#: Longest pick stream one launch prefetches: SMEM is 1 MiB on v5e and
#: also holds the row windows, so longer streams run as several launches.
MAX_PICKS_PER_LAUNCH = 1 << 17


def resident_vmem_limit(carries) -> int:
    """Scoped VMEM limit (bytes) for kernels holding ``carries`` — the
    (n, k) arrays kept VMEM-resident — as one lane-padded copy each."""
    need = 0
    for c in carries:
        n, k = c.shape
        need += n * (-(-k // 128) * 128) * jnp.dtype(c.dtype).itemsize
    return need + _VMEM_HEADROOM


def resident_sweep(step, prefetch, row_streams, carries, *, interpret):
    """Launch ``len(prefetch[0])`` sequential grid steps of ``step`` over
    VMEM-resident ``carries``; returns the updated carries (a tuple).

    ``prefetch``: scalar-prefetch operands, the pick stream first.
    ``row_streams``: ``(array, memory_space)`` pairs of shape (m, 1, w);
    step s sees row ``prefetch[0][s]`` of each as a (1, w) ref.
    ``step(s, *prefetch_refs, *row_refs, *carry_refs)`` updates the VMEM
    carries in place.  Streams longer than ``MAX_PICKS_PER_LAUNCH`` run
    as consecutive launches over the same carries (same update order).
    """
    steps = prefetch[0].shape[0]
    if steps > MAX_PICKS_PER_LAUNCH:
        for lo in range(0, steps, MAX_PICKS_PER_LAUNCH):
            seg = prefetch[0][lo:lo + MAX_PICKS_PER_LAUNCH]
            carries = resident_sweep(step, (seg, *prefetch[1:]), row_streams,
                                     carries, interpret=interpret)
        return carries
    n_pref, n_rows, n_carry = len(prefetch), len(row_streams), len(carries)

    def kernel(*refs):
        pref = refs[:n_pref]
        rows = refs[n_pref:n_pref + n_rows]
        hbm_in = refs[n_pref + n_rows:n_pref + n_rows + n_carry]
        hbm_out = refs[n_pref + n_rows + n_carry:n_pref + n_rows + 2 * n_carry]
        vmem = refs[n_pref + n_rows + 2 * n_carry:]
        s = pl.program_id(0)

        @pl.when(s == 0)
        def _load():
            for src, dst in zip(hbm_in, vmem):
                pltpu.sync_copy(src, dst)

        step(s, *pref, *rows, *vmem)

        @pl.when(s == pl.num_programs(0) - 1)
        def _store():
            for src, dst in zip(vmem, hbm_out):
                pltpu.sync_copy(src, dst)

    def row_spec(arr, space):
        return pl.BlockSpec((pl.Squeezed(), 1, arr.shape[2]),
                            lambda s, idx, *_: (idx[s], 0, 0),
                            memory_space=space)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pref,
        grid=(steps,),
        in_specs=([row_spec(a, sp) for a, sp in row_streams]
                  + [pl.BlockSpec(memory_space=pl.ANY)] * n_carry),
        out_specs=tuple([pl.BlockSpec(memory_space=pl.ANY)] * n_carry),
        scratch_shapes=[pltpu.VMEM(c.shape, c.dtype) for c in carries],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(jax.ShapeDtypeStruct(c.shape, c.dtype)
                        for c in carries),
        input_output_aliases={n_pref + n_rows + i: i for i in range(n_carry)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=resident_vmem_limit(carries)),
        interpret=interpret,
    )(*prefetch, *(a for a, _ in row_streams), *carries)


def _row_windows(vals, cols):
    """(m, 1, width) f32 values / int32 column ids for the SMEM row stream."""
    m, width = vals.shape
    return (vals.astype(jnp.float32).reshape(m, 1, width),
            cols.astype(jnp.int32).reshape(m, 1, width))


def _row_dot(vals_ref, cols_ref, xv, width: int):
    """``<A_r, x>`` as a (1, k) f32 row: ``width`` single-row reads of the
    resident iterate, summed in slot order."""
    acc = vals_ref[0, 0] * xv[pl.ds(cols_ref[0, 0], 1), :]
    for j in range(1, width):
        acc = acc + vals_ref[0, j] * xv[pl.ds(cols_ref[0, j], 1), :]
    return acc


def _scatter_row(vals_ref, cols_ref, xv, g, beta: float, width: int):
    """``x[cols_r] += beta * A_r g`` as ``width`` sequential row RMWs.
    Real columns of one row are distinct; padding slots (value 0) add
    exact zeros wherever they land, so this matches ``x.at[cols].add``."""
    for j in range(width):
        c = pl.ds(cols_ref[0, j], 1)
        xv[c, :] = xv[c, :] + (beta * vals_ref[0, j]) * g


@functools.partial(jax.jit, static_argnames=("beta", "interpret"))
def sweep_rows_gs(
    vals: jax.Array,
    cols: jax.Array,
    b: jax.Array,
    x: jax.Array,
    picks: jax.Array,
    *,
    beta: float = 1.0,
    write_base: jax.Array | int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Apply ``len(picks)`` sequential coordinate-GS row updates; returns x.

    vals/cols: (m, width) padded row windows (global column ids);
    b: (m, k); x: (n, k); picks: (steps,) int32 row ids in [0, m).

    ``write_base`` offsets every write: pick ``r`` updates row
    ``write_base + r`` of ``x`` (gathers stay at the stored global column
    ids).  This is the slab offset of the distributed local phases — a
    worker's rows are local ids but its replica is full-length — and may
    be a traced scalar (``axis_index`` under shard_map); the caller must
    keep ``write_base + r`` inside [0, n).  Default 0: the sequential
    square-system sweep.
    """
    m, width = vals.shape
    n, k = x.shape
    assert b.shape == (m, k)
    if picks.shape[0] == 0:
        return x
    base = jnp.asarray(write_base, jnp.int32).reshape((1,))
    vw, cw = _row_windows(vals, cols)

    def step(s, idx_ref, base_ref, vals_ref, cols_ref, b_ref, xv):
        r = pl.ds(base_ref[0] + idx_ref[s], 1)
        gamma = b_ref[...] - _row_dot(vals_ref, cols_ref, xv, width)
        xv[r, :] = xv[r, :] + beta * gamma

    (x,) = resident_sweep(
        step, (picks.astype(jnp.int32), base),
        [(vw, pltpu.SMEM), (cw, pltpu.SMEM), (b.reshape(m, 1, k), None)],
        [x], interpret=interpret)
    return x


def _rk_step(s, idx_ref, vals_ref, cols_ref, b_ref, rn_ref, *carries,
             beta: float, width: int):
    """One Kaczmarz row action applied to every carry (the working
    replica, plus the round delta in the two-carry form); the residual
    reads the first carry."""
    g = (b_ref[...] - _row_dot(vals_ref, cols_ref, carries[0], width)) \
        / rn_ref[0, 0]
    for xv in carries:
        _scatter_row(vals_ref, cols_ref, xv, g, beta, width)


def _rk_sweep(vals, cols, b, rn, carries, picks, *, beta, interpret):
    m, width = vals.shape
    k = b.shape[1]
    assert b.shape == (m, k) and rn.shape == (m,)
    vw, cw = _row_windows(vals, cols)
    step = functools.partial(_rk_step, beta=beta, width=width)
    return resident_sweep(
        step, (picks.astype(jnp.int32),),
        [(vw, pltpu.SMEM), (cw, pltpu.SMEM), (b.reshape(m, 1, k), None),
         (rn.astype(jnp.float32).reshape(m, 1, 1), pltpu.SMEM)],
        carries, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("beta", "interpret"))
def sweep_rows_rk(
    vals: jax.Array,
    cols: jax.Array,
    b: jax.Array,
    rn: jax.Array,
    x: jax.Array,
    picks: jax.Array,
    *,
    beta: float = 1.0,
    interpret: bool = False,
) -> jax.Array:
    """Apply ``len(picks)`` sequential Kaczmarz row updates; returns x.

    vals/cols: (m, width) padded row windows; b: (m, k); rn: (m,) squared
    row norms (the caller's sampling distribution — passed in so the
    divisor matches the scan engine's bit-for-bit); x: (n, k);
    picks: (steps,) int32 row ids in [0, m).
    """
    if picks.shape[0] == 0:
        return x
    (x,) = _rk_sweep(vals, cols, b, rn, [x], picks, beta=beta,
                     interpret=interpret)
    return x


@functools.partial(jax.jit, static_argnames=("beta", "interpret"))
def sweep_rows_rk_delta(
    vals: jax.Array,
    cols: jax.Array,
    b: jax.Array,
    rn: jax.Array,
    x: jax.Array,
    d: jax.Array,
    picks: jax.Array,
    *,
    beta: float = 1.0,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Apply ``len(picks)`` sequential Kaczmarz row updates to the
    (replica, round-delta) pair in one launch; returns ``(x, d)``.

    The distributed form of ``sweep_rows_rk``: every update lands in both
    carries (both stay VMEM-resident across all steps), so the caller can
    psum / a2a-exchange the accumulated ``d`` at round end.  vals/cols:
    (m, width) padded row windows with global column ids — a worker's
    slab; no write base is needed because Kaczmarz writes land at the
    stored (global) column ids.  rn: (m,) squared row norms, zero rows
    pre-guarded by the caller.
    """
    assert d.shape == x.shape
    if picks.shape[0] == 0:
        return x, d
    return _rk_sweep(vals, cols, b, rn, [x, d], picks, beta=beta,
                     interpret=interpret)
