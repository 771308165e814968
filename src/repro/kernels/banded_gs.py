"""Pallas TPU kernel: banded randomized block Gauss-Seidel sweep — the
inner loop of the halo-exchange distributed solver (core/parallel_rgs.py,
§Perf s4-s6) as a single fused kernel.

Per grid step s (sequential on TPU):
    bi   = picks[s]                       # random local block-row (prefetched)
    g    = b[bi] - sum_d A_bands[bi, d] @ x[(bi + d)*block : ...]
    x[(bi + bands)*block : ...] += beta * g

``x`` is the halo-padded window ((nb_local + 2*bands)*block, k) and stays
VMEM-resident across the whole sweep (BlockSpec maps the full array at every
step), so successive steps see each other's updates — sequential randomized
block GS, the tau = 0 best case of the paper's analysis.  The A-band panel
for the chosen row streams HBM->VMEM via the scalar-prefetch index map: the
per-step HBM traffic is exactly the (2*bands+1) tiles + nothing else, which
is what makes the memory roofline attainable (no score/convert spills —
contrast the unfused jnp path).

Validity masking: border blocks whose band column falls outside the matrix
contribute zero (the tiles are zero-padded by ``pack_bands``), so no branch
is needed inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, a_ref, b_ref, x_ref, o_ref, *, block: int, bands: int,
            beta: float):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = x_ref[...]

    bi = idx_ref[s]
    width = 2 * bands + 1
    acc = b_ref[...].astype(jnp.float32)              # (block, k)
    for d in range(width):
        xs = o_ref[pl.ds((bi + d) * block, block), :]
        acc -= jnp.dot(a_ref[0, d], xs, preferred_element_type=jnp.float32)
    rows = pl.ds((bi + bands) * block, block)
    o_ref[rows, :] = o_ref[rows, :] + beta * acc.astype(o_ref.dtype)


#: the sweep wrappers share one jit signature: geometry + step size static
_STATIC_ARGS = ("block", "bands", "beta", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC_ARGS)
def banded_gs_sweep(
    A_bands: jax.Array,
    b: jax.Array,
    xw: jax.Array,
    picks: jax.Array,
    *,
    block: int = 128,
    bands: int = 2,
    beta: float = 1.0,
    interpret: bool = False,
) -> jax.Array:
    """Apply ``len(picks)`` banded block-GS steps; returns the updated window.

    A_bands: (nb_local, 2*bands+1, block, block) — zero-padded border tiles;
    b: (nb_local*block, k); xw: ((nb_local + 2*bands)*block, k) halo window;
    picks: (steps,) int32 local block-row ids in [0, nb_local).
    """
    nb_local, width = A_bands.shape[:2]
    n_local, k = b.shape
    assert width == 2 * bands + 1
    assert n_local == nb_local * block
    assert xw.shape[0] == n_local + 2 * bands * block
    steps = picks.shape[0]
    if steps == 0:
        return xw

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, width, block, block),
                         lambda s, idx: (idx[s], 0, 0, 0)),
            pl.BlockSpec((block, k), lambda s, idx: (idx[s], 0)),
            pl.BlockSpec(xw.shape, lambda s, idx: (0, 0)),
        ],
        out_specs=pl.BlockSpec(xw.shape, lambda s, idx: (0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, block=block, bands=bands, beta=beta),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(xw.shape, xw.dtype),
        interpret=interpret,
    )(picks, A_bands, b, xw)


def _rk_kernel(idx_ref, gate_ref, a_ref, b_ref, rn_ref, x_ref, d_ref,
               xo_ref, do_ref, *, block: int, bands: int, beta: float):
    """One masked banded Kaczmarz panel step (grid step s, sequential).

    Carries TWO VMEM-resident vectors: the working window ``xo`` and the
    round's delta window ``do`` (what the distributed engine psums at round
    end).  ``gate_ref[s]`` is 1 when this worker owns the picked panel and
    0 otherwise — foreign picks perform the same reads but apply exact-zero
    updates, mirroring the scan strategy's masked arithmetic bit for bit.
    """
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        xo_ref[...] = x_ref[...]
        do_ref[...] = d_ref[...]

    bi = idx_ref[s]
    width = 2 * bands + 1
    acc = b_ref[...].astype(jnp.float32)              # (block, k)
    for d in range(width):
        xs = xo_ref[pl.ds((bi + d) * block, block), :]
        acc -= jnp.dot(a_ref[0, d], xs, preferred_element_type=jnp.float32)
    g = acc.astype(xo_ref.dtype)
    betam = jnp.where(gate_ref[s] > 0, beta, 0.0)
    gn = (betam * g / rn_ref[...]).astype(jnp.float32)  # rn_ref: (block, 1)
    for d in range(width):
        contrib = jnp.dot(a_ref[0, d].T, gn,
                          preferred_element_type=jnp.float32)
        contrib = contrib.astype(xo_ref.dtype)
        rows = pl.ds((bi + d) * block, block)
        xo_ref[rows, :] = xo_ref[rows, :] + contrib
        do_ref[rows, :] = do_ref[rows, :] + contrib


@functools.partial(jax.jit, static_argnames=_STATIC_ARGS)
def banded_rk_sweep(
    A_bands: jax.Array,
    b: jax.Array,
    rn: jax.Array,
    xw: jax.Array,
    dw: jax.Array,
    picks: jax.Array,
    gates: jax.Array,
    *,
    block: int = 128,
    bands: int = 2,
    beta: float = 1.0,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Apply ``len(picks)`` masked banded Kaczmarz panel steps in one
    launch; returns the updated (window, delta-window) pair.

    The RK extension of ``banded_gs_sweep``: the residual read is the same
    Θ(width) tile sweep, but the update is the damped Cimmino-within-panel
    action ``x += beta * A_B^T diag(1/||a_i||²) (b - A x)_B``, whose writes
    reach ``bands`` block columns either side of the panel — all inside the
    halo-padded window, which (with the delta) stays VMEM-resident for the
    whole sweep.

    A_bands: (nb_local, 2*bands+1, block, block) — border tiles zero-padded
    (``pack_bands_local``); b: (nb_local*block, k); rn: (nb_local, block)
    squared row norms (zero rows pre-guarded to 1 by the caller);
    xw/dw: ((nb_local + 2*bands)*block, k); picks: (steps,) int32 local
    block-row ids in [0, nb_local); gates: (steps,) int32 ownership mask.
    """
    nb_local, width = A_bands.shape[:2]
    n_local, k = b.shape
    assert width == 2 * bands + 1
    assert n_local == nb_local * block
    assert xw.shape == dw.shape == (n_local + 2 * bands * block, k)
    assert rn.shape == (nb_local, block)
    steps = picks.shape[0]
    if steps == 0:
        return xw, dw

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, width, block, block),
                         lambda s, idx, gate: (idx[s], 0, 0, 0)),
            pl.BlockSpec((block, k), lambda s, idx, gate: (idx[s], 0)),
            # (nb_local, block, 1) view: the block's last two dims are the
            # array's own, and the column broadcasts over the k lanes.
            pl.BlockSpec((pl.Squeezed(), block, 1),
                         lambda s, idx, gate: (idx[s], 0, 0)),
            pl.BlockSpec(xw.shape, lambda s, idx, gate: (0, 0)),
            pl.BlockSpec(dw.shape, lambda s, idx, gate: (0, 0)),
        ],
        out_specs=(pl.BlockSpec(xw.shape, lambda s, idx, gate: (0, 0)),
                   pl.BlockSpec(dw.shape, lambda s, idx, gate: (0, 0))),
    )
    return pl.pallas_call(
        functools.partial(_rk_kernel, block=block, bands=bands, beta=beta),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(xw.shape, xw.dtype),
                   jax.ShapeDtypeStruct(dw.shape, dw.dtype)),
        interpret=interpret,
    )(picks.astype(jnp.int32), gates.astype(jnp.int32), A_bands, b,
      rn.reshape(nb_local, block, 1), xw, dw)


def pack_bands_local(A_bands_global: jax.Array, lo_block: int, nb_local: int,
                     nb: int, bands: int) -> jax.Array:
    """Slice a worker's rows out of global band tiles, zeroing tiles whose
    column block falls outside [0, nb) (border validity baked into data)."""
    tiles = A_bands_global[lo_block:lo_block + nb_local]
    width = 2 * bands + 1
    out = []
    for bi in range(nb_local):
        row = []
        for d in range(width):
            cb = lo_block + bi + d - bands
            t = tiles[bi, d]
            row.append(t if 0 <= cb < nb else jnp.zeros_like(t))
        out.append(jnp.stack(row))
    return jnp.stack(out)
