"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def block_gs_sweep_ref(A, b, x, blocks, *, block: int, beta: float = 1.0):
    """Sequential randomized block-GS steps (same semantics as the kernel)."""
    def step(x, bi):
        rows = bi * block + jnp.arange(block)
        g = b[rows] - A[rows] @ x
        return x.at[rows].add(beta * g), None

    x, _ = jax.lax.scan(step, x, blocks)
    return x


def bbmv_ref(A_dense, x):
    """y = A @ x on the dense equivalent of the banded matrix."""
    return A_dense @ x


def spmv_ell_ref(vals, cols, x):
    """y = A @ x from (rows, width) value/column windows: a gather of
    ``x[cols]`` reduced over the window axis (``EllOp``'s storage, and
    ``CsrOp.padded_rows()``).  Values up-cast to f32 before contracting,
    matching the kernels' f32 accumulation (identity for f32 storage)."""
    return jnp.einsum("nw,nwk->nk", vals.astype(jnp.float32),
                      x[cols]).astype(x.dtype)
