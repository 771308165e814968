"""Compile-only rehearsal: the fused sweep kernels of the main solve path,
compiled by the TPU compiler for a described (not attached) v5e chip at
the reference scale chip_smoke.py runs — n = 16384 unknowns, k = 51
right-hand sides, and one record chunk of the fused engine around them.
A tiling, SMEM or VMEM refusal shows up here, on the CPU, before it costs
a chip run.  Nothing executes, so these tests say nothing about results or
speed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under several pytest
workers every worker imports this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import banded_gs, block_gs, sweep_csr

N, K = 16384, 51
#: row_cap of random_sparse_spd(16384, row_nnz=16) — the padded-row width
#: the CSR/ELL sweeps stream at that size
WIDTH = 51
BLOCK, BANDS = 128, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]


f32, i32 = jnp.float32, jnp.int32
NB = N // BLOCK
_ROWS = ((N, WIDTH), f32), ((N, WIDTH), i32)
_VEC = ((N, K), f32)
_WIN = ((N + 2 * BANDS * BLOCK, K), f32)
_BANDS = ((NB, 2 * BANDS + 1, BLOCK, BLOCK), f32)

#: kernel -> (jitted callable with statics bound, argument shapes); one
#: sweep's worth of picks each
KERNELS = {
    "sweep_rows_gs": (
        functools.partial(sweep_csr.sweep_rows_gs, beta=0.9),
        [*_ROWS, _VEC, _VEC, ((N,), i32)]),
    "sweep_rows_rk": (
        functools.partial(sweep_csr.sweep_rows_rk, beta=0.9),
        [*_ROWS, _VEC, ((N,), f32), _VEC, ((N,), i32)]),
    "sweep_rows_rk_delta": (
        functools.partial(sweep_csr.sweep_rows_rk_delta, beta=0.9),
        [*_ROWS, _VEC, ((N,), f32), _VEC, _VEC, ((N,), i32)]),
    "banded_gs_sweep": (
        functools.partial(banded_gs.banded_gs_sweep, block=BLOCK,
                          bands=BANDS, beta=0.9),
        [_BANDS, _VEC, _WIN, ((NB,), i32)]),
    "banded_rk_sweep": (
        functools.partial(banded_gs.banded_rk_sweep, block=BLOCK,
                          bands=BANDS, beta=0.9),
        [_BANDS, _VEC, ((NB, BLOCK), f32), _WIN, _WIN, ((NB,), i32),
         ((NB,), i32)]),
    "block_gs_sweep": (
        functools.partial(block_gs.block_gs_sweep, block=BLOCK, beta=0.9),
        [((N, N), f32), _VEC, _VEC, ((NB,), i32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_fused_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = KERNELS[name]
    lowered = jax.jit(fn).lower(*_shapes(one_chip, *specs))
    assert "tpu_custom_call" in lowered.as_text()   # a Pallas kernel, not XLA
    compiled = lowered.compile()   # raises what the chip's compiler refuses
    assert compiled.memory_analysis() is not None


#: (k, row_cap, panel_width) of the two benchmark systems at n = 16384:
#: random_sparse_spd's k = 51 system and HPCG's 27-point stencil, k = 1
CHUNKS = [(51, 52, 384), (1, 27, 256)]


@pytest.mark.parametrize("k,cap,width", CHUNKS)
def test_fused_chunk_residual_has_no_scatter(k, cap, width, one_chip):
    """One record chunk of the fused engine over a ``CsrOp`` (the sweep,
    then ``record_metrics``' residual) compiles with no scatter: the
    residual reduces the row windows, and shares the sweep's two
    row-window gathers, so the chunk gathers three times (values, ids,
    ``x[cols]``)."""
    from repro.core import engine
    from repro.core.operators import CsrOp
    flat = (N // 8) * width + cap
    data, ids, rows, start, nnz = _shapes(
        one_chip, ((flat,), f32), ((flat,), i32), ((flat,), i32), ((N,), i32),
        ((N,), i32))
    op = CsrOp(data, ids, rows, start, nnz, shape=(N, N), nnz=N * cap,
               row_cap=cap, rows_per_panel=8, panel_width=width)
    b, x, picks = _shapes(one_chip, ((N, k), f32), ((N, k), f32), ((N,), i32))
    text = engine._sequential_fused_impl.lower(
        op, b, x, None, action="gs", key=jax.random.key(0), num_iters=N,
        beta=1.0, block=1, record_every=0, picks=picks).compile().as_text()
    ops = re.findall(r"= [^=]*? (gather|scatter)\(", text)
    assert ops.count("scatter") == 0
    assert ops.count("gather") == 3
