"""The program's profiler marks: named scopes in the fused chunk's HLO and
host spans around ``solve_batched``'s chunk loop.

Scopes (``jax.named_scope``) change HLO metadata only; spans
(``TraceAnnotation``) are host events of the JAX profiler.  Neither may
move an iterate, so a profiled solve is compared bitwise with an
unprofiled one.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import random_sparse_spd
from repro.core.engine import _sequential_fused_impl, solve_batched
from repro.core.operators import CsrOp, EllOp

N, K, REC = 128, 3, 128
CHUNK_CHILDREN = ("repro.picks_slice", "repro.launch", "repro.resid_wait",
                  "repro.on_record")


@pytest.fixture(scope="module")
def prob():
    return random_sparse_spd(N, row_nnz=4, n_rhs=K, seed=3)


def _op(fmt, A, storage_dtype=None):
    if fmt == "csr":
        return CsrOp.from_dense(A, storage_dtype=storage_dtype)
    width = int((np.asarray(A) != 0).sum(1).max())
    return EllOp.from_dense(A, width=width, storage_dtype=storage_dtype)


def _chunk_hlo(op, b):
    """Compiled HLO of the chunk executable ``sequential_chunk`` launches."""
    return _sequential_fused_impl.lower(
        op, b, b, None, action="gs", key=jax.random.key(0), num_iters=REC,
        beta=1.0, block=1, record_every=0,
        picks=jax.ShapeDtypeStruct((REC,), jnp.int32)).compile().as_text()


@pytest.mark.parametrize("fmt, want", [
    ("csr", {"repro.row_windows", "repro.sweep_operands", "repro.sweep",
             "repro.residual"}),
    # ELL storage already is the row-window form: no per-chunk gather
    ("ell", {"repro.sweep_operands", "repro.sweep", "repro.residual"}),
])
def test_chunk_hlo_carries_program_scopes(prob, fmt, want):
    # bfloat16 storage makes the operand re-layout a real cast that
    # survives optimization (at float32 it is a free reshape)
    op = _op(fmt, prob.A, storage_dtype="bfloat16")
    text = _chunk_hlo(op, prob.b)
    op_names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    found = set(re.findall(r"repro\.\w+", op_names))
    assert want <= found
    if fmt == "ell":
        assert "repro.row_windows" not in found


def _events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.end_ns))
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _solve(op, b, tol):
    return solve_batched(op, b, action="gs", key=jax.random.key(5),
                         num_iters=8 * REC, tol=tol, record_every=REC,
                         fused=True, on_record=lambda *a: True)


def test_profiled_solve_spans_and_bitwise_iterates(prob, tmp_path):
    op = _op("csr", prob.A)
    # a per-column target the solve meets part-way, so it exits early
    tol = 0.05 * np.linalg.norm(np.asarray(prob.b), axis=0)
    plain = _solve(op, prob.b, tol)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _solve(op, prob.b, tol)
        jax.block_until_ready(traced.x)
    finally:
        jax.profiler.stop_trace()
    assert 0 < traced.iters_run < 8 * REC
    np.testing.assert_array_equal(np.asarray(plain.x), np.asarray(traced.x))
    np.testing.assert_array_equal(np.asarray(plain.rounds),
                                  np.asarray(traced.rounds))

    ev = _events(tmp_path)
    by = {}
    for e in ev:
        by.setdefault(e[0], []).append(e)
    (solve,) = by["repro.solve_batched"]
    chunks = by["repro.chunk"]
    assert len(chunks) == traced.iters_run // REC
    for name in ("repro.draw_picks", "repro.result"):
        (span,) = by[name]
        assert _inside(span, solve)
    for c in chunks:
        assert _inside(c, solve)
        for child in CHUNK_CHILDREN:
            assert sum(_inside(e, c) for e in by[child]) == 1
    for child in CHUNK_CHILDREN:
        assert len(by[child]) == len(chunks)
