"""``correct``: sound runs pass, and the control and each fault the
cells can have fail it.  The cells' own entries, traffic and
checks run at a small size on the CPU, the chip look skipped; each fault
breaks the timed path underneath the harness."""
import jax
import numpy as np
import pytest

from bench import reference
from bench.tests import small
from repro.kernels import ops

CELLS = list(small.SIZES)
#: cells with more than one column, which can lose half of them
MULTI = [c for c in CELLS if small.SIZES[c].get("k", 1) > 1]


@pytest.fixture(autouse=True)
def fresh_jit():
    # a fault must not be hidden by a program traced before it was planted
    jax.clear_caches()
    yield
    jax.clear_caches()


def unchanged(vals, cols, b, x, picks, **kw):
    return x


def half_left_out(real):
    def sweep(vals, cols, b, x, picks, **kw):
        h = x.shape[1] // 2 or 1
        return x.at[:, :h].set(real(vals, cols, b, x, picks, **kw)[:, :h])
    return sweep


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = small.run(cell)
    assert r["correct"], r["checks"]
    assert r["checks"]["resid_gap"]["value"] < reference.GAP_LIMIT


@pytest.mark.parametrize("cell", CELLS)
def test_control_bfloat16_storage_fails(cell):
    r = small.run(cell, storage_dtype="bfloat16")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_step_returning_its_state_fails(cell, monkeypatch):
    monkeypatch.setattr(ops, "sweep_rows_gs", unchanged)
    r = small.run(cell)
    assert not r["correct"] and r["failed"] > 0, r["checks"]


@pytest.mark.parametrize("cell", MULTI)
def test_half_the_columns_left_out_fails(cell, monkeypatch):
    monkeypatch.setattr(ops, "sweep_rows_gs", half_left_out(
        ops.sweep_rows_gs))
    r = small.run(cell)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_solve_answer_altered_fails(cell, monkeypatch):
    from bench.entries import solve
    real = solve.solve_batched

    def altered(*a, **kw):
        res = real(*a, **kw)
        return res._replace(x=res.x.at[3].add(1e-2))

    monkeypatch.setattr(solve, "solve_batched", altered)
    r = small.run(cell)
    assert not r["correct"] and r["failed"] == 0, r["checks"]
    assert r["checks"]["resid_gap"]["value"] > reference.GAP_LIMIT
