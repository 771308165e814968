"""Autotuning subsystem (repro.tune): table round-trip, the bitwise
fallback/forcing contract at every dispatch seam, and the committed CPU
smoke table's acceptance pins.

The contract under test (DESIGN.md §9): explicit choices (``fused``
bools, ``variant=``, ``skip_empty=``, integer ``rows_per_panel``) are
FORCED and bitwise-pinned to pre-autotune behavior; ``"auto"`` resolves
through the active table silently; a missing entry (or no table) runs
today's hardcoded default, bitwise-unchanged.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_array_equal

from conftest import run_forced_device_script
from repro.core import CsrOp, Schedule, random_sparse_spd, solve
from repro.core.engine import solve_sequential
from repro.tune import (TuneKey, TuningTable, shape_bucket, use_table)
from repro.tune import runtime
from repro.tune.table import default_path


def _table_for(op, *entries) -> TuningTable:
    """A synthetic in-memory table with the given (key, choice) pairs."""
    t = TuningTable(backend="cpu", device_kind="cpu", interpret_mode=True,
                    jax_version=jax.__version__)
    for key, choice in entries:
        t.record(key, choice, {choice: 1.0})
    return t


# -- table mechanics ---------------------------------------------------------

def test_shape_bucket_rounds_up_to_power_of_two():
    assert shape_bucket(1) == "n1"
    assert shape_bucket(1000) == "n1024"
    assert shape_bucket(1024) == "n1024"
    assert shape_bucket(1025) == "n2048"


def test_table_roundtrip_identical_choices(tmp_path):
    t = TuningTable(backend="cpu", device_kind="cpu", interpret_mode=True,
                    jax_version="0.x")
    t.record(TuneKey("sweep", "CsrOp", "gs", "n256", "f32"), "scan",
             {"scan": 10.0, "fused": 20.0})
    t.record(TuneKey("matvec", "CsrOp", "-", "n256", "f32"), "sliced",
             {"sliced": 1.0})
    path = t.save(tmp_path / "TUNE_test.json")
    back = TuningTable.load(path)
    assert back.choices() == t.choices()
    assert back.backend == "cpu" and back.interpret_mode is True


def test_table_load_drops_entries_on_version_mismatch(tmp_path):
    t = TuningTable(backend="cpu", version=999)
    t.record(TuneKey("sweep", "CsrOp", "gs", "n256", "f32"), "fused",
             {"fused": 1.0})
    back = TuningTable.load(t.save(tmp_path / "TUNE_old.json"))
    assert back.entries == {}   # fallback contract: unknown schema -> defaults


def test_default_table_missing_vs_unloadable(tmp_path, monkeypatch):
    """A missing table means "no table" (every seam defaults); a table
    that exists but does not load is an error, not a silent default."""
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(tmp_path / "TUNE_none.json"))
    monkeypatch.setattr(runtime, "_default_cache", runtime._UNSET)
    assert runtime._load_default() is None
    bad = tmp_path / "TUNE_bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(bad))
    monkeypatch.setattr(runtime, "_default_cache", runtime._UNSET)
    with pytest.raises(ValueError):
        runtime._load_default()


def test_schedule_rejects_non_tristate_fused():
    with pytest.raises(ValueError):
        Schedule(num_iters=8, fused="always").validate()


# -- fused-vs-scan seam ------------------------------------------------------

def _gs_problem(n=96):
    prob = random_sparse_spd(n, row_nnz=8, n_rhs=2, seed=0)
    cop = CsrOp.from_dense(prob.A)
    return prob, cop, jnp.zeros_like(prob.x_star)


def _seq(cop, prob, x0, fused):
    return solve_sequential(cop, prob.b, x0, prob.x_star, action="gs",
                            key=jax.random.key(3), num_iters=64,
                            record_every=32, fused=fused)


def test_resolve_fused_explicit_bools_never_overridden():
    _prob, cop, _x0 = _gs_problem()
    steer = _table_for(cop, (runtime.sweep_key(cop, "gs"), "fused"))
    with use_table(steer):
        assert runtime.resolve_fused(False, cop, "gs") is False
        assert runtime.resolve_fused(True, cop, "gs") is True
        assert runtime.resolve_fused("auto", cop, "gs") is True
    with use_table(None):
        assert runtime.resolve_fused("auto", cop, "gs") is False


def test_auto_missing_entry_is_bitwise_todays_default():
    prob, cop, x0 = _gs_problem()
    with use_table(None):
        auto = _seq(cop, prob, x0, "auto")
        scan = _seq(cop, prob, x0, False)
    assert_array_equal(np.asarray(auto.x), np.asarray(scan.x))
    assert_array_equal(np.asarray(auto.resid), np.asarray(scan.resid))


def test_auto_with_table_is_bitwise_the_forced_variant():
    prob, cop, x0 = _gs_problem()
    for choice, forced in (("fused", True), ("scan", False)):
        t = _table_for(cop, (runtime.sweep_key(cop, "gs"), choice))
        with use_table(t):
            auto = _seq(cop, prob, x0, "auto")
        explicit = _seq(cop, prob, x0, forced)
        assert_array_equal(np.asarray(auto.x), np.asarray(explicit.x))


# -- CSR matvec seam ---------------------------------------------------------

def _patchy_csr(n=96):
    prob = random_sparse_spd(n, row_nnz=8, n_rhs=1, seed=1)
    A = np.array(prob.A)
    A[0:32] = 0.0          # whole empty panels (rows_per_panel=8)
    return CsrOp.from_dense(jnp.asarray(A)), prob.x_star


def test_matvec_missing_entry_matches_prepr_auto_selection():
    prob, cop, _x0 = _gs_problem()
    pop, x = _patchy_csr()
    with use_table(None):
        # dense panels: auto picked the plain sliced kernel
        assert_array_equal(np.asarray(cop.matvec(prob.x_star)),
                           np.asarray(cop.matvec(prob.x_star,
                                                 skip_empty=False)))
        # empty panels present: auto picked the predicated twin
        assert_array_equal(np.asarray(pop.matvec(x)),
                           np.asarray(pop.matvec(x, skip_empty=True)))


def test_matvec_table_entry_steers_to_segsum_bitwise():
    prob, cop, _x0 = _gs_problem()
    t = _table_for(cop, (runtime.matvec_key(cop), "segsum"))
    with use_table(t):
        steered = cop.matvec(prob.x_star)
    assert_array_equal(np.asarray(steered),
                       np.asarray(cop.matvec_segsum(prob.x_star)))


def test_matvec_explicit_variant_beats_contrary_table():
    prob, cop, _x0 = _gs_problem()
    t = _table_for(cop, (runtime.matvec_key(cop), "segsum"))
    with use_table(t):
        forced = cop.matvec(prob.x_star, variant="sliced")
        skipped = cop.matvec(prob.x_star, skip_empty=False)
    with use_table(None):
        default = cop.matvec(prob.x_star, skip_empty=False)
    assert_array_equal(np.asarray(forced), np.asarray(default))
    assert_array_equal(np.asarray(skipped), np.asarray(default))


def test_matvec_unknown_variant_raises():
    prob, cop, _x0 = _gs_problem()
    with pytest.raises(ValueError, match="unknown matvec variant"):
        cop.matvec(prob.x_star, variant="blocked")


# -- rows_per_panel seam -----------------------------------------------------

def test_tuned_rows_per_panel_is_layout_only_bitwise():
    prob, _cop, _x0 = _gs_problem()
    t = _table_for(None, (runtime.panel_key(prob.A.shape[0]), "4"))
    with use_table(t):
        assert runtime.tuned_rows_per_panel(prob.A.shape[0]) == 4
        auto = solve(prob, key=jax.random.key(7), format="csr",
                     schedule=Schedule(num_iters=48, record_every=48))
    with use_table(None):
        assert runtime.tuned_rows_per_panel(prob.A.shape[0]) is None
        explicit = solve(prob, key=jax.random.key(7), format="csr",
                         rows_per_panel=4,
                         schedule=Schedule(num_iters=48, record_every=48))
        default = solve(prob, key=jax.random.key(7), format="csr",
                        schedule=Schedule(num_iters=48, record_every=48))
    # table-driven == explicitly forced == the default-8 layout: panel
    # grouping never changes per-row summation order
    assert_array_equal(np.asarray(auto.x), np.asarray(explicit.x))
    assert_array_equal(np.asarray(auto.x), np.asarray(default.x))


# -- the committed CPU smoke table -------------------------------------------

def test_committed_cpu_table_pins_scan_for_banded_gs_n1024():
    """The acceptance pin: on the CPU interpret-mode shape the committed
    table selects the scan engine for banded GS at the n=1024 bucket
    (the recorded BENCH inversion of the TPU design point)."""
    table = TuningTable.load(default_path("cpu"))
    assert table.backend == "cpu" and table.interpret_mode is True
    key = TuneKey("sweep", "BlockBandedOp", "gs", "n1024", "f32")
    assert table.lookup(key) == "scan"
    # and through the runtime seam an n<=1024 banded op resolves to scan
    class _Shim:                    # sweep_key reads class name + shape only
        shape = (1024, 1024)
    _Shim.__name__ = "BlockBandedOp"
    with use_table(table):
        assert runtime.fused_choice(_Shim(), "gs") == "scan"
        assert runtime.resolve_fused("auto", _Shim(), "gs") is False


# -- every strategy row resolves silently ------------------------------------

AUTO_RESOLVES_SCRIPT = """
    import warnings
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (BlockBandedOp, CsrOp, DenseOp, EllOp,
                            block_banded_spd, random_sparse_spd)
    from repro.core.engine import _DISTRIBUTED_STRATEGIES, solve_distributed
    from repro.kernels.bbmv import dense_to_bands
    from repro.launch.mesh import make_host_mesh
    from repro.tune import use_table

    mesh = make_host_mesh(4)
    bb = block_banded_spd(64, block=8, bands=1, n_rhs=2, seed=2)
    sp = random_sparse_spd(64, row_nnz=8, n_rhs=2, seed=0)
    width = int((np.asarray(sp.A) != 0).sum(1).max())
    ops = {
        "DenseOp": (DenseOp(sp.A), sp),
        "BlockBandedOp": (BlockBandedOp(
            dense_to_bands(bb.A, bands=1, block=8), bands=1), bb),
        "EllOp": (EllOp.from_dense(sp.A, width=width), sp),
        "CsrOp": (CsrOp.from_dense(sp.A), sp),
    }
    for table in (None,):   # missing-entry path: auto must stay silent
        with use_table(table):
            for (action, fmt, sync), kind in sorted(
                    _DISTRIBUTED_STRATEGIES.items()):
                op, prob = ops[fmt]
                x0 = jnp.zeros_like(prob.x_star)
                with warnings.catch_warnings(record=True) as caught:
                    warnings.simplefilter("always")
                    solve_distributed(
                        op, prob.b, x0, prob.x_star, action=action,
                        sync=sync, fused="auto", key=jax.random.key(1),
                        mesh=mesh, rounds=1, local_steps=4)
                fused_warns = [w for w in caught
                               if "fused" in str(w.message)]
                assert not fused_warns, (action, fmt, sync, fused_warns)
    print("AUTO_RESOLVES_OK")
"""


@pytest.mark.slow
def test_fused_auto_resolves_without_warnings_on_every_strategy_row():
    """``fused="auto"`` means nothing was forced: no strategy row —
    including the ones with no fused local phase — may emit the
    fused-fallback ``UserWarning`` on the auto path."""
    run_forced_device_script(AUTO_RESOLVES_SCRIPT, marker="AUTO_RESOLVES_OK")
