"""Process-wide active tuning table + the dispatch-seam lookups.

The engine's dispatch seams (``solve_sequential`` / ``solve_distributed``
/ ``sequential_chunk`` fused-vs-scan, ``CsrOp.matvec`` variant selection,
``solve``'s ``rows_per_panel`` default) call the helpers here instead of
hardcoding a pick.  Resolution order, everywhere:

1. an explicit caller choice (``fused=True/False``, ``variant=...``,
   ``skip_empty=True/False``, an integer ``rows_per_panel``) is FORCED —
   bitwise-pinned to the pre-autotune behavior, never overridden;
2. otherwise the active table's entry for the site's ``TuneKey`` wins;
3. no entry (or no table) -> today's hardcoded default, bitwise-unchanged.

The active table defaults to the committed ``TUNE_<backend>.json`` for
the current backend (lazily loaded once; missing file -> no table; a file
that fails to load raises).  The
``REPRO_TUNE_TABLE`` environment variable overrides: a path loads that
file, ``off``/``none``/``0`` disables lookups entirely.  Tests and the
autotuner swap tables with ``use_table`` / ``set_active_table``.

Every lookup reads only static operator metadata (``shape``, leaf
*dtypes*, class name), so the helpers are safe under ``jax.jit`` tracing
— they never concretize a leaf.
"""
from __future__ import annotations

import contextlib
import os

from repro.tune.table import (
    TuneKey, TuningTable, default_path, shape_bucket, storage_key)

_ENV_VAR = "REPRO_TUNE_TABLE"
_UNSET = object()           # "never set: lazily load the committed default"
_active = _UNSET
_default_cache = _UNSET     # memoized committed-table load (None = no file)


def _load_default() -> TuningTable | None:
    global _default_cache
    if _default_cache is _UNSET:
        env = os.environ.get(_ENV_VAR, "")
        if env.lower() in ("off", "none", "0"):
            _default_cache = None
        else:
            path = env or default_path()
            try:
                _default_cache = TuningTable.load(path)
            except FileNotFoundError:
                # no table for this backend: every lookup defaults; a
                # table that exists but does not load is an error
                _default_cache = None
    return _default_cache


def active_table() -> TuningTable | None:
    """The table lookups consult; None disables every lookup (pure
    hardcoded-default behavior, the pre-autotune engine)."""
    if _active is _UNSET:
        return _load_default()
    return _active


def set_active_table(table: TuningTable | None):
    """Install ``table`` process-wide; returns the previous setting (which
    may be the internal "unset" sentinel — pass it back to restore)."""
    global _active
    prev = _active
    _active = table
    return prev


@contextlib.contextmanager
def use_table(table: TuningTable | None):
    """Scoped ``set_active_table`` (tests, the autotuner's forced runs)."""
    prev = set_active_table(table)
    try:
        yield table
    finally:
        set_active_table(prev)


# -- key derivation from live operators -------------------------------------

def _op_storage_key(op) -> str:
    """'f32'/'bf16' from the operator's stored coefficient dtype (dtype is
    static metadata — present on tracers, so this never concretizes)."""
    for attr in ("data", "vals", "A_bands", "A"):
        leaf = getattr(op, attr, None)
        if leaf is not None:
            return storage_key(leaf.dtype)
    return "f32"


def sweep_key(op, action: str) -> TuneKey:
    return TuneKey("sweep", type(op).__name__, action,
                   shape_bucket(op.shape[0]), _op_storage_key(op))


def matvec_key(op) -> TuneKey:
    return TuneKey("matvec", type(op).__name__, "-",
                   shape_bucket(op.shape[0]), _op_storage_key(op))


def panel_key(m: int, storage_dtype=None) -> TuneKey:
    return TuneKey("panel", "CsrOp", "-", shape_bucket(m),
                   storage_key(storage_dtype))


# -- dispatch-seam lookups ---------------------------------------------------

def fused_choice(op, action: str) -> str | None:
    """The table's fused-vs-scan winner ("fused"/"scan") or None."""
    t = active_table()
    return None if t is None else t.lookup(sweep_key(op, action))


def resolve_fused(fused, op, action: str) -> bool:
    """Resolve a ``Schedule.fused`` value at a dispatch seam.

    Explicit booleans pass through untouched (the bitwise pin);
    ``"auto"`` returns the table's measured winner, or False — today's
    default engine — when no entry exists.
    """
    if fused == "auto":
        return fused_choice(op, action) == "fused"
    return bool(fused)


def matvec_variant(op) -> str | None:
    """The table's CSR matvec variant for ``op``'s bucket, or None."""
    t = active_table()
    return None if t is None else t.lookup(matvec_key(op))


def tuned_rows_per_panel(m: int, storage_dtype=None) -> int | None:
    """The table's ``rows_per_panel`` winner for an m-row CSR build, or
    None (-> the caller's hardcoded default)."""
    t = active_table()
    choice = None if t is None else t.lookup(panel_key(m, storage_dtype))
    return None if choice is None else int(choice)
