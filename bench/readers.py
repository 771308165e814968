"""What the per-layer metric files in ``bench/metrics/`` compute.  Each
returns None where its run has nothing to read; a reader never makes up
a 0."""
from __future__ import annotations

from bench import trace_reduce, work

#: the fused coordinate-GS sweep kernel's instruction in the device trace:
#: the custom call takes the name of the jitted ``sweep_rows_gs``
#: (``sweep_rows_gs.2`` on one chip), with whatever prefix or suffix a
#: caller's program adds to it
SWEEP_GS_OP = r".*sweep_rows_gs.*"


def mean(values):
    return sum(values) / len(values) if values else None


def sweeps_to_tol(r):
    """Mean over solves of the sweeps the worst column needed."""
    return mean(r.counters.get("sweeps_to_tol", []))


def idle_pct(r):
    if r.trace is None or not r.window_s:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.window_s)


def sweep_seconds(r):
    """Kernel seconds and updates, or None."""
    if r.trace is None or not r.counters.get("updates"):
        return None
    t = trace_reduce.op_seconds(r.trace, SWEEP_GS_OP)
    if t <= 0:
        return None
    return t, r.counters["updates"]


def us_per_update(r):
    got = sweep_seconds(r)
    return None if got is None else 1e6 * got[0] / got[1]


def sweep_roofline_pct(r):
    got = sweep_seconds(r)
    if got is None or r.peaks is None:
        return None
    w = work.gs_update(r.counters["matrix"], r.counters["k"],
                       r.counters["storage_dtype"])
    share, _ = work.roofline_share(w, got[1], got[0], r.peaks)
    return 100.0 * share
