"""What the program's own marks own in a ``jax.profiler`` trace.

The program marks its work for the profiler:

* device scopes, ``jax.named_scope("repro.<x>")``, which land in each
  HLO instruction's ``op_name`` metadata: ``repro.row_windows``
  (``CsrOp.padded_rows``), ``repro.sweep_operands`` (the kernel's operand
  re-layout), ``repro.sweep`` (the sweep kernel) and ``repro.residual``
  (``record_metrics``);
* host spans, ``TraceAnnotation("repro.<x>")``, in ``solve_batched``:
  ``repro.solve_batched`` around ``repro.draw_picks``, one ``repro.chunk``
  per chunk (``repro.picks_slice``, ``repro.launch``,
  ``repro.resid_wait``, ``repro.on_record``) and ``repro.result``.

``reduce`` takes planes in ``trace_reduce``'s form and returns
``trace_reduce.reduce``'s numbers with these beside them:

* ``scope_s``: per-device seconds of device operations by innermost
  ``repro.*`` scope, with the rest under ``"(no repro scope)"``;
* ``module_ops``: ``[module, instruction, scope, seconds]``, costliest
  first; an operation is keyed by module and instruction, since
  ``fusion.N`` names repeat across modules;
* ``idle_by_program_span``: the idle gaps of ``trace_reduce.reduce``
  labelled by the innermost ``repro.*`` host span open at the gap's
  midpoint, ``"(no repro span)"`` where none is;
* ``program_spans``: how many spans of each ``repro.*`` name the trace
  holds (``repro.chunk`` counts the chunks run).

An operation's module is the ``XLA Modules`` event it starts in, and its
scope comes from ``hlo_scopes``: a map from (module, instruction) to scope
read from compiled HLO text (``hlo_scopes()``), where a fusion takes its
root instruction's scope.  On a v5e the trace itself carries no scope: an
``XLA Ops`` event holds the instruction's text without its metadata, and
the device plane has no name-scope line.  The text is that of the
executables the program launched: ``launched`` records the engine's jitted
calls as set-up makes them, and ``compiled_text`` compiles those calls
again.  ``scope_coverage`` is the share of the mapped modules' device time
whose instructions the map names; below ``MIN_COVERAGE`` (the map is of
another executable) there is no ``scope_s`` and no ``module_ops``, so a
reader finds nothing rather than 0 ms.  Without a map there are neither.

Run as a script, it runs one traced window of a cell as ``bench/run.py
--trace 1`` does, and prints these numbers and the per-chunk metrics
below as one JSON line:

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import bisect
import contextlib
import os
import re
import sys

if __name__ == "__main__":   # run as a script: the checkout and its src/
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import trace_reduce  # noqa: E402

PROGRAM_PREFIX = "repro."
NO_SCOPE = "(no repro scope)"
NO_SPAN = "(no repro span)"
#: the device scopes the per-chunk metrics read
SCOPES = ("repro.row_windows", "repro.sweep_operands", "repro.sweep",
          "repro.residual")
#: the least share of the mapped modules' time the map must name
MIN_COVERAGE = 0.95

_SCOPE = re.compile(r"repro\.\w+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE_ID = re.compile(r"\(\d+\)$")


def innermost_scope(op_name: str) -> str | None:
    """``jit(f)/repro.sweep/jit(g)/repro.sweep_operands/reshape`` ->
    ``repro.sweep_operands``."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def module_name(event_name: str) -> str:
    """``jit__sequential_fused_impl(12)`` -> ``jit__sequential_fused_impl``."""
    return _MODULE_ID.sub("", event_name.strip())


# --- compiled HLO text ----------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def hlo_scopes(text: str) -> dict[tuple[str, str], str | None]:
    """``{(module, instruction): scope}`` for every instruction of compiled
    HLO ``text`` (one or more modules), ``None`` where no ``repro.*``
    scope holds it.  A fusion is keyed by its own name and takes the scope
    of the root instruction of the computation it calls."""
    out: dict[tuple[str, str], str | None] = {}
    for chunk in re.split(r"^(?=HloModule )", text, flags=re.M):
        if not chunk.startswith("HloModule "):
            continue
        module = chunk.split(None, 2)[1].rstrip(",")
        own: dict[str, str | None] = {}
        calls: dict[str, str] = {}
        roots: dict[str, str] = {}
        comp = None
        for line in chunk.splitlines()[1:]:
            head = _COMP.match(line)
            if head and "=" not in line.split("{", 1)[0]:
                comp = head.group(1)
                continue
            m = _INSTR.match(line)
            if not m or comp is None:
                continue
            name, rhs = m.group(1), m.group(2)
            meta = _OP_NAME.search(rhs)
            own[name] = innermost_scope(meta.group(1)) if meta else None
            if line.lstrip().startswith("ROOT"):
                roots[comp] = name
            called = _CALLS.search(rhs)
            if called and " fusion(" in f" {rhs}":
                calls[name] = called.group(1)
        for name, scope in own.items():
            if name in calls and calls[name] in roots:
                scope = own.get(roots[calls[name]]) or scope
            out[(module, name)] = scope
    return out


@contextlib.contextmanager
def launched(module):
    """Inside the block, record the first call of each jitted function of
    ``module`` (``{name: (function, args, kwargs)}``) as the program
    makes it."""
    jitted = {k: v for k, v in vars(module).items()
              if callable(v) and hasattr(v, "lower")}
    calls: dict = {}

    def recorder(name, fn):
        def call(*args, **kwargs):
            calls.setdefault(name, (fn, args, kwargs))
            return fn(*args, **kwargs)
        return call

    for name, fn in jitted.items():
        setattr(module, name, recorder(name, fn))
    try:
        yield calls
    finally:
        for name, fn in jitted.items():
            setattr(module, name, fn)


def compiled_text(calls) -> str:
    """Compiled HLO text of the calls ``launched`` recorded."""
    return "\n".join(fn.lower(*args, **kwargs).compile().as_text()
                     for fn, args, kwargs in calls.values())


# --- reduction -------------------------------------------------------------

def _device_ops(plane):
    """``[(module, instruction, start, duration)]`` of a device plane."""
    lines = {line["name"]: line["events"] for line in plane["lines"]}
    modules = sorted((s, s + d, module_name(name))
                     for name, s, d in lines.get("XLA Modules", []))
    starts = [s for s, _, _ in modules]
    out = []
    for text, s, d in lines.get(trace_reduce.OPS_LINE, []):
        i = bisect.bisect_right(starts, s) - 1
        module = modules[i][2] if i >= 0 and modules[i][1] >= s else ""
        out.append((module, trace_reduce.op_name(text), s, d))
    return out


def _device_planes(planes, n_devices):
    devs = sorted((int(m.group(1)), p) for p in planes
                  if (m := trace_reduce.DEVICE_PLANE.match(p["name"])))
    return [p for _, p in devs[:n_devices]]


def program_spans(planes) -> list[tuple[float, float, str]]:
    spans = []
    for p in planes:
        if trace_reduce.DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(PROGRAM_PREFIX):
                    spans.append((start, start + dur, name))
    return sorted(spans)


def _idle_gaps(per_dev, lo, hi):
    gaps = []
    for ops in per_dev:
        merged = trace_reduce.union((s, s + d) for *_, s, d in ops)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[j], edges[j + 1])
                 for j in range(0, len(edges), 2) if edges[j + 1] > edges[j]]
    return sorted(gaps)


def reduce(planes, n_devices=None, hlo_scopes=None) -> dict:
    """``trace_reduce.reduce``'s numbers and the program's (module doc)."""
    summary = trace_reduce.reduce(planes, n_devices)
    per_dev = [_device_ops(p) for p in _device_planes(planes, n_devices)]
    nd = len(per_dev)
    ns = 1e-9
    out = dict(summary)
    if hlo_scopes:
        mapped = {m for m, _ in hlo_scopes}
        in_mapped = named = 0.0
        scope_ns: dict[str, float] = {}
        op_ns: dict[tuple[str, str, str], float] = {}
        for ops in per_dev:
            for module, instr, _, d in ops:
                if module in mapped:
                    in_mapped += d
                    named += d if (module, instr) in hlo_scopes else 0.0
                scope = hlo_scopes.get((module, instr)) or NO_SCOPE
                scope_ns[scope] = scope_ns.get(scope, 0.0) + d
                key = (module, instr, scope)
                op_ns[key] = op_ns.get(key, 0.0) + d
        out["scope_coverage"] = named / in_mapped if in_mapped else None
        if in_mapped and named / in_mapped >= MIN_COVERAGE:
            out["scope_s"] = {k: v * ns / nd for k, v in scope_ns.items()}
            out["module_ops"] = sorted(
                ([m, i, sc, v * ns / nd] for (m, i, sc), v in op_ns.items()),
                key=lambda row: -row[3])

    spans = program_spans(planes)
    bench = trace_reduce.host_spans(planes)
    every = [(s, s + d) for ops in per_dev for *_, s, d in ops]
    lo = min([s for s, _ in every] + [s for s, _, _ in spans + bench])
    hi = max([e for _, e in every] + [e for _, e, _ in spans + bench])
    gaps = _idle_gaps(per_dev, lo, hi)
    idle: dict[str, float] = {}
    for (s, e), name in zip(gaps, label_points(
            [(s + e) / 2 for s, e in gaps], spans)):
        idle[name] = idle.get(name, 0.0) + (e - s)
    counts: dict[str, int] = {}
    for _, _, name in spans:
        counts[name] = counts.get(name, 0) + 1
    out["idle_by_program_span"] = sorted(
        ([k, v * ns / nd] for k, v in idle.items()), key=lambda kv: -kv[1])
    out["program_spans"] = counts
    return out


def label_points(points, spans) -> list[str]:
    """``trace_reduce.label_points`` with this module's empty label."""
    return [NO_SPAN if name == trace_reduce.NO_SPAN else name
            for name in trace_reduce.label_points(points, spans)]


# --- per-chunk readers: a ``harness.Readings`` with ``reduce``'s trace ---

def chunks(r):
    """The chunks the traced window ran: its ``repro.chunk`` spans."""
    if r.trace is None:
        return None
    return r.trace.get("program_spans", {}).get("repro.chunk") or None


def scope_ms_per_chunk(scope: str):
    def read(r):
        n = chunks(r)
        if n is None or "scope_s" not in r.trace:
            return None
        return 1e3 * r.trace["scope_s"].get(scope, 0.0) / n
    return read


row_windows_ms_per_chunk = scope_ms_per_chunk("repro.row_windows")
operands_ms_per_chunk = scope_ms_per_chunk("repro.sweep_operands")
residual_ms_per_chunk = scope_ms_per_chunk("repro.residual")


def host_gap_ms_per_chunk(r):
    """Device idle inside the program's spans, per chunk."""
    n = chunks(r)
    if n is None:
        return None
    idle = sum(v for k, v in r.trace["idle_by_program_span"] if k != NO_SPAN)
    return 1e3 * idle / n


#: metric name (without its cell suffix) -> reader
READERS = {
    "operator.row_windows_ms_per_chunk": row_windows_ms_per_chunk,
    "kernel.operands_ms_per_chunk": operands_ms_per_chunk,
    "engine.residual_ms_per_chunk": residual_ms_per_chunk,
    "engine.host_gap_ms_per_chunk": host_gap_ms_per_chunk,
}


# --- one traced window ----------------------------------------------------

def run(name: str, seed: int, seconds: float) -> dict:
    """One traced window of cell ``name``, set up as ``harness.run_cell``
    sets it up; the program's numbers beside the benchmark's."""
    import tempfile
    import time

    import jax
    from bench import harness
    from repro.core import engine

    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell, config, traffic = harness.resolve_cell(spec, name)
    devices = harness.require_chips(int(cell["chips"]))
    harness.enable_cache()
    ctx = harness.Ctx(cell, config, traffic, seed, devices)
    entry = harness.load_module(os.path.join(harness.BENCH, "entries",
                                             traffic["entry"] + ".py"))
    harness.make_data(ctx)
    with launched(engine) as calls:
        state = entry.setup(ctx)
    with tempfile.TemporaryDirectory(prefix="bench_scopes_") as tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
        w0 = time.perf_counter()
        out = entry.window(ctx, state, seconds)
        window_s = time.perf_counter() - w0
        jax.profiler.stop_trace()
        planes = trace_reduce.from_profile(trace_reduce.find_xplane(tdir))
    hlo = compiled_text(calls)
    entry.teardown(ctx, state)
    summary = reduce(planes, n_devices=len(devices),
                     hlo_scopes=hlo_scopes(hlo))
    print(f"window {window_s:.3f}s, {out['attempted']} solves",
          file=sys.stderr, flush=True)

    readings = harness.Readings(
        out["counters"], summary, config,
        trace_reduce.load_peaks(devices[0].device_kind), window_s)
    busy = summary["busy_s"]
    scope_s = summary.get("scope_s")
    rec = int(config["program"]["record_every"])
    return {
        "workload": name, "seed": seed, "window_s": window_s,
        "solves": out["attempted"], "failed": out["failed"],
        "device": {"kind": devices[0].device_kind, "busy_s": busy},
        "chunks_counted": out["counters"]["updates"] // rec,
        "per_chunk": {k: read(readings) for k, read in READERS.items()},
        "per_layer": harness.read_per_layer(spec, name, readings),
        "launched": sorted(calls),
        "scope_coverage": summary["scope_coverage"],
        "scope_share_of_busy": (sum(scope_s.get(s, 0.0) for s in SCOPES)
                                / busy if scope_s else None),
        "scope_s": scope_s,
        "module_ops": summary.get("module_ops", [])[:24],
        "idle_by_span": summary["idle_by_span"],
        "idle_by_program_span": summary["idle_by_program_span"],
        "program_spans": summary["program_spans"],
    }


def main(argv=None) -> int:
    import argparse
    import json
    import time

    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description="one traced window of a cell, "
                                 "reduced by the program's spans and scopes")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    try:
        result = run(args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    result["command_s"] = time.perf_counter() - t0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
