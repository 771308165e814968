"""Reduce a ``jax.profiler`` trace to the benchmark's device numbers.

Input is a list of planes, each ``{"name", "lines": [{"name", "events":
[(name, start_ns, duration_ns), ...]}]}`` — what ``from_profile`` makes of
the profiler's ``.xplane.pb``, and what a test can write by hand.

* A device is a plane named ``/device:TPU:<i>``; its operations are the
  events of its ``XLA Ops`` line.  An event's name there is the HLO
  instruction's text (``%fusion.3 = f32[...] fusion(...)``); an
  operation is named by its instruction name alone (``fusion.3``), since
  the operands name other instructions.
* Busy time is the union of a device's operation intervals; idle is the
  rest of the traced window.
* Collective time is the union of the intervals of collective operations
  (all-gather, all-reduce, collective-permute, all-to-all,
  reduce-scatter, in their sync, start and done forms).
* Each idle gap between two operations is labelled by the benchmark's own
  host span (a ``TraceAnnotation`` named ``bench.*``) that was open at the
  gap's midpoint: the innermost one, i.e. the open span that started last,
  on any host thread.

Device numbers are per device, averaged over the devices the run used.
"""
from __future__ import annotations

import glob
import heapq
import json
import os
import re

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter",
    re.IGNORECASE)
SPAN_PREFIX = "bench."
NO_SPAN = "(no bench span)"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def from_profile(path: str) -> list[dict]:
    """The planes of a profiler file as plain lists."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = _peaks_table()
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


def _peaks_table() -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        return json.load(f)


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def device_ops(planes, n_devices=None) -> list[list[tuple]]:
    """Per device (in id order), its ``XLA Ops`` events."""
    devs = []
    for p in planes:
        m = DEVICE_PLANE.match(p["name"])
        if m:
            ops = [(op_name(name), s, d) for line in p["lines"]
                   if line["name"] == OPS_LINE
                   for name, s, d in line["events"]]
            devs.append((int(m.group(1)), ops))
    devs.sort(key=lambda d: d[0])
    if n_devices is not None:
        devs = devs[:n_devices]
    return [ops for _, ops in devs]


def op_name(text: str) -> str:
    """``%sweep_rows_gs.2 = f32[...] custom-call(...)`` -> ``sweep_rows_gs.2``."""
    return text.split(" = ", 1)[0].lstrip("%")


def host_spans(planes) -> list[tuple[float, float, str]]:
    spans = []
    for p in planes:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for line in p["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append((start, start + dur, name))
    return sorted(spans)


def label_points(points, spans) -> list[str]:
    """For each time in ``points`` (ascending), the innermost open span."""
    out, heap, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            s, e, name = spans[i]
            heapq.heappush(heap, (-s, e, name))
            i += 1
        while heap and heap[0][1] < t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else NO_SPAN)
    return out


def reduce(planes, n_devices=None) -> dict:
    """The device numbers of one traced window (see the module doc)."""
    per_dev = device_ops(planes, n_devices)
    if not per_dev or not any(per_dev):
        raise ValueError("the trace holds no device operations")
    nd = len(per_dev)
    every = [(s, s + d) for ops in per_dev for _, s, d in ops]
    lo = min(s for s, _ in every)
    hi = max(e for _, e in every)
    spans = host_spans(planes)
    if spans:
        lo, hi = min(lo, spans[0][0]), max(hi, max(e for _, e, _ in spans))

    busy = coll = 0.0
    op_ns: dict[str, float] = {}
    gaps = []
    for ops in per_dev:
        merged = union((s, s + d) for _, s, d in ops)
        busy += _length(merged)
        coll += _length(union((s, s + d) for name, s, d in ops
                              if COLLECTIVE.search(name)))
        for name, _, d in ops:
            op_ns[name] = op_ns.get(name, 0.0) + d
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[j], edges[j + 1])
                 for j in range(0, len(edges), 2) if edges[j + 1] > edges[j]]

    gaps.sort()
    labels = label_points([(s + e) / 2 for s, e in gaps], spans)
    idle: dict[str, float] = {}
    for (s, e), name in zip(gaps, labels):
        idle[name] = idle.get(name, 0.0) + (e - s)
    ns = 1e-9
    return {
        "devices": nd,
        "traced_s": (hi - lo) * ns,
        "busy_s": busy * ns / nd,
        "collective_s": coll * ns / nd,
        "op_s": {k: v * ns / nd for k, v in op_ns.items()},
        "top_ops": sorted(([k, v * ns / nd] for k, v in op_ns.items()),
                          key=lambda kv: -kv[1]),
        "idle_by_span": sorted(([k, v * ns / nd] for k, v in idle.items()),
                               key=lambda kv: -kv[1]),
    }


def reduce_file(path: str, n_devices=None) -> dict:
    return reduce(from_profile(path), n_devices)


def op_seconds(summary: dict, pattern: str) -> float:
    """Per-device seconds of the operations whose name matches in full."""
    rx = re.compile(pattern)
    return sum(v for k, v in summary["op_s"].items() if rx.fullmatch(k))
