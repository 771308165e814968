"""Shared benchmark plumbing: CSV emission, JSON persistence, timing."""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax

#: Repo root — where the persisted BENCH_*.json files land so successive
#: PRs can diff them (printed records alone left no perf trajectory).
REPO_ROOT = Path(__file__).resolve().parent.parent


def emit(name: str, **fields):
    """One CSV-ish record per line: benchmark,key=value,..."""
    kv = ",".join(f"{k}={v}" for k, v in fields.items())
    print(f"{name},{kv}", flush=True)


def _jsonable(obj):
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "item") and getattr(obj, "ndim", 1) == 0:
        return obj.item()                     # numpy / jax scalars
    if hasattr(obj, "tolist"):
        return obj.tolist()                   # numpy / jax arrays
    return obj


def provenance() -> dict:
    """Where a benchmark's numbers actually came from: jax version,
    backend, device kind, and whether Pallas ran in interpret mode
    (``repro.kernels.ops.interpret_default`` — the same predicate the
    kernel wrappers use), so interpret-mode CPU timings can never
    masquerade as hardware numbers when BENCH files are diffed."""
    from repro.kernels.ops import interpret_default
    return {"jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "interpret_mode": interpret_default()}


def worker_mesh(workers: int):
    """A ``workers``-device mesh over the devices this process holds.  A
    chip belongs to one process, so a benchmark never starts a JAX child
    for more devices: too few is an error (on the CPU backend, start with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<workers>``)."""
    from repro.launch.mesh import make_host_mesh
    have = len(jax.devices())
    if have < workers:
        raise RuntimeError(
            f"needs {workers} devices, this process sees {have}")
    return make_host_mesh(workers)


def write_json(name: str, payload) -> Path:
    """Persist a benchmark payload as ``BENCH_<name>.json`` at the repo
    root (round-trippable: numpy/jax scalars and arrays are plain lists).
    Every payload is stamped with ``provenance()``."""
    path = REPO_ROOT / f"BENCH_{name}.json"
    payload = dict(_jsonable(payload))
    payload["provenance"] = provenance()
    path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"[bench] wrote {path}", flush=True)
    return path


def timed(fn, *args, warmup: int = 1, iters: int = 3, stat: str = "median"):
    """Wall time of ``fn(*args)`` with block_until_ready.

    ``stat="median"`` (default) or ``"min"`` — min-of-N is the trustworthy
    statistic for BENCH_*.json deltas (one-sided noise: a run can only be
    slowed down by interference, never sped up), so bench_kernels times
    with ``stat="min"`` and a ``--repeats`` flag.
    """
    if stat not in ("min", "median"):
        raise ValueError(f"unknown stat: {stat!r}")
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[0] if stat == "min" else times[len(times) // 2]
