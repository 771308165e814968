"""The benchmark's harness: everything between ``bench/run.py``'s command
line and a cell's entry, and nothing that belongs to one cell.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix.  The harness finds each by its name:

* ``bench/configs/<config>.json`` — the deployment's sizes, guarantee and
  program settings; its ``recipe`` names ``bench/recipes/<recipe>.py``,
  which makes the matrix from the seed;
* ``bench/traffic/<traffic>.json`` — the traffic mix's parameters; its
  ``entry`` names ``bench/entries/<entry>.py``, which drives the system
  under test;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric.

A run: make the data, set up the entry (build the operator, warm its
shapes, one untimed call), measure for ``seconds``, read the device's
peak memory, then check a seeded sample of the answers against the
float64 reference (``bench/reference.py``).
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: answers compared with the reference per run, drawn from the seed
SAMPLE = 24


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a benchmark file by path: as ``bench.<dir>.<name>`` where
    that is a module name, else under a name of its own (a metric's file
    name holds dots)."""
    rel = os.path.relpath(os.path.abspath(path)[:-len(".py")], ROOT)
    parts = rel.split(os.sep)
    if parts[0] == "bench" and all(p.isidentifier() for p in parts):
        return importlib.import_module(".".join(parts))
    name = "bench_file_" + os.path.abspath(path).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(spec: dict, name: str, bench_dir: str = BENCH):
    """``(cell, config, traffic)`` for the cell ``name`` of ``spec``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    config = load_json(os.path.join(bench_dir, "configs",
                                    cell["config"] + ".json"))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def cell_metrics(spec: dict, name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that cell ``name``
    reports (a metric without ``workloads`` is reported everywhere)."""
    return [m for m in spec[kind]
            if name in m.get("workloads", [w["name"]
                                           for w in spec["workloads"]])]


def seed_key(seed: int):
    """A JAX key that keeps every bit of ``seed`` (``jax.random.key``
    alone truncates to 32 bits)."""
    import jax
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def require_chips(chips: int):
    """The first ``chips`` TPU devices; ``NoChip`` otherwise."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def enable_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path
    inside the checkout (or ``JAX_COMPILATION_CACHE_DIR``), holding every
    program however quickly it compiled."""
    import jax
    from repro.launch.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations.  JAX times a persistent-cache read as
    a compile too, so cache hits are taken off."""

    def __init__(self):
        import jax
        self._timed = self._hits = 0

        def timed(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self._timed += 1

        def hit(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self._hits += 1

        jax.monitoring.register_event_duration_secs_listener(timed)
        jax.monitoring.register_event_listener(hit)

    @property
    def count(self) -> int:
        return self._timed - self._hits


class Reservoir:
    """A uniform sample of at most ``size`` items from a stream of unknown
    length, drawn from ``seed`` (Algorithm R)."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.items: list = []
        self.seen = 0
        self._rng = np.random.default_rng([int(seed) % (1 << 64), 7])

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = int(self._rng.integers(0, self.seen))
        if j < self.size:
            self.items[j] = item


class Ctx:
    """What an entry gets: the cell, its configuration and traffic, the
    seed, the chips, the data, and a place for counters."""

    def __init__(self, cell, config, traffic, seed, devices, *,
                 storage_dtype=None):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed = int(seed)
        self.devices = devices
        #: the operator's coefficient storage; the control sets bfloat16
        self.storage_dtype = storage_dtype or config["storage_dtype"]
        self.sample = Reservoir(SAMPLE, seed)
        self.A_host = None   # scipy CSR, float32 values: the reference's
        self.A_dev = None    # the dense matrix on the device, as users hold it


def make_data(ctx: Ctx) -> None:
    """The configuration's matrix from the seed: sparse on the host for
    the reference, dense on the device for the program."""
    import jax
    import jax.numpy as jnp
    recipe = load_module(os.path.join(BENCH, "recipes",
                                      ctx.config["recipe"] + ".py"))
    A = recipe.build(ctx.config, ctx.seed)
    ctx.A_host = A
    coo = A.tocoo()
    n = A.shape[0]
    # One shape for every seed: the triplets are padded to the recipe's
    # largest count with copies of the first one (``set`` is idempotent).
    pad = int(recipe.max_nnz(ctx.config)) - coo.nnz
    rows, cols, vals = (np.concatenate([a, np.repeat(a[:1], pad)])
                        for a in (coo.row, coo.col, coo.data))
    ctx.A_dev = _densify(jnp.asarray(rows, jnp.int32),
                         jnp.asarray(cols, jnp.int32),
                         jnp.asarray(vals, jnp.float32), n=n)
    ctx.A_dev.block_until_ready()


def _densify(rows, cols, vals, *, n):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def dense(r, c, v):
        return jnp.zeros((n, n), jnp.float32).at[r, c].set(v)

    return dense(rows, cols, vals)


def e2e_value(e2e: dict, name: str) -> float:
    """The entry's reading for end-to-end metric ``name``.  A metric kept
    apart for some cells, ``<quantity>.<qualifier>``, reads the entry's
    ``<quantity>``."""
    return e2e[name] if name in e2e else e2e[name.split(".", 1)[0]]


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def read_per_layer(spec, name, readings, bench_dir: str = BENCH) -> dict:
    out = {}
    for m in cell_metrics(spec, name, "per_layer"):
        reader = load_module(os.path.join(bench_dir, "metrics",
                                          m["name"] + ".py"))
        value = reader.read(readings)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Readings:
    """What a per-layer reader reads: the entry's counters, the reduced
    trace, the work of one update and the chip's peaks."""

    def __init__(self, counters, trace, config, peaks, window_s):
        self.counters = counters
        self.trace = trace
        self.config = config
        self.peaks = peaks
        self.window_s = window_s


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t0: float, spec: dict | None = None, check_chip: bool = True,
             storage_dtype: str | None = None, overrides=None,
             log=None) -> dict:
    """One run of cell ``name``; returns the result object.

    ``check_chip=False`` (tests) runs on whatever JAX has; ``overrides``
    updates the configuration (tests shrink it); ``storage_dtype``
    replaces the configuration's (the control)."""
    import jax
    from bench import reference, trace_reduce

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec = spec or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = resolve_cell(spec, name)
    if overrides:
        config = {**config, **overrides.get("config", {})}
        traffic = {**traffic, **overrides.get("traffic", {})}
    chips = int(cell["chips"])
    devices = require_chips(chips) if check_chip else jax.devices()[:chips]
    compiles = CompileCounter()
    log(f"chips ready at {time.perf_counter() - t0:.3f}s")
    ctx = Ctx(cell, config, traffic, seed, devices,
              storage_dtype=storage_dtype)
    entry = load_module(os.path.join(BENCH, "entries",
                                     traffic["entry"] + ".py"))

    make_data(ctx)
    log(f"data: {config['recipe']} n={ctx.A_host.shape[0]} "
        f"nnz={ctx.A_host.nnz} row_cap={int(np.diff(ctx.A_host.indptr).max())}"
        f" at {time.perf_counter() - t0:.3f}s")
    state = entry.setup(ctx)
    setup_s = time.perf_counter() - t0
    log(f"setup_s {setup_s:.3f} (compiles so far {compiles.count})")

    tdir = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    c0 = compiles.count
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir.name, profiler_options=opts)
    w0 = time.perf_counter()
    out = entry.window(ctx, state, seconds)
    window_s = time.perf_counter() - w0
    if trace:
        jax.profiler.stop_trace()
    window_compiles = compiles.count - c0
    log(f"window {window_s:.3f}s, {out['attempted']} attempted, "
        f"{out['failed']} failed, {window_compiles} compiles in the window")
    for line in out.get("notes", []):
        log(line)

    peak = memory_peak(devices)
    entry.teardown(ctx, state)
    del state

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    breakdown = None
    if trace:
        summary = trace_reduce.reduce_file(
            trace_reduce.find_xplane(tdir.name), n_devices=len(devices))
        tdir.cleanup()
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = window_s
        peaks = (trace_reduce.load_peaks(devices[0].device_kind)
                 if devices[0].platform == "tpu" else None)
        metrics = read_per_layer(
            spec, name, Readings(out["counters"], summary, config, peaks,
                                 window_s))
        breakdown = {"device_ops": summary["top_ops"][:10],
                     "idle_gaps": summary["idle_by_span"][:10]}
    else:
        metrics = {m["name"]: {"value": float(e2e_value(out["e2e"],
                                                        m["name"])),
                               "unit": m["unit"]}
                   for m in cell_metrics(spec, name, "end_to_end")
                   if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    checks = reference.check(ctx, out)
    result["correct"] = all(c["ok"] for c in checks.values())
    result["metrics"] = metrics
    result["device"] = device
    result["window_compiles"] = window_compiles
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result
