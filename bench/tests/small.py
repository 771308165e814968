"""Shrunken cells for the benchmark's CPU tests: the cell's own entry,
traffic and checks, at a size interpret-mode kernels finish quickly."""
from __future__ import annotations

import os
import time

from bench import harness

N = 128
SEED = 2**33 + 12345
#: each cell's configuration cut to ``N`` rows
SIZES = {"spd16k.solve": {"n": N, "k": 4, "row_nnz": 4, "row_cap": 15},
         "hpcg16k.solve": {"nx": 4, "ny": 4, "nz": 8}}


def spec() -> dict:
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def overrides(cell: str) -> dict:
    cfg = {**SIZES[cell],
           "program": {"action": "gs", "fused": True, "rows_per_panel": 8,
                       "record_every": N, "max_sweeps": 256}}
    return {"config": cfg, "traffic": {"rhs_pool": 2}}


def run(cell: str, *, seconds: float = 1.0, seed: int = SEED, **kw) -> dict:
    return harness.run_cell(cell, seed, seconds, False,
                            t0=time.perf_counter(), spec=spec(),
                            check_chip=False, overrides=overrides(cell),
                            log=lambda m: None, **kw)
