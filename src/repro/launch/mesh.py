"""Worker-mesh construction for the distributed solvers.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (a forced-host-device run must set XLA_FLAGS
before the first jax call).
"""
from __future__ import annotations

import jax

from repro.compat import make_mesh as _mesh


def make_host_mesh(workers: int | None = None, axis: str = "workers"):
    """1-D mesh over the locally visible devices (solver benchmarks)."""
    n = workers or len(jax.devices())
    return _mesh((n,), (axis,))
