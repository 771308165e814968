"""The benchmark's host spans: ``TraceAnnotation``s named ``bench.<x>``
around each call the benchmark makes into the program, so that an idle
gap in the device trace can be put down to what the host was doing."""
from __future__ import annotations

import jax


def span(name: str):
    return jax.profiler.TraceAnnotation("bench." + name)
