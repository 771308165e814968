"""Published peaks of one TPU v5e chip (Google Cloud documentation, "TPU
v5e"), the denominators of the roofline terms ``benchmarks/bench_kernels.py``
reports.  A roofline share is only meaningful over a kernel time measured
on that chip.
"""
from __future__ import annotations

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # bytes/s / chip
