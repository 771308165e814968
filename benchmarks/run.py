"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One module per paper table/figure, all in this one process.  Output is
CSV-ish lines ``benchmark,key=value,...``; the exit code is non-zero when
any job failed.
"""
from __future__ import annotations

import argparse
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter")
    ap.add_argument("--fast", action="store_true", help="smaller problem sizes")
    args = ap.parse_args()

    from benchmarks import (bench_kernels, fig1_residual, fig2_scaling,
                            fig3_async_penalty, theory_validation)

    jobs = [
        ("fig1_residual", lambda: fig1_residual.run(
            n=1024 if args.fast else 2048)),
        ("fig2_scaling", lambda: fig2_scaling.run(
            n=512 if args.fast else 1024, workers=(1, 2, 4) if args.fast
            else (1, 2, 4, 8))),
        ("fig3_async_penalty", lambda: fig3_async_penalty.run(
            n=512 if args.fast else 1024,
            taus=(4, 16) if args.fast else (4, 16, 64),
            trials=3 if args.fast else 5)),
        ("theory_validation", lambda: theory_validation.run(
            n=256 if args.fast else 512, seeds=4 if args.fast else 8)),
        ("bench_kernels", lambda: bench_kernels.run(
            n=512 if args.fast else 1024)),
    ]
    failed = []
    for name, fn in jobs:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        try:
            fn()
        except Exception as e:  # run the other jobs, then fail
            failed.append(name)
            print(f"{name},error={type(e).__name__}:{e}", flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failed:
        print(f"# failed: {','.join(failed)}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
