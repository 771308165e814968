"""Readings for the limits of ``correct``, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <a,b,...> [--control]

For each seed, runs the cell as a run does (short window) and prints the
numbers compared with their limits, one JSON line per seed.  With
``--control`` the program runs with bfloat16 coefficient storage, the
nearest precision below the configuration's float32: the control that has
to come out as not correct.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    from bench import harness
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _, _ = harness.resolve_cell(spec, args.workload)
    try:
        harness.require_chips(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(
            args.workload, seed, args.seconds, False,
            t0=time.perf_counter(), spec=spec,
            storage_dtype="bfloat16" if args.control else None)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "failed": r["failed"], "checks": r["checks"],
                          "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
