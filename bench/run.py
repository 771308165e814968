"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it starts
on and prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, traced, ``breakdown``; ``checks`` (each number compared
with its limit) comes last.  The same numbers end standard error.  With
no TPU, or fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, _, _ = harness.resolve_cell(spec, args.workload)
    try:
        harness.require_chips(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    harness.enable_cache()
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t0=T0, spec=spec)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
