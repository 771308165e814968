"""The chip benchmark: one command, cells described in ``BENCHMARK.json``.

Run ``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the repository root on a machine with the TPU chips
the cell asks for.  See ``bench/run.py``.
"""
