"""Run one benchmark cell on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration and its
traffic are found by name through BENCHMARK.json (see bench/harness.py).
The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` are also the last lines of standard
error. Exits non-zero, with no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
import os
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    main(t_start=T_START)
