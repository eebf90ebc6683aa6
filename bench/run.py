#!/usr/bin/env python3
"""Run one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up the cell named in BENCHMARK.json, measures for
``--seconds``, checks what the measured path produced against the plain
reference, and prints one JSON result as the last line of standard
output (the numbers compared, each beside its limit, are also the last
lines of standard error).  Exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
