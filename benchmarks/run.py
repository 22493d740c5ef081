#!/usr/bin/env python3
"""The benchmark's one command: one process, one cell, one result line.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell needs is data found by the names in BENCHMARK.json
(benchmarks/README.md); this file only stamps the process start, which
``setup_s`` is measured from, and hands over to ``benchlib.harness``.
"""

import time

T_START = time.monotonic()

import os  # noqa: E402 — the stamp above comes first on purpose
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_start=T_START))
