#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>
    python3 benchmarks/chip/run.py --workload <cell> --seed 1 --seconds 2 \
        --trace 0 --rehearse      # tiny, on the CPU; never correct

The cells, their configurations, traffic mixes and metrics are named in
BENCHMARK.json at the root of the checkout; harness.py says how a run
works. Without a TPU (and without --rehearse) the run exits 2 and prints
no result.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(t_start=T_START))
