#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (JAX start, planner and kernel warm-up, the coded store) comes
first, then ``--seconds`` of steps, then the check of what the window
produced.  The last line of standard output is one JSON object; the
numbers compared, each with its limit, are the last lines of standard
error.  Without a TPU, or without the system under test beside the
benchmark, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402

from cb_harness import Refused, run  # noqa: E402

if __name__ == "__main__":
    try:
        run(sys.argv[1:], T_PROCESS)
    except Refused as e:
        print(e.code, file=sys.stderr, flush=True)
        sys.exit(2)
