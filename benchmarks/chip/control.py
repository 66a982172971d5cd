#!/usr/bin/env python3
"""Readings of the control or of a planted fault at a cell's own size.

    python benchmarks/chip/control.py --workload <cell> --program <name> \
        --seeds <n,n,...> --seconds <s>

``--program`` names one of ``cb_control.PROGRAMS``; it takes the program's
place in a whole run per seed (set-up, a window of ``--seconds``, the
check), all in one process.  Each run prints the numbers compared with
their limits, as a benchmark run does; the control and every fault must
come out ``"correct": false``.  Needs the TPU, as a run does.
"""
import argparse
import sys
import time

from cb_control import PROGRAMS
from cb_harness import (Refused, accelerator, import_program, load_cell,
                        run_on, use_compile_cache)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program", required=True, choices=sorted(PROGRAMS))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench, cell, config, mix = load_cell(args.workload)
    import_program()
    use_compile_cache()
    devices = accelerator(cell["chips"])
    verdicts = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0)
        line = run_on(run, bench, cell, config, mix, devices,
                      time.perf_counter(),
                      program=PROGRAMS[args.program](config))
        verdicts.append(line["correct"])
    print(f"[{args.program}] correct={verdicts}", flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        print(e.code, file=sys.stderr, flush=True)
        sys.exit(2)
