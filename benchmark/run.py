#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell on the machine it is started on (a TPU; no CPU
fallback).  Prints, before its last line, its set-up split into phases
with the compile cache's hits, misses and the names of the programs
that missed; its last line on standard output is the result object.
See ``benchmark/README.md``."""

import time
T0 = time.perf_counter()        # set-up is counted from here

import argparse                 # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.lib.cell import NoChip, run_cell
    try:
        return run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), t0=T0)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
