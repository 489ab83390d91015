#!/usr/bin/env python3
"""Readings of a serving cell with its program module's PLANTED FAULT in
place, beside ``calibrate.py serve``'s (the program and the float8
control): the third reading a cell's limits are set from.  Not part of
a benchmark run.

    python3 benchmark/calibrate_fault.py --workload glm47flash-long \
        --seeds 1,2,3 --seconds 30

Per seed a window at the cell's load with the fault in place
(``programs/<program>.py:planted_fault()``: hooks whose ``wrap_engine``
puts it there between the engine's construction and its warm-up), and
the served tokens' gaps against the sound reference.  The fault has to
come out as not correct, by one of the cell's limits.  Every line is one
JSON object on standard output."""

import time
T0 = time.perf_counter()

import argparse                 # noqa: E402
import gc                       # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    from benchmark.calibrate import ints, say
    from benchmark.lib import model
    from benchmark.lib.cell import open_cell
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    for seed in ints(args.seeds):
        t = time.perf_counter()
        kind, ctx, _ = open_cell(args.workload, seed, args.seconds, False,
                                 t0=t)
        fault = model.program_module(ctx.config).planted_fault()
        run = kind.Run(ctx, hooks=fault)
        run.set_up()
        out = run.window()
        run.release()
        run.verify(out)
        say(ctx, seed=seed, workload=args.workload,
            fault=type(fault).__name__, notes=out["notes"],
            end_to_end=out["end_to_end"], fault_gaps=run.gaps)
        del run, out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
