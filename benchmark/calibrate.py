#!/usr/bin/env python3
"""Readings that the limits and rates of the cells are set from.  Not
part of a benchmark run: a benchmark PR runs this on the chip when it
defines or changes a cell, and writes what it read into ``PERF.md``.

    python3 benchmark/calibrate.py train --workload mistral7b-train \
        --seeds 1,2,3 --control-seeds 1,2,3
    python3 benchmark/calibrate.py serve --workload mistral7b-batch \
        --seeds 1,2,3 --seconds 20
    python3 benchmark/calibrate.py sweep --workload mistral7b-chat \
        --rates 0.5,1,2 --seconds 30 --seed 1

``train``: per seed, the program's first steps against the reference
(the lower readings); on the control seeds also the control (the
reference in float8) and the planted fault (half of the batch left out)
against the reference (the upper readings).  ``serve``: per seed a short
window at the cell's load, the program's gaps and the control's on the
same requests.  ``sweep``: one engine, the open-loop mix at each rate:
the backlog at the window's middle and end, to find the knee.

Every line is one JSON object on standard output."""

import time
T0 = time.perf_counter()

import argparse                 # noqa: E402
import copy                     # noqa: E402
import gc                       # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def say(ctx, **record):
    """One line, with the device the reading was taken on: a limit is
    set from chip readings only."""
    print(json.dumps(dict(record, device_kind=ctx.devices[0].device_kind),
                     default=float), flush=True)


class HalfBatch:
    """The planted fault: half of the batch left out, the mean taken
    over the rest — the program is fed the first half twice."""

    def batch(self, ids, labels):
        import jax.numpy as jnp
        h = ids.shape[0] // 2
        return (jnp.concatenate([ids[:h], ids[:h]]),
                jnp.concatenate([labels[:h], labels[:h]]))

    def compiled(self, step):
        return step


def ints(text):
    return [int(x) for x in text.split(",") if x]


def train(args):
    from benchmark.lib import compare, model
    from benchmark.lib.cell import open_cell
    controls = set(ints(args.control_seeds))
    for seed in ints(args.seeds):
        t = time.perf_counter()
        kind, ctx, _ = open_cell(args.workload, seed, 1.0, False, t0=t)
        run = kind.Run(ctx)
        run.set_up()
        first = run.first
        run.release()
        ref = model.reference_module(ctx.config)
        tr = ctx.config["assumed"]["train"]
        kw = dict(dtype=ctx.config["torch_dtype"], lr=tr["learning_rate"],
                  betas=tuple(tr["adam_betas"]), eps=tr["adam_eps"])
        t_ref = time.perf_counter()
        want = ref.train_steps(ctx.config, seed, first["batches"], **kw)
        t_ref = time.perf_counter() - t_ref
        say(ctx, seed=seed, side="program", setup_phases=ctx.phases.seconds,
            losses=first["losses"], ref_losses=want["losses"],
            reference_s=t_ref, **compare.train_readings(first, want))
        if seed in controls:
            low = ref.train_steps(ctx.config, seed, first["batches"],
                                  prec="fp8", **kw)
            say(ctx, seed=seed, side="control_fp8", losses=low["losses"],
                **compare.train_readings(low, want))
            del low
            gc.collect()
            fault = kind.Run(ctx, hooks=HalfBatch())
            fault.set_up()
            got = fault.first
            fault.release()
            say(ctx, seed=seed, side="fault_half_batch", losses=got["losses"],
                **compare.train_readings(got, want))
        del want
        gc.collect()


def serve(args):
    from benchmark.lib.cell import open_cell

    class Control:
        control = "fp8"

        def wrap_engine(self, eng):
            pass

    for seed in ints(args.seeds):
        t = time.perf_counter()
        kind, ctx, _ = open_cell(args.workload, seed, args.seconds, False,
                                 t0=t)
        run = kind.Run(ctx)
        run.set_up()
        out = run.window()
        run.release()
        run.verify(out)
        program = run.gaps
        run.hooks = Control()
        run.verify(out)
        say(ctx, seed=seed, workload=args.workload, setup_s=ctx.setup_s,
            phases=ctx.phases.seconds, notes=out["notes"],
            end_to_end=out["end_to_end"], program=program,
            control_fp8=run.gaps)
        del run, out
        gc.collect()


def sweep(args):
    from benchmark.lib.cell import open_cell
    from benchmark.lib.traffic import request_plan
    t = time.perf_counter()
    kind, ctx, _ = open_cell(args.workload, args.seed, args.seconds, False,
                             t0=t)
    run = kind.Run(ctx)
    run.set_up()
    eng, fe = run.eng, run.fe
    from paddle_tpu.serving import ServingFrontend
    for rate in [float(x) for x in args.rates.split(",")]:
        traffic = copy.deepcopy(ctx.traffic)
        traffic["arrivals"]["rate_rps"] = rate
        run.plan = request_plan(traffic, args.seed, args.seconds,
                                int(ctx.config["vocab_size"]))
        run.counters_at_open = run._counters()
        out = run.window()          # closes the frontend, cancels the rest
        say(ctx, rate_rps=rate, window_s=out["window_s"],
            attempted=out["attempted"], notes=out["notes"],
            end_to_end=out["end_to_end"],
            finished_per_s=out["notes"]["finished"] / out["window_s"])
        run.eng, run.fe = eng, ServingFrontend(eng)
        run.fe.run_until_drained(timeout_s=600)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("train", "serve", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    {"train": train, "serve": serve, "sweep": sweep}[args.what](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
