"""One run of one cell: set-up, the measured window, the result line.

A cell is ``workloads/<name>.json`` = configuration + traffic mix +
limits.  The traffic file names its ``kind``; ``kinds/<kind>.py`` drives
the program's entry points for it.  Per-layer metrics are
``metrics/*.json`` whose ``workloads`` list names the cell; each names
its ``reducer`` (``reducers/<reducer>.py``).  So a new configuration,
mix, cell or metric is a new file, and none that exists is edited.
"""

from __future__ import annotations

import gc
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from . import compare, model, setup
from .peaks import peaks_of


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks."""


def say(stream, **record) -> None:
    print(json.dumps(record, default=float), file=stream, flush=True)


class Context:
    """What a kind's driver gets, and the two clocks of a run: the
    phases of set-up and the profiler's traced tail of the window."""

    def __init__(self, workload: Dict, config: Dict, traffic: Dict,
                 seed: int, seconds: float, trace: bool, t0: float,
                 phases: setup.Phases, watch: setup.CompileWatch,
                 devices: List, peaks: Optional[Dict], cache_dir: str):
        self.workload, self.config, self.traffic = workload, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t0, self.phases, self.watch = t0, phases, watch
        self.devices, self.peaks, self.cache_dir = devices, peaks, cache_dir
        self.trace_seconds = min(float(traffic.get("trace_seconds", 3.0)),
                                 seconds / 2)
        self.runtime_env: Dict[str, str] = {}
        self.setup_s: Optional[float] = None
        self.compile_at_open: Optional[Dict] = None
        self.trace_dir: Optional[str] = None
        self.tracing = False
        self._window_span = None

    # -- the window ----------------------------------------------------
    def window_opens(self) -> None:
        """The first instant of the measured window: set-up ends here."""
        self.phases.mark("warmup")
        self.setup_s = time.perf_counter() - self.t0
        self.compile_at_open = self.watch.snapshot()

    def trace_due(self, elapsed: float) -> bool:
        return self.trace and not self.tracing \
            and self.trace_dir is None \
            and elapsed >= self.seconds - self.trace_seconds

    def start_trace(self) -> None:
        """Trace the TAIL of the window: what the host clock reads
        before this instant is free of the profiler's cost."""
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.trace_dir)
        self._window_span = jax.profiler.TraceAnnotation("bench:window")
        self._window_span.__enter__()
        self.tracing = True

    def stop_trace(self) -> None:
        import jax
        if not self.tracing:
            return
        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing = False


def load_metrics(cell: str, root: str) -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(root, "metrics", "*.json"))):
        with open(path) as f:
            m = json.load(f)
        if cell in m["workloads"]:
            out.append(m)
    return out


def reduce_metrics(defs: List[Dict], readings: Dict) -> Dict[str, Dict]:
    """Each metric through its own reader; one that finds nothing to
    read returns None and is left out of the line."""
    out = {}
    for m in defs:
        reducer = importlib.import_module(
            f"benchmark.reducers.{m['reducer']}")
        value = reducer.reduce(m, readings)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def find_devices(chips: int, allow_cpu: bool):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU; jax found {len(devices)} x "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s); jax found "
                     f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def open_cell(name: str, seed: int, seconds: float, trace: bool, *,
              t0: float, root: str = model.HERE, allow_cpu: bool = False):
    """Find the cell's files, import the system under test, place the
    compile cache, look for the chip.  Returns the kind's module, the
    context of this run and the cell's per-layer metric files."""
    phases = setup.Phases(t0)
    workload = model.load_json("workloads", name, root)
    config = model.load_json("configs", workload["config"], root)
    traffic = model.load_json("traffic", workload["traffic"], root)
    metric_defs = load_metrics(name, root)
    kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
    runtime_env = setup.apply_runtime_env(config)

    import jax  # noqa: F401
    import paddle_tpu  # noqa: F401  (the system under test)
    phases.mark("import")
    from paddle_tpu import native
    native.load_library()
    phases.mark("native")

    cache_dir = setup.place_compile_cache()
    watch = setup.CompileWatch().install()
    devices = find_devices(int(workload["chips"]), allow_cpu)
    phases.mark("device")           # the runtime reaches the chip here
    try:
        peaks = peaks_of(devices[0].device_kind)
    except KeyError:
        if not allow_cpu:
            raise
        peaks = None                # a CPU rehearsal: no share of a peak
    ctx = Context(workload, config, traffic, seed, seconds, trace, t0,
                  phases, watch, devices, peaks, cache_dir)
    ctx.runtime_env = runtime_env
    return kind, ctx, metric_defs


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t0: float, root: str = model.HERE, allow_cpu: bool = False,
             out=sys.stdout, err=sys.stderr, hooks: Any = None) -> int:
    """Run the cell and print its lines.  Returns the exit code.

    ``allow_cpu`` and ``hooks`` are for the tests under
    ``benchmark/tests`` (a CPU rehearsal at a tiny size, and a timed
    path broken on purpose); the command line offers neither."""
    from paddle_tpu import native
    kind, ctx, metric_defs = open_cell(name, seed, seconds, trace, t0=t0,
                                       root=root, allow_cpu=allow_cpu)
    phases, watch, devices, peaks = ctx.phases, ctx.watch, ctx.devices, \
        ctx.peaks
    workload, cache_dir = ctx.workload, ctx.cache_dir
    kind_name = devices[0].device_kind

    run = kind.Run(ctx, hooks)
    run.set_up()                    # marks build / state / warmup itself
    outcome = run.window()          # calls ctx.window_opens() first
    in_window = setup.since(watch.snapshot(), ctx.compile_at_open)
    say(out, event="setup", workload=name, seed=seed,
        setup_s=ctx.setup_s, phases=phases.seconds,
        compile=ctx.compile_at_open, compile_cache_dir=cache_dir,
        runtime_env=ctx.runtime_env, native_available=native.available())
    peak = memory_peak(devices)
    run.release()
    gc.collect()

    device = {"platform": devices[0].platform, "kind": kind_name,
              "count": len(devices), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {}
    if trace:
        from . import xplane
        path = xplane.find_xplane(ctx.trace_dir) if ctx.trace_dir else None
        summary = xplane.summarize(path) if path else None
        if ctx.trace_dir:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        if summary is None or summary["busy_s"] <= 0:
            raise RuntimeError("the traced run shows no operation on "
                               "the device")
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        readings = dict(outcome["readings"], trace=summary, peaks=peaks)
        metrics = reduce_metrics(metric_defs, readings)
        # the result line's lists hold ten entries at the most; what
        # lies below the tenth is on a line of its own
        say(out, event="breakdown", workload=name,
            device_ops=summary["device_ops"], idle_gaps=summary["idle_gaps"])
        result["breakdown"] = {"device_ops": summary["device_ops"][:10],
                               "idle_gaps": summary["idle_gaps"][:10]}
    else:
        metrics = {k: outcome["end_to_end"][k]
                   for k in workload["end_to_end"]}
        metrics["setup_s"] = {"value": ctx.setup_s, "unit": "s"}

    t_check = time.perf_counter()
    checks = run.verify(outcome)
    correct = compare.judge(checks) and not outcome.get("crashed", False)
    say(out, event="window", workload=name, window_s=outcome["window_s"],
        compiles_in_window=in_window["backend_compiles"],
        cache_misses_in_window=in_window["cache_misses"],
        missed_in_window=in_window["missed"], notes=outcome.get("notes"),
        reference_s=time.perf_counter() - t_check)
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})"
              f"{'' if c['value'] is not None and c['value'] <= c['limit'] else '  <-- OVER'}",
              file=err, flush=True)
    line = {"correct": bool(correct), "attempted": outcome["attempted"],
            "failed": outcome["failed"], "metrics": metrics,
            "device": device}
    line.update(result)
    line["compiles_in_window"] = in_window["backend_compiles"]
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    print(json.dumps(line, default=float), file=out, flush=True)
    return 0
