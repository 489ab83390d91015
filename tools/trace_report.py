#!/usr/bin/env python
"""Render request-trace JSONL into per-phase latency-budget tables.

Input is one JSON object per line in the ``Trace.to_dict()`` shape —
what ``paddle_tpu.observability.tracing.write_spans_jsonl`` emits, what
``GET /v1/trace/<id>`` returns, and what an SLO-exemplar event carries
in its ``trace`` field.  Pure stdlib on purpose: the tool must open a
flight dump on a laptop without the framework (or jax) installed.

    python tools/trace_report.py traces.jsonl
    python tools/trace_report.py traces.jsonl --trace <trace_id>
    python tools/trace_report.py --engine engine.json
    python tools/trace_report.py --xplane <trace dir or .xplane.pb>

The default view is the attribution table (per-phase p50/p95/sum
contribution to TTFT and TPOT, mirroring ``LoadReport.attribution``);
``--trace`` renders one request's span waterfall instead.

``--engine`` reads the engine timeline instead (what ``GET
/v1/trace/engine`` returns, ``TRACER.timeline().to_dict()``): the
budget of a scheduler iteration phase by phase, the iterations in which
running streams waited for a prefill, and what a prefill costs by its
chunks.

``--xplane`` reads a ``jax.profiler`` trace instead (the one mode that
imports jax, and the benchmark's interval arithmetic from the checkout
this file lies in): the engine timeline's ``pt:`` spans on the
profiler's clock — per span its count, mean and p95 — and the device's
idle time split by the innermost host span that covers each instant.
"""

import argparse
import bisect
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple


def load_traces(path: str) -> List[Dict[str, Any]]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            # exemplar event records wrap the trace dict
            if "trace" in d and "spans" not in d:
                d = d["trace"]
            out.append(d)
    return out


def _pct(vals: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy 'linear' method), stdlib."""
    xs = sorted(vals)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    frac = pos - lo
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


def phase_totals(trace: Dict[str, Any], t_lo: float,
                 t_hi: Optional[float]) -> Dict[str, float]:
    """Per-phase span time clipped to the [t_lo, t_hi] window, in
    seconds relative to trace start (the to_dict convention)."""
    totals: Dict[str, float] = {}
    for s in trace.get("spans", ()):
        t0, t1 = float(s["t0_s"]), float(s["t1_s"])
        lo = max(t0, t_lo)
        hi = t1 if t_hi is None else min(t1, t_hi)
        if hi > lo:
            totals[s["name"]] = totals.get(s["name"], 0.0) + (hi - lo)
    return totals


def attribution(traces: List[Dict[str, Any]],
                pcts: Sequence[int] = (50, 95)) -> Dict[str, Any]:
    """Per-phase contribution to TTFT and TPOT across traces — the
    JSONL-side twin of ``tracing.attribution`` (which works on live
    Trace objects)."""
    ttft_by: Dict[str, List[float]] = {}
    tpot_by: Dict[str, List[float]] = {}
    n = 0
    for tr in traces:
        meta = tr.get("meta") or {}
        ttft = meta.get("ttft_s")
        dur = tr.get("duration_s")
        if ttft is None or dur is None:
            continue
        n += 1
        head = phase_totals(tr, 0.0, float(ttft))
        explained = sum(head.values())
        gap = max(float(ttft) - explained, 0.0)
        if gap > 0.0:
            head["unattributed"] = gap
        for k, v in head.items():
            ttft_by.setdefault(k, []).append(v)
        for k, v in phase_totals(tr, float(ttft), float(dur)).items():
            tpot_by.setdefault(k, []).append(v)

    def digest(by: Dict[str, List[float]]) -> Dict[str, Any]:
        return {k: {**{f"p{q}": round(_pct(vs, q), 6) for q in pcts},
                    "sum": round(sum(vs), 6)}
                for k, vs in sorted(by.items())}

    return {"n_traced": n, "ttft": digest(ttft_by),
            "tpot": digest(tpot_by)}


def render_attribution(traces: List[Dict[str, Any]],
                       pcts: Sequence[int] = (50, 95)) -> str:
    states: Dict[str, int] = {}
    for tr in traces:
        st = tr.get("state") or "live"
        states[st] = states.get(st, 0) + 1
    att = attribution(traces, pcts)
    lines = [
        f"{len(traces)} traces ("
        + ", ".join(f"{v} {k}" for k, v in sorted(states.items()))
        + f") · {att['n_traced']} with TTFT"]
    cols = [f"p{q}" for q in pcts] + ["sum"]
    for window in ("ttft", "tpot"):
        rows = att[window]
        if not rows:
            continue
        lines.append("")
        lines.append(f"{window.upper()} attribution (s)".ljust(30)
                     + "".join(c.rjust(12) for c in cols))
        order = sorted(rows, key=lambda k: -rows[k]["sum"])
        for name in order:
            d = rows[name]
            lines.append(
                ("  " + name).ljust(30)
                + "".join(f"{d[c]:12.6f}" for c in cols))
    return "\n".join(lines)


def render_timeline(tr: Dict[str, Any], width: int = 48) -> str:
    dur = float(tr.get("duration_s") or 0.0) or max(
        [float(s["t1_s"]) for s in tr.get("spans", ())] or [0.0])
    meta = tr.get("meta") or {}
    head = [f"trace {tr.get('trace_id')} [{tr.get('state') or 'live'}]"
            f" rid={tr.get('rid')} dur={dur:.6f}s"]
    keys = ("ttft_s", "tpot_s", "n_tokens", "reason", "replayed",
            "exemplar")
    kv = {k: meta[k] for k in keys if k in meta}
    if kv:
        head.append("  " + "  ".join(f"{k}={v}" for k, v in kv.items()))
    lines = head
    for s in tr.get("spans", ()):
        t0, t1 = float(s["t0_s"]), float(s["t1_s"])
        a = int(t0 / dur * width) if dur else 0
        b = int(t1 / dur * width) if dur else 0
        bar = " " * a + ("█" * max(b - a, 1) if t1 > t0 else "▏")
        attrs = s.get("attrs") or {}
        tail = ("  " + " ".join(f"{k}={v}" for k, v in attrs.items())
                if attrs else "")
        lines.append(f"  {s['name']:<16} |{bar:<{width}}| "
                     f"{t0:9.6f}→{t1:9.6f} ({t1 - t0:.6f}s){tail}")
    if tr.get("dropped_spans"):
        lines.append(f"  … {tr['dropped_spans']} spans dropped "
                     f"(ring full)")
    return "\n".join(lines)


# -- the engine timeline -------------------------------------------------
#: the waits for the device inside an iteration: the rest is the host's
DEVICE_WAITS = ("logits_fetch", "first_token_fetch")


def _ms_row(label: str, vals: Sequence[float]) -> str:
    """Count, p50, p75, p95 and max of ``vals`` (no columns when empty)."""
    return label.ljust(34) + f"{len(vals):8d}" + "".join(
        f"{_pct(vals, q):10.3f}" for q in (50, 75, 95, 100) if vals)


def render_engine(tl: Dict[str, Any]) -> str:
    """The per-phase budget of a scheduler iteration from the engine
    timeline's span trees (milliseconds; a phase an iteration did not
    run counts 0 in ``mean``/``p95``, ``own`` is a span's time less its
    children's)."""
    its = tl.get("iterations") or []
    if not its:
        return "no iterations on the timeline"
    rows: List[Dict[str, float]] = []       # per iteration: name -> ms
    own: List[Dict[str, float]] = []
    count: Dict[str, int] = {}
    stalled_admit: List[float] = []
    stalled_step: List[float] = []
    stalled_slots = slot_steps = 0
    chunk_ms: Dict[int, List[float]] = {}
    valid = padded = 0
    plans: Dict[Tuple[int, ...], List[float]] = {}
    for it in its:
        spans = it["spans"]
        ms = {s["span_id"]: 1e3 * (s["t1_s"] - s["t0_s"]) for s in spans}
        total: Dict[str, float] = {}
        self_ms = {s["span_id"]: ms[s["span_id"]] for s in spans}
        for s in spans:
            total[s["name"]] = total.get(s["name"], 0.0) + ms[s["span_id"]]
            count[s["name"]] = count.get(s["name"], 0) + 1
            if s["parent"] in self_ms:
                self_ms[s["parent"]] -= ms[s["span_id"]]
        mine: Dict[str, float] = {}
        for s in spans:
            mine[s["name"]] = mine.get(s["name"], 0.0) \
                + self_ms[s["span_id"]]
        rows.append(total)
        own.append(mine)
        chunks = [s for s in spans if s["name"] == "prefill_chunk"]
        for c in chunks:
            a = c["attrs"]
            chunk_ms.setdefault(a["size"], []).append(ms[c["span_id"]])
            valid += a["valid"]
            padded += a["size"]
        prefills = [s for s in spans if s["name"] == "prefill"]
        if len(prefills) == 1:
            plans.setdefault(tuple(c["attrs"]["size"] for c in chunks),
                             []).append(ms[prefills[0]["span_id"]])
        slot_steps += sum(s["attrs"]["batch"] for s in spans
                          if s["name"] in ("decode_dispatch",
                                           "spec_decode"))
        admit = next((s for s in spans if s["name"] == "admit"), None)
        if admit and chunks and admit["attrs"]["running"]:
            stalled_slots += admit["attrs"]["running"]
            stalled_admit.append(total["admit"])
            stalled_step.append(sum(total.get(k, 0.0) for k in (
                "admit", "decode_dispatch", "logits_fetch", "pick",
                "spec_decode")))
    n = len(rows)
    root = its[0]["spans"][0]["name"]
    lines = [f"{n} iterations (numbers {its[0]['n']}-{its[-1]['n']}, "
             f"root {root}; the ring dropped {tl.get('dropped', 0)})",
             "", "span".ljust(20) + "".join(c.rjust(11) for c in (
                 "spans", "in_iters", "mean_ms", "p95_ms", "max_ms",
                 "own_mean"))]
    for name in sorted(count, key=lambda k: -sum(
            r.get(k, 0.0) for r in rows)):
        v = [r.get(name, 0.0) for r in rows]
        lines.append(
            name.ljust(20) + f"{count[name]:11d}"
            f"{sum(1 for r in rows if name in r):11d}"
            f"{sum(v) / n:11.3f}{_pct(v, 95):11.3f}{max(v):11.3f}"
            f"{sum(o.get(name, 0.0) for o in own) / n:11.3f}")
    host = [r[root] - sum(r.get(k, 0.0) for k in DEVICE_WAITS)
            for r in rows]
    longest = max(range(n), key=lambda i: rows[i][root])
    lines += [
        "", f"{root} less " + " and ".join(DEVICE_WAITS)
        + f" (the host's share): mean {sum(host) / n:.3f} ms, p50 "
        f"{_pct(host, 50):.3f}, p95 {_pct(host, 95):.3f}",
        f"longest: iteration {its[longest]['n']}, "
        f"{rows[longest][root]:.3f} ms (admit "
        f"{rows[longest].get('admit', 0.0):.3f})",
        "", f"iterations that ran a prefill while streams were live: "
        f"{len(stalled_admit)} of {n} = "
        f"{100 * len(stalled_admit) / n:.2f}%; of the slots' decode "
        f"steps {stalled_slots} of {slot_steps} = "
        f"{100 * stalled_slots / max(slot_steps, 1):.2f}% waited",
        "in those, ms".ljust(34) + "".join(c.rjust(w) for c, w in (
            ("n", 8), ("p50", 10), ("p75", 10), ("p95", 10),
            ("max", 10))),
        _ms_row("  admit", stalled_admit),
        _ms_row("  admit + the decode step", stalled_step)]
    if padded:
        lines += ["", f"prefill tokens useful / dispatched: {valid} / "
                  f"{padded} = {100 * valid / padded:.2f}%"]
        for size in sorted(chunk_ms):
            v = chunk_ms[size]
            lines.append(f"  dispatching a chunk of {size}: {len(v)} "
                         f"times, mean {sum(v) / len(v):.3f} ms")
        lines.append("a prefill alone in its iteration, by its chunks "
                     "(first chunk to first token)".ljust(62)
                     + "n".rjust(6) + "mean_ms".rjust(10)
                     + "max_ms".rjust(10))
        for plan in sorted(plans, key=lambda k: (sum(k), k)):
            v = plans[plan]
            lines.append(("  " + " + ".join(map(str, plan))).ljust(62)
                         + f"{len(v):6d}{sum(v) / len(v):10.3f}"
                         f"{max(v):10.3f}")
    return "\n".join(lines)


# -- the profiler's trace ----------------------------------------------
#: the engine timeline's spans (tracing.PROFILER_PREFIX) and the
#: benchmark harness's own: both are read, only the first are reported
PT, BENCH = "pt:", "bench:"
#: spans whose own time is what the span table failed to cover ...
INNER_NODES = (PT + "iteration", PT + "engine_step")
#: ... and the three that were leaves until their phases got spans of
#: their own: inner nodes of a trace that holds the child named here
SPLIT_NODES = {PT + "decode_dispatch": PT + "launch",
               PT + "logits_fetch": PT + "device_wait",
               PT + "deliver": PT + "collect"}
#: the clock check's two handshakes, (before the program, after it):
#: the decode program (XLA module ``jit_step``) starts only after the
#: ``pt:launch`` that hands it over began, and the ``pt:device_wait``
#: that waits for it returns only after it ended.  A trace without the
#: two (a program before PR 38) has the wider pair around them: the
#: whole ``pt:decode_dispatch`` (uploads included) and the whole
#: ``pt:logits_fetch`` (the copy included)
SYNC_NARROW = (PT + "launch", PT + "device_wait")
SYNC_WIDE = (PT + "decode_dispatch", PT + "logits_fetch")
SYNC_MODULE = re.compile(r"^jit_step\b")

HostSpan = Tuple[str, float, float]


def read_xplane(path: str, xp) -> Tuple[List, List, List[HostSpan]]:
    """Of the FIRST device plane (the serve cells hold one chip) the
    operations' intervals and ``(name, start, end)`` of its programs,
    and the host's ``pt:`` / ``bench:`` spans, in seconds on the
    trace's own axis."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = xp.find_xplane(path)
        if found is None:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = found

    def events(line):
        return [(e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events]

    planes = list(ProfileData.from_file(path).planes)
    device = sorted((p for p in planes if xp.DEVICE_PLANE.match(p.name)),
                    key=lambda p: p.name)
    lines = {ln.name: events(ln) for ln in device[0].lines} \
        if device else {}
    modules = lines.get("XLA Modules", [])
    ops = [(a, b) for _, a, b in lines.get("XLA Ops", modules)]
    host = [ev for p in planes if p.name == "/host:CPU"
            for ln in p.lines for ev in events(ln)
            if ev[0].startswith((PT, BENCH))]
    return ops, modules, host


def handshakes(host: List[HostSpan]) -> Tuple[str, str]:
    """The narrow pair where the trace has both spans, else the wide."""
    names = {n for n, _, _ in host}
    return SYNC_NARROW if names.issuperset(SYNC_NARROW) else SYNC_WIDE


def clock_check(modules, host: List[HostSpan],
                sync: Optional[Tuple[str, str]] = None
                ) -> Tuple[List[float], List[float]]:
    """How far the device's clock may be AHEAD of the host's, in
    seconds, from each decode step's two handshakes ``sync`` (the
    narrowest pair the trace holds when not given): at most
    ``jit_step`` start less the start of the last ``sync[0]`` span
    before it (a program cannot start before it was handed over), and
    at least ``jit_step`` end less the end of the first ``sync[1]`` span
    after it (the wait cannot return before the program ended).
    Returns (the upper bounds, the lower bounds); the offset lies
    between the largest lower and the smallest upper."""
    before, after = sync or handshakes(host)
    upper, lower = [], []
    dispatch = sorted(a for n, a, _ in host if n == before)
    fetched = sorted(b for n, _, b in host if n == after)
    for n, a, b in modules:
        if not SYNC_MODULE.match(n):
            continue
        # the clocks are within milliseconds, a step is ~100: the
        # nearest dispatch before the program's middle is its own
        i = bisect.bisect_right(dispatch, (a + b) / 2)
        if i:
            upper.append(a - dispatch[i - 1])
        j = bisect.bisect_left(fetched, (a + b) / 2)
        if j < len(fetched):
            lower.append(b - fetched[j])
    return upper, lower


def idle_by_span(ops, host: List[HostSpan], shift: float, xp
                 ) -> Tuple[float, float, int, Dict[str, float]]:
    """With the device's events moved by ``shift`` seconds: the traced
    window, and its idle seconds, gaps and idle seconds by the
    innermost host span."""
    ops = [(a + shift, b + shift) for a, b in ops]
    window = [s for s in host if s[0] == BENCH + "window"]
    lo = min([a for a, _ in ops] + [a for _, a, _ in window])
    hi = max([b for _, b in ops] + [b for _, _, b in window])
    idle = xp.gaps(ops, lo, hi)
    named = xp.idle_by_span(idle, [s for s in host if s not in window])
    return hi - lo, sum(b - a for a, b in idle), len(idle), named


def inner_nodes(host: List[HostSpan]) -> Tuple[str, ...]:
    names = {n for n, _, _ in host}
    return INNER_NODES + tuple(p for p, child in SPLIT_NODES.items()
                               if child in names)


def leaf_share(named: Dict[str, float],
               inner: Sequence[str] = INNER_NODES) -> float:
    """Per cent of the idle time named by a leaf span of the table
    (``inner``: the spans whose own time names nothing)."""
    return 100 * sum(v for k, v in named.items() if k.startswith(PT)
                     and k not in inner) / sum(named.values())


def render_xplane(path: str) -> str:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from benchmark.lib import xplane as xp
    except ImportError as e:
        raise SystemExit(f"--xplane needs the checkout's benchmark/lib "
                         f"beside tools/ ({e})")
    finally:
        sys.path.remove(root)
    ops, modules, host = read_xplane(path, xp)
    if not ops:
        raise SystemExit(f"{path}: no operation ran on a device")
    window_s, idle_s, n_gaps, named = idle_by_span(ops, host, 0.0, xp)
    lines = [f"device: window {window_s:.6f} s, busy "
             f"{xp.union_seconds(ops):.6f} s, idle {idle_s:.6f} s "
             f"({100 * idle_s / window_s:.3f}%) in {n_gaps} gaps"]
    pt = [s for s in host if s[0].startswith(PT)]
    if not pt:
        lines.append(f"no {PT} spans in this trace (tracer off, or the "
                     f"profiler session was not open around an engine "
                     f"iteration)")
        return "\n".join(lines)
    by: Dict[str, List[float]] = {}
    for n, a, b in pt:
        by.setdefault(n, []).append(b - a)
    lines += ["", "span".ljust(24) + "".join(
        c.rjust(12) for c in ("count", "mean_ms", "p95_ms", "sum_s"))]
    for n in sorted(by, key=lambda k: -sum(by[k])):
        v = by[n]
        lines.append(n.ljust(24) + f"{len(v):12d}"
                     f"{1e3 * sum(v) / len(v):12.3f}"
                     f"{1e3 * _pct(v, 95):12.3f}{sum(v):12.6f}")
    # the two clocks are not one: name the gaps as recorded, and with
    # the device moved onto the host's clock by either end of the
    # interval the decode steps' handshakes leave for the offset
    sync = handshakes(host)
    inner = inner_nodes(host)
    upper, lower = clock_check(modules, host, sync)
    ends = [max(lower), min(upper)] if upper and lower else []
    moved = [idle_by_span(ops, host, -off, xp)[3] for off in ends]
    lines += ["", "idle by innermost span".ljust(24)
              + "seconds".rjust(12) + "share_%".rjust(12) + "".join(
                  f"if {1e3 * off:+.2f}ms".rjust(12) for off in ends)]
    for k, v in sorted(named.items(), key=lambda kv: -kv[1]):
        lines.append(k.ljust(24) + f"{v:12.6f}{100 * v / idle_s:12.2f}"
                     + "".join(
                         f"{100 * m.get(k, 0.0) / sum(m.values()):12.2f}"
                         for m in moved))
    lines.append(f"named by a leaf {PT} span, % of the idle time:"
                 .ljust(48) + f"{leaf_share(named, inner):12.2f}"
                 + "".join(f"{leaf_share(m, inner):12.2f}" for m in moved))
    if ends:
        lines += ["", f"clocks, over {len(lower)} decode steps: device "
                  f"clock - host clock lies in [{1e3 * ends[0]:.3f}, "
                  f"{1e3 * ends[1]:.3f}] ms (from jit_step end - "
                  f"{sync[1]} end, median {1e3 * _pct(lower, 50):.3f}"
                  f", to jit_step start - {sync[0]} start, median "
                  f"{1e3 * _pct(upper, 50):.3f}); the last columns name "
                  f"the gaps with the device's events moved onto the "
                  f"host's clock for either end; a span shorter than "
                  f"that interval is not resolved"]
        wide = sync != SYNC_WIDE and clock_check(modules, host, SYNC_WIDE)
        if wide and all(wide):
            lines.append(
                f"the interval's width: {1e3 * (ends[1] - ends[0]):.3f} ms"
                f" between {sync[0]} and {sync[1]}; "
                f"{1e3 * (min(wide[0]) - max(wide[1])):.3f} ms between "
                f"{SYNC_WIDE[0]} and {SYNC_WIDE[1]}, the handshakes of a "
                f"trace without the two")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/trace_report.py",
        description="per-phase latency-budget attribution from request-"
                    "trace JSONL (docs/observability.md)")
    ap.add_argument("path", help="JSONL of Trace.to_dict() lines (with "
                    "--engine: the timeline's JSON; with --xplane: a "
                    "profiler trace dir or .xplane.pb)")
    ap.add_argument("--xplane", action="store_true",
                    help="read a jax.profiler trace: the engine's pt: "
                         "spans and the device's idle time by span")
    ap.add_argument("--engine", action="store_true",
                    help="read the engine timeline (the JSON of GET "
                         "/v1/trace/engine): an iteration's budget by "
                         "phase, prefill stalls, chunk costs")
    ap.add_argument("--trace", default=None, metavar="ID",
                    help="render one trace's span waterfall (trace_id, "
                         "rid, or request_id)")
    ap.add_argument("--pcts", default="50,95",
                    help="percentile columns (default: 50,95)")
    args = ap.parse_args(argv)
    if args.xplane:
        print(render_xplane(args.path))
        return 0
    if args.engine:
        with open(args.path) as f:
            print(render_engine(json.load(f)))
        return 0
    traces = load_traces(args.path)
    if not traces:
        print(f"no traces in {args.path}", file=sys.stderr)
        return 1
    if args.trace is not None:
        want = args.trace
        for tr in traces:
            if want in (tr.get("trace_id"), str(tr.get("rid")),
                        tr.get("request_id")):
                print(render_timeline(tr))
                return 0
        print(f"no trace {want!r} in {args.path}", file=sys.stderr)
        return 1
    pcts = [int(p) for p in args.pcts.split(",") if p]
    print(render_attribution(traces, pcts))
    return 0


if __name__ == "__main__":
    sys.exit(main())
