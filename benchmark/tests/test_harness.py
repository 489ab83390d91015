"""The rest of a run, driven on the CPU at a tiny size with the look for
a chip skipped: the last line's shape, cells found as files, `correct`
true for the sound program and FALSE for the control (the reference in
float8 put in the program's place) and for each fault a cell can have
(the timed path broken underneath)."""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.calibrate import HalfBatch
from benchmark.lib import compare, model
from benchmark.lib.cell import run_cell
from benchmark.tests import tiny

REPO = os.path.dirname(model.HERE)

# tiny-size limits, set as PERF.md sets the real ones: above what sound
# runs read here (gradient 0.0014, change 0.0018, loss 3e-5, widest gap
# 3e-4 over a few seeds), below what the float8 control reads (gradient
# 0.0185, widest gap 0.015)
LIMITS = {
    "mistral7b-train": {"loss_gap_step2": 1e-3, "loss_gap_step3": 1e-3,
                        "first_grad_norm_gap": 0.006,
                        "param_change_gap": 0.006},
    "mistral7b-batch": {"widest_gap": 0.003, "mean_gap": 0.0003},
    "mistral7b-chat": {"widest_gap": 0.003, "mean_gap": 0.0003},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("root")), LIMITS)


def run(root, cell, seed, seconds=1.0, trace=False, hooks=None):
    import time
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(cell, seed, seconds, trace, t0=time.perf_counter(),
                  root=root, allow_cpu=True, out=out, err=err, hooks=hooks)
    lines = out.getvalue().strip().splitlines()
    return rc, [json.loads(x) for x in lines], err.getvalue()


def check_shape(last, cell_metrics):
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "checks"           # the comparison comes last
    assert set(last["metrics"]) == set(cell_metrics)
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in last["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell,metrics", [
    ("tiny-train", {"train_tok_s", "setup_s"}),
    ("tiny-batch", {"serve_tok_s", "setup_s"}),
    ("tiny-chat", {"itl_p95_ms", "setup_s"})])
def test_sound_run(root, cell, metrics):
    rc, lines, err = run(root, cell, 2 ** 31 + 77)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, err
    check_shape(last, metrics)
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["compiles_in_window"] == 0
    setup = lines[0]
    assert setup["event"] == "setup" and "warmup" in setup["phases"]
    assert setup["setup_s"] == last["metrics"]["setup_s"]["value"]
    assert {"cache_hits", "cache_misses", "missed"} <= set(setup["compile"])
    # every number compared is on standard error beside its limit
    for name in last["checks"]:
        assert f"check {name}:" in err


def test_traced_run_reports_per_layer_metrics(root, monkeypatch):
    from benchmark.lib import xplane
    # a CPU has no device plane: read the recorded TPU trace instead
    monkeypatch.setattr(xplane, "find_xplane", lambda d: os.path.join(
        os.path.dirname(__file__), "data", "small.xplane.pb"))
    rc, lines, err = run(root, "tiny-chat", 5, seconds=2.0, trace=True)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, err
    # counters, request traces, the engine timeline (a stretch within an
    # iteration, a span chosen by its attributes, one span's share of
    # another) and the profiler's trace
    assert {"decode_batch.chat", "stalled_share.chat", "bucket_fill.chat",
            "ttft_p50_ms.chat", "decode_step_ms.chat", "iter_host_ms.chat",
            "prefill_stall_p95_ms.chat", "prefill_share.chat",
            "device_idle.chat"} <= set(last["metrics"])
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert 0 < m["prefill_share.chat"] < 100 and m["bucket_fill.chat"] <= 100
    # the host's part of an iteration is less than the whole of one that
    # also waits for a decode step
    assert 0 < m["iter_host_ms.chat"] and 0 < m["decode_step_ms.chat"]
    # no peaks for a CPU: the share of a peak is left out, never 0
    assert "serve_mfu.chat" not in last["metrics"]
    assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in last["breakdown"].values())
    longer = next(x for x in lines if x.get("event") == "breakdown")
    assert longer["device_ops"][:10] == last["breakdown"]["device_ops"]


# ---- the timed path broken underneath --------------------------------
class StateUnchanged:
    """A step that returns its state unchanged."""

    def batch(self, ids, labels):
        return ids, labels

    def compiled(self, step):
        import jax
        import jax.numpy as jnp

        def broken(state, ids, labels):
            keep = jax.tree.map(jnp.copy, state)
            _, loss = step(state, ids, labels)
            return keep, loss
        return broken


class TokenAltered:
    """Every fourth token of every request altered where it is produced
    (by the request's own count: a count over all requests would fall on
    one slot of the batch, which the sample compared may miss)."""
    control = ""

    def wrap_engine(self, eng):
        orig = eng._append_tok

        def altered(req, tok):
            orig(req, (tok + 1) % eng.cfg.vocab_size
                 if len(req.out) % 4 == 3 else tok)
        eng._append_tok = altered


@pytest.mark.parametrize("cell,hooks,number", [
    ("tiny-train", StateUnchanged(), "param_change_gap"),
    ("tiny-train", HalfBatch(), "first_grad_norm_gap"),
    ("tiny-batch", TokenAltered(), "widest_gap"),
    ("tiny-chat", TokenAltered(), "widest_gap")])
def test_fault_is_not_correct(root, cell, hooks, number):
    rc, lines, err = run(root, cell, 11, hooks=hooks)
    last = lines[-1]
    assert rc == 0 and last["correct"] is False
    c = last["checks"][number]
    assert c["value"] > c["limit"]
    assert "<-- OVER" in err


# ---- the control: the reference, one precision down ------------------
def test_control_train_is_not_correct(root):
    cfg = model.load_json("configs", "mistral-7b-v0.3-train", root)
    traffic = model.load_json("traffic", "pretrain-2k", root)
    ref = model.reference_module(cfg)
    from benchmark.lib.traffic import PackedDocuments
    data = PackedDocuments(traffic, 3, cfg["vocab_size"])
    rows = [data[i] for i in range(12)]
    batches = [(np.stack([r[0] for r in rows[k:k + 4]]),
                np.stack([r[1] for r in rows[k:k + 4]]))
               for k in (0, 4, 8)]
    kw = dict(dtype="bfloat16", lr=1e-4)
    want = ref.train_steps(cfg, 3, batches, **kw)
    low = ref.train_steps(cfg, 3, batches, prec="fp8", **kw)
    limits = model.load_json("workloads", "tiny-train", root)["limits"]
    assert compare.judge(compare.train_checks(want, want, limits))
    assert not compare.judge(compare.train_checks(low, want, limits))


def test_control_serve_is_not_correct(root):
    cfg = model.load_json("configs", "mistral-7b-v0.3-serve", root)
    ref = model.reference_module(cfg)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg["vocab_size"], n) for n in (30, 44, 52)]
    limits = model.load_json("workloads", "tiny-batch", root)["limits"]
    low = ref.served_gaps(cfg, 9, seqs, [10, 20, 30], 64, "bfloat16",
                          control="fp8")
    assert low["tokens"] == 20 + 24 + 22
    assert not compare.judge(compare.serve_checks(low, 0, limits))


# ---- data-driven: new files are found, none is edited ----------------
FIXTURE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def put(root, kind, name, obj):
    with open(os.path.join(root, kind, name + ".json"), "w") as f:
        json.dump(obj, f)


def metric(name, cell, **kw):
    return dict({"name": name, "unit": "x", "better": "higher",
                 "layer": "scheduler + KV", "source": "program_counter",
                 "moves": "serve_tok_s", "workloads": [cell]}, **kw)


def test_new_cell_mix_and_metric_are_found_as_files(root, monkeypatch):
    from benchmark.lib import xplane
    mix = model.load_json("traffic", "offline-batch", root)
    mix["prompt_tokens"] = dict(mix["prompt_tokens"], min=2, max=9)
    put(root, "traffic", "short-batch", mix)
    cell = model.load_json("workloads", "tiny-batch", root)
    put(root, "workloads", "tiny-short", dict(cell, traffic="short-batch"))
    put(root, "metrics", "prefill_tokens.short", metric(
        "prefill_tokens.short", "tiny-short", reducer="counter_ratio",
        numerator="prefill_tokens", denominator="decode_steps"))
    monkeypatch.setattr(xplane, "find_xplane", lambda d: FIXTURE)
    rc, lines, err = run(root, "tiny-short", 4, seconds=1.0, trace=True)
    assert rc == 0 and lines[-1]["correct"] is True, err
    assert lines[-1]["metrics"]["prefill_tokens.short"]["value"] > 0


def test_second_architecture_is_files_alone(root, monkeypatch):
    """A program module, its reference, a configuration naming both, a
    cell, and three metrics that read what the harness names nowhere: a
    span and a counter of the new engine's own, and a roofline over the
    new program's own work keys.  Nothing that exists is edited: the two
    modules are FILES dropped beside the others (here: a directory
    added to each package's search path)."""
    import benchmark.programs
    import benchmark.reference
    from benchmark.lib import peaks, xplane
    second = os.path.join(os.path.dirname(__file__), "second")
    for pkg, sub in ((benchmark.programs, "programs"),
                     (benchmark.reference, "reference")):
        monkeypatch.setattr(pkg, "__path__", list(pkg.__path__)
                            + [os.path.join(second, sub)])
    harness = os.path.dirname(os.path.dirname(__file__))
    named = subprocess.run(
        ["grep", "-rlE", "state_sync|mixer_", os.path.join(harness, "lib"),
         os.path.join(harness, "kinds"), os.path.join(harness, "reducers"),
         os.path.join(harness, "programs")], capture_output=True, text=True)
    assert named.stdout == ""        # the harness holds none of the names

    cfg = model.load_json("configs", "mistral-7b-v0.3-serve", root)
    put(root, "configs", "second-serve",
        dict(cfg, program="second", reference="second_ref"))
    cell = model.load_json("workloads", "tiny-batch", root)
    put(root, "workloads", "second-batch", dict(cell, config="second-serve"))
    put(root, "metrics", "state_sync_us.second", metric(
        "state_sync_us.second", "second-batch", source="program_span",
        reducer="span_mean", span="state_sync", scale=1e6))
    put(root, "metrics", "state_syncs.second", metric(
        "state_syncs.second", "second-batch", reducer="counter_ratio",
        numerator="state_syncs", denominator="decode_steps"))
    put(root, "metrics", "mixer_roofline.second", metric(
        "mixer_roofline.second", "second-batch", unit="%",
        layer="kernels", source="device_trace", reducer="trace_roofline",
        pattern="convolution_tanh", line="ops",
        flops="traced_mixer_flops", bytes="traced_mixer_bytes"))
    put(root, "metrics", "second_mfu", metric(
        "second_mfu", "second-batch", unit="%", layer="whole step, serve",
        reducer="work_share", flops="window_flops", seconds="window_s"))
    # a CPU has no device plane and no peaks: the recorded TPU trace,
    # and a row of peaks for the kind the CPU reports
    monkeypatch.setattr(xplane, "find_xplane", lambda d: FIXTURE)
    monkeypatch.setitem(peaks.PEAKS, "cpu",
                        {"flops": 197e12, "bytes_per_s": 819e9})
    rc, lines, err = run(root, "second-batch", 6, seconds=2.0, trace=True)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, err
    got = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(got) == {"state_sync_us.second", "state_syncs.second",
                        "mixer_roofline.second", "second_mfu"}
    assert 0 < got["state_sync_us.second"] < 1e4
    # one sync a scheduler iteration, so at least one a decode step
    assert got["state_syncs.second"] >= 1
    # the roofline divides the SECOND program's work (2 * 2048**3 a
    # sequence and step) by the trace's kernel time: llama's counts at
    # this size would read under a millionth of it
    assert got["mixer_roofline.second"] > 1
    # 7 FLOPs a token: llama's count for the tiny model is ~2e5 a token
    window = next(x for x in lines if x.get("event") == "window")
    tokens = window["notes"]["tokens"]
    assert 0 < got["second_mfu"] * 197e12 / 100 < 7 * 60 * tokens


def test_benchmark_json_matches_the_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert b["paths"] == ["benchmark"]
    for c in b["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        cfg = model.load_json("configs", c["name"])
        assert c["reduced"] == sorted(cfg["published"])
        # both of its modules are files, found by the names it gives
        assert os.path.isfile(os.path.join(
            model.HERE, "programs", cfg["program"] + ".py"))
        assert os.path.isfile(os.path.join(
            model.HERE, "reference", cfg["reference"] + ".py"))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for w in b["workloads"]:
        f = model.load_json("workloads", w["name"])
        assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
            (w["config"], w["traffic"], w["chips"], w["why"])
        for m in f["end_to_end"]:
            assert w["name"] in e2e[m].get("workloads", [w["name"]])
    keys = ("name", "unit", "better", "source", "layer", "moves",
            "workloads")
    files = {}
    for name in os.listdir(os.path.join(model.HERE, "metrics")):
        m = model.load_json("metrics", name[:-5])
        files[m["name"]] = {k: m[k] for k in keys}
    assert {m["name"]: m for m in b["per_layer"]} == files


def test_cpu_run_exits_non_zero_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral7b-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
