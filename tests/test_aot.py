"""AOT compile-artifact subsystem (ISSUE 6): roundtrip fidelity, typed
failure modes, and warm-start guarantees.

The load-bearing contracts:

* exported → reloaded executables are BIT-identical to fresh compiles
  (train step params after optimizer steps; engine greedy tokens);
* a warm start performs ZERO backend compiles (CompileMonitor-pinned);
* every way an artifact can be unusable — version skew, geometry drift,
  CRC corruption (tests/faults.py bitrot injector), devices this
  process does not have — either raises a TYPED AotError or falls back
  to a fresh compile with the reason recorded, never runs a wrong
  program.
"""

import os

import jax
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
from paddle_tpu import parallel as dist
from paddle_tpu.aot import (AotArtifactCorruptError,
                            AotManifestMismatchError, ArtifactStore,
                            ShapeBucketRegistry, export_compiled,
                            export_engine, export_jit_apply,
                            export_train_step)
from paddle_tpu.core import rng as core_rng
from paddle_tpu.hapi.model import Model
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.models.llama import build_llama_train_step, llama_tiny
from paddle_tpu.observability import CompileMonitor, MemorySink, REGISTRY
from paddle_tpu.optimizer import AdamW
from paddle_tpu.parallel.topology import HybridTopology, set_topology

from faults import corrupt_file

rng = np.random.default_rng(0)


# ---------------------------------------------------------------------
# bucket registry
# ---------------------------------------------------------------------
def test_bucket_plan_covers_any_length():
    reg = ShapeBucketRegistry((16, 64), max_batch=4)
    for n in (1, 15, 16, 17, 63, 64, 65, 200):
        plan = reg.plan_chunks(n)
        assert sum(v for _, v in plan) == n
        assert all(size in (16, 64) and 1 <= v <= size
                   for size, v in plan)
    # exact-bucket chunks are hits, padded tails are misses
    reg2 = ShapeBucketRegistry((16, 64))
    reg2.plan_chunks(80)                    # 64 + 16: two hits
    assert (reg2.hits, reg2.misses) == (2, 0)
    reg2.plan_chunks(70)                    # 64 hit + padded 16
    assert (reg2.hits, reg2.misses) == (3, 1)
    assert reg2.padded_tokens == 10
    with pytest.raises(ValueError):
        reg2.plan_chunks(0)
    rt = ShapeBucketRegistry.from_manifest(reg.to_manifest())
    assert rt.chunk_sizes == reg.chunk_sizes
    assert rt.max_batch == 4


# ---------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def serve_setup(tmp_path_factory):
    cfg = llama_tiny()
    topo = dist.init_topology(devices=jax.devices()[:1])
    _, init_fn = build_llama_train_step(cfg, topo, num_microbatches=1)
    params = init_fn(0)["params"]
    set_topology(HybridTopology())
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 17)]
    aot_dir = str(tmp_path_factory.mktemp("serve_aot"))
    eng = _engine(cfg, params)
    export_engine(eng, aot_dir)
    # fresh-compile reference outputs (bucketed prefill, same code path
    # the AOT engine runs)
    for p in prompts:
        eng.add_request(p, 4)
    fresh = eng.run_to_completion()
    return cfg, params, prompts, aot_dir, fresh


def _engine(cfg, params, **kw):
    kw.setdefault("prefill_buckets", (8,))
    return ContinuousBatchingEngine(cfg, params, max_batch=2,
                                    block_size=8, num_blocks=64, **kw)


def test_engine_aot_warm_zero_compiles_bit_identical(serve_setup):
    """ISSUE 6 acceptance: artifact-loaded engine records zero
    backend_compile events and reproduces the fresh engine's greedy
    tokens exactly."""
    cfg, params, prompts, aot_dir, fresh = serve_setup
    monitor = CompileMonitor().install()
    try:
        eng = _engine(cfg, params, aot_dir=aot_dir)
        assert eng.aot_loaded, eng.aot_error
        for p in prompts:
            eng.add_request(p, 4)
        warm = eng.run_to_completion()
    finally:
        monitor.uninstall()
    assert monitor.n_compiles == 0, monitor.summary()
    assert set(warm) == set(fresh)
    for rid in fresh:
        np.testing.assert_array_equal(warm[rid], fresh[rid])
    stats = eng.aot_stats()
    assert stats["aot_loaded"] and stats["bucket_hits"] >= 1


def test_bucketed_prefill_matches_legacy_engine(serve_setup):
    """Declared-bucket (padded chunk-fill) prefill must reproduce the
    legacy per-length dense prefill's tokens — the padding mask may not
    leak into real rows or pool pages."""
    cfg, params, prompts, _aot_dir, fresh = serve_setup
    legacy = ContinuousBatchingEngine(cfg, params, max_batch=2,
                                      block_size=8, num_blocks=64)
    rids = [legacy.add_request(p, 4) for p in prompts]
    out = legacy.run_to_completion()
    for rid, ref in zip(rids, fresh.values()):
        np.testing.assert_array_equal(out[rid], ref)


def test_engine_aot_warm_sampled_zero_compiles(serve_setup):
    """ISSUE 7 satellite: sampling runs at the fixed decode width, so
    the exported sampler program covers EVERY sampled sub-batch — a
    warm-started engine serving sampled requests records zero backend
    compiles and reproduces the fresh engine's sampled tokens exactly."""
    cfg, params, prompts, aot_dir, _fresh = serve_setup
    sampling = dict(temperature=0.8, top_k=16, top_p=0.9)
    ref_eng = _engine(cfg, params)
    rids = [ref_eng.add_request(p, 4, seed=i + 1, **sampling)
            for i, p in enumerate(prompts)]
    ref = ref_eng.run_to_completion()

    monitor = CompileMonitor().install()
    try:
        eng = _engine(cfg, params, aot_dir=aot_dir)
        assert eng.aot_loaded, eng.aot_error
        wids = [eng.add_request(p, 4, seed=i + 1, **sampling)
                for i, p in enumerate(prompts)]
        warm = eng.run_to_completion()
    finally:
        monitor.uninstall()
    assert monitor.n_compiles == 0, monitor.summary()
    for rid, wid in zip(rids, wids):
        np.testing.assert_array_equal(warm[wid], ref[rid])


def test_engine_config_mismatch_falls_back_with_event(serve_setup):
    """A geometry change (different pool size) must fall back to fresh
    compiles — cleanly, with the reason on the engine and an `aot`
    telemetry event — and still serve correctly."""
    cfg, params, prompts, aot_dir, fresh = serve_setup
    sink = MemorySink()
    REGISTRY.add_sink(sink)
    REGISTRY.enable()
    try:
        eng = ContinuousBatchingEngine(
            cfg, params, max_batch=2, block_size=8, num_blocks=32,
            prefill_buckets=(8,), aot_dir=aot_dir)
    finally:
        REGISTRY.disable()
        REGISTRY.remove_sink(sink)
    assert not eng.aot_loaded
    assert "config hash" in eng.aot_error
    events = [e for e in sink.by_kind("aot")
              if e.get("action") == "fallback"]
    assert events and events[0]["dir"] == aot_dir
    rid = eng.add_request(prompts[0], 4)
    np.testing.assert_array_equal(eng.run_to_completion()[rid],
                                  list(fresh.values())[0])


def test_engine_version_skew_falls_back(serve_setup, tmp_path):
    """A manifest stamped by another jax version is NOT ours: fall back
    cleanly (never deserialize)."""
    import json
    import shutil
    cfg, params, prompts, aot_dir, _fresh = serve_setup
    skew = tmp_path / "skew"
    shutil.copytree(aot_dir, skew)
    mpath = skew / "manifest.json"
    m = json.loads(mpath.read_text())
    m["env"]["jax"] = "0.0.1"
    mpath.write_text(json.dumps(m))
    eng = _engine(cfg, params, aot_dir=str(skew))
    assert not eng.aot_loaded and "skew" in eng.aot_error


def test_engine_magic_mismatch_falls_back(serve_setup, tmp_path):
    import json
    import shutil
    cfg, params, _prompts, aot_dir, _fresh = serve_setup
    old = tmp_path / "oldfmt"
    shutil.copytree(aot_dir, old)
    mpath = old / "manifest.json"
    m = json.loads(mpath.read_text())
    m["magic"] = "paddle_tpu.aot.v0"
    mpath.write_text(json.dumps(m))
    eng = _engine(cfg, params, aot_dir=str(old))
    assert not eng.aot_loaded and "manifest" in eng.aot_error


def test_crc_corruption_raises_typed_error(serve_setup, tmp_path):
    """Bit-rot on an executable payload (tests/faults.py injector) is a
    TYPED AotArtifactCorruptError from the store — and the engine turns
    it into a clean fresh-compile fallback."""
    import shutil
    cfg, params, prompts, aot_dir, _fresh = serve_setup
    rotten = tmp_path / "rot"
    shutil.copytree(aot_dir, rotten)
    corrupt_file(str(rotten / "decode.xbin"), offset=256)
    store = ArtifactStore(str(rotten))
    with pytest.raises(AotArtifactCorruptError, match="CRC"):
        store.get("decode")
    eng = _engine(cfg, params, aot_dir=str(rotten))
    assert not eng.aot_loaded and "CRC" in eng.aot_error
    rid = eng.add_request(prompts[0], 2)
    assert rid in eng.run_to_completion()


def test_missing_manifest_is_mismatch(tmp_path):
    store = ArtifactStore(str(tmp_path / "nowhere"))
    assert not store.exists()
    with pytest.raises(AotManifestMismatchError, match="no AOT manifest"):
        store.manifest()


# ---------------------------------------------------------------------
# rotation roots + GC (ISSUE 8 satellite)
# ---------------------------------------------------------------------
def _copy_generation(aot_dir, root, name):
    """A published generation without recompiling: clone an exported
    artifact dir under the rotation root."""
    import shutil
    gen = os.path.join(str(root), name)
    shutil.copytree(aot_dir, gen)
    return ArtifactStore(gen)


def test_rotation_publish_resolve_and_gc(serve_setup, tmp_path):
    """Loaders passing the ROOT as aot_dir follow the atomic `latest`
    pointer; publish(keep_last=N) prunes generations beyond N."""
    cfg, params, prompts, aot_dir, fresh = serve_setup
    root = tmp_path / "root"
    root.mkdir()
    _copy_generation(aot_dir, root, "gen-0001").publish()
    eng = _engine(cfg, params, aot_dir=str(root))
    assert eng.aot_loaded, eng.aot_error
    rid = eng.add_request(prompts[0], 4)
    np.testing.assert_array_equal(eng.run_to_completion()[rid],
                                  list(fresh.values())[0])

    _copy_generation(aot_dir, root, "gen-0002").publish(keep_last=2)
    _copy_generation(aot_dir, root, "gen-0003").publish(keep_last=2)
    names = sorted(os.listdir(root))
    assert names == ["gen-0002", "gen-0003", "latest"], names
    assert (root / "latest").read_text().strip() == "gen-0003"
    eng2 = _engine(cfg, params, aot_dir=str(root))
    assert eng2.aot_loaded, eng2.aot_error


def test_gc_never_removes_pointed_generation(serve_setup, tmp_path):
    """Pointer-last semantics: the generation `latest` names survives
    GC regardless of age — age prunes, the pointer decides liveness."""
    cfg, params, _prompts, aot_dir, _fresh = serve_setup
    root = tmp_path / "root"
    root.mkdir()
    oldest = _copy_generation(aot_dir, root, "gen-0001")
    _copy_generation(aot_dir, root, "gen-0002")
    _copy_generation(aot_dir, root, "gen-0003")
    oldest.publish()                      # pointer at the OLDEST
    removed = ArtifactStore(str(root)).gc(keep_last=1)
    assert [os.path.basename(r) for r in removed] == ["gen-0002"]
    assert sorted(os.listdir(root)) == ["gen-0001", "gen-0003", "latest"]
    eng = _engine(cfg, params, aot_dir=str(root))
    assert eng.aot_loaded, eng.aot_error  # still serves the pointed gen
    with pytest.raises(ValueError, match="keep_last"):
        ArtifactStore(str(root)).gc(keep_last=0)


def test_pointer_publish_crash_keeps_previous_live(serve_setup, tmp_path,
                                                   monkeypatch):
    """A crash at pointer-publish time (tests/faults.py failed-rename
    injector) leaves the PREVIOUS pointer intact and loadable — the
    checkpoint-manager durability recipe, reused."""
    from faults import SimulatedCrash, fail_replace
    cfg, params, _prompts, aot_dir, _fresh = serve_setup
    root = tmp_path / "root"
    root.mkdir()
    _copy_generation(aot_dir, root, "gen-0001").publish()
    gen2 = _copy_generation(aot_dir, root, "gen-0002")
    with fail_replace(monkeypatch, failures=1):
        with pytest.raises(SimulatedCrash):
            gen2.publish()
    assert (root / "latest").read_text().strip() == "gen-0001"
    eng = _engine(cfg, params, aot_dir=str(root))
    assert eng.aot_loaded, eng.aot_error
    gen2.publish()                        # retry succeeds
    assert (root / "latest").read_text().strip() == "gen-0002"


def test_rotation_bitrot_and_dangling_pointer_fall_back_typed(
        serve_setup, tmp_path):
    """Bit-rot on the pointed generation's manifest, or a pointer whose
    generation was deleted, is a typed fallback — never a wrong
    program, and the engine still serves via fresh compiles."""
    cfg, params, prompts, aot_dir, _fresh = serve_setup
    root = tmp_path / "root"
    root.mkdir()
    gen = _copy_generation(aot_dir, root, "gen-0001")
    gen.publish()
    corrupt_file(os.path.join(gen.directory, "manifest.json"), offset=8)
    eng = _engine(cfg, params, aot_dir=str(root))
    assert not eng.aot_loaded and "manifest" in eng.aot_error
    rid = eng.add_request(prompts[0], 2)
    assert rid in eng.run_to_completion()

    root2 = tmp_path / "root2"
    root2.mkdir()
    (root2 / "latest").write_text("gen-0042")
    eng2 = _engine(cfg, params, aot_dir=str(root2))
    assert not eng2.aot_loaded
    assert "deleted out from under" in eng2.aot_error


# ---------------------------------------------------------------------
# train step (hapi Model)
# ---------------------------------------------------------------------
class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(nn.functional.relu(self.fc1(x)))


def _make_model(aot_dir=None):
    core_rng.seed(0)
    m = Model(_MLP())
    m.prepare(optimizer=AdamW(learning_rate=1e-3),
              loss=nn.CrossEntropyLoss(), aot_dir=aot_dir)
    return m


def _batch(b=4):
    r = np.random.default_rng(1)
    return (r.standard_normal((b, 8)).astype(np.float32),
            r.integers(0, 4, (b,)).astype(np.int64))


def test_train_step_roundtrip_bit_identical(tmp_path):
    """Exported → reloaded train step equals fresh-compile bit-for-bit
    over BOTH its signatures (first step: per-name opt state; second:
    fused) with zero backend compiles."""
    x, y = _batch()
    export_train_step(_make_model(), [x], [y], str(tmp_path))
    ref = _make_model()
    ref.train_batch([x], [y])
    ref.train_batch([x], [y])
    want = {n: np.asarray(p._value)
            for n, p in ref.network.named_parameters()}
    aot = _make_model(aot_dir=str(tmp_path))
    monitor = CompileMonitor().install()
    try:
        aot.train_batch([x], [y])
        aot.train_batch([x], [y])
    finally:
        monitor.uninstall()
    assert aot._aot_error is None
    assert monitor.n_compiles == 0, monitor.summary()
    for n, p in aot.network.named_parameters():
        np.testing.assert_array_equal(want[n], np.asarray(p._value))


def test_train_step_rotation_root_resolves_and_rotates(tmp_path):
    """Model.prepare(aot_dir=ROOT) follows the `latest` pointer; a
    re-export with keep_last=1 prunes the old generation and the next
    prepare picks up the new one — the fleet upgrade loop."""
    x, y = _batch()
    root = str(tmp_path / "train_root")
    export_train_step(_make_model(), [x], [y], root, rotate=True,
                      keep_last=1)
    assert sorted(os.listdir(root)) == ["gen-0001", "latest"]
    m = _make_model(aot_dir=root)
    monitor = CompileMonitor().install()
    try:
        m.train_batch([x], [y])
    finally:
        monitor.uninstall()
    assert m._aot_error is None
    assert monitor.n_compiles == 0, monitor.summary()
    export_train_step(_make_model(), [x], [y], root, rotate=True,
                      keep_last=1)
    assert sorted(os.listdir(root)) == ["gen-0002", "latest"]
    m2 = _make_model(aot_dir=root)
    losses, _ = m2.train_batch([x], [y])
    assert m2._aot_error is None and np.isfinite(losses[0])


def test_train_step_unknown_signature_falls_back(tmp_path):
    """A batch shape the artifacts don't cover dispatches to a fresh
    jit — training continues, nothing raises."""
    x, y = _batch()
    export_train_step(_make_model(), [x], [y], str(tmp_path))
    m = _make_model(aot_dir=str(tmp_path))
    x2, y2 = _batch(b=6)                  # different leading dim
    losses, _ = m.train_batch([x2], [y2])
    assert np.isfinite(losses[0])


def test_train_step_corrupt_artifact_falls_back(tmp_path):
    x, y = _batch()
    export_train_step(_make_model(), [x], [y], str(tmp_path))
    corrupt_file(str(tmp_path / "train_step_init.xbin"), offset=128)
    m = _make_model(aot_dir=str(tmp_path))
    losses, _ = m.train_batch([x], [y])   # fresh-compile fallback
    assert np.isfinite(losses[0])
    assert m._aot_error is not None and "CRC" in m._aot_error


def test_one_device_artifact_loads_among_many_devices(tmp_path):
    """A program exported for ONE device loads onto that device and
    runs while the process holds several (conftest's 8 virtual CPUs).
    Told nothing, jax 0.9's loader assumes every local device executes
    and the call fails with "Expected args ... to have 8 shards"."""
    import jax.numpy as jnp
    assert len(jax.devices()) > 1
    dev = jax.devices()[3]
    x = jax.device_put(jnp.arange(8.0), dev)
    store = export_compiled(str(tmp_path), "double", jax.jit(lambda a: a * 2),
                            (x,), config={"kind": "one_device"})
    assert store.entry("double")["device_ids"] == [dev.id]
    out = ArtifactStore(str(tmp_path)).get("double")(x)
    assert out.devices() == {dev}
    np.testing.assert_array_equal(np.asarray(out), np.arange(8.0) * 2)
    # an artifact for a device this process lacks is a typed mismatch
    store.entry("double")["device_ids"] = [10 ** 6]
    with pytest.raises(AotManifestMismatchError, match="device ids"):
        store.get("double")


def test_export_jit_apply_roundtrip(tmp_path):
    """The raw fused-optimizer program (build_jit_apply) round-trips
    bit-exactly through the artifact store."""
    import jax.numpy as jnp
    params = {f"p{i}": jnp.asarray(
        rng.standard_normal(8 + i).astype(np.float32)) for i in range(3)}
    grads = {k: jnp.asarray(rng.standard_normal(v.shape)
                            .astype(np.float32))
             for k, v in params.items()}

    opt = AdamW(learning_rate=1e-3, weight_decay=0.01)
    state = opt.init_state(params)
    export_jit_apply(opt, params, grads, state, str(tmp_path),
                     donate=False)
    loaded = ArtifactStore(str(tmp_path)).get("jit_apply")
    p_ref, _ = AdamW(learning_rate=1e-3,
                     weight_decay=0.01).build_jit_apply(donate=False)(
        params, grads, state, 1e-3, 1)
    p_got, _ = loaded(params, grads, state, 1e-3, 1)
    for k in p_ref:
        np.testing.assert_array_equal(np.asarray(p_ref[k]),
                                      np.asarray(p_got[k]))


# ---------------------------------------------------------------------
# jit.save / jit.load aot=True
# ---------------------------------------------------------------------
def test_jit_save_load_aot_embedded_executable(tmp_path):
    from paddle_tpu.jit import load as jit_load
    from paddle_tpu.jit import save as jit_save
    from paddle_tpu.static import InputSpec

    net = _MLP()
    path = str(tmp_path / "m")
    jit_save(net, path, input_spec=[InputSpec([2, 8], "float32")],
             aot=True)
    x = rng.standard_normal((2, 8)).astype(np.float32)
    monitor = CompileMonitor().install()
    try:
        tl = jit_load(path)
        out = tl(x)
    finally:
        monitor.uninstall()
    assert tl.aot_loaded
    assert monitor.n_compiles == 0, monitor.summary()
    ref = net(pt.Tensor(x))
    np.testing.assert_allclose(np.asarray(out._value),
                               np.asarray(ref._value), rtol=1e-6)


def test_jit_save_aot_rejects_dynamic_dims(tmp_path):
    from paddle_tpu.jit import save as jit_save
    from paddle_tpu.static import InputSpec

    with pytest.raises(ValueError, match="dynamic"):
        jit_save(_MLP(), str(tmp_path / "dyn"),
                 input_spec=[InputSpec([None, 8], "float32")], aot=True)


def test_jit_load_aot_env_skew_uses_stablehlo(tmp_path):
    """Version skew on the embedded executable silently falls back to
    the portable STABLEHLO program; corruption raises typed."""
    import pickle
    from paddle_tpu.jit import load as jit_load
    from paddle_tpu.jit import save as jit_save
    from paddle_tpu.static import InputSpec

    net = _MLP()
    path = str(tmp_path / "m")
    jit_save(net, path, input_spec=[InputSpec([2, 8], "float32")],
             aot=True)
    with open(path + ".pdmodel", "rb") as f:
        blob = pickle.load(f)
    blob["aot"]["env"]["jaxlib"] = "9.9.9"
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump(blob, f)
    tl = jit_load(path)
    assert not tl.aot_loaded          # skew → portable path
    x = rng.standard_normal((2, 8)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(tl(x)._value),
                               np.asarray(net(pt.Tensor(x))._value),
                               rtol=1e-6)

    blob["aot"]["payload"] = blob["aot"]["payload"][:-7] + b"\xde" * 7
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump(blob, f)
    with pytest.raises(AotArtifactCorruptError, match="CRC"):
        jit_load(path)
