"""Inference predictor facade (VERDICT r2 item 9; reference:
AnalysisPredictor inference/api/analysis_predictor.h:105 scoped to the
TPU-sensible subset): Config/create_predictor handle API over jit.save'd
STABLEHLO, plus the LLM serving path — save → load in a FRESH process →
paged-KV generate() equality vs the in-process rollout for GPT and Llama.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.inference import (Config, LLMPredictor, Predictor,
                                  create_predictor)
from paddle_tpu.static import InputSpec


def _np(x):
    return np.asarray(x._value)


class TestPredictorFacade:
    def _save_model(self, tmp_path):
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 2))
        net.eval()
        prefix = str(tmp_path / "m")
        paddle.jit.save(net, prefix, input_spec=[InputSpec([None, 4])])
        return net, prefix

    def test_handle_api_matches_eager(self, tmp_path):
        net, prefix = self._save_model(tmp_path)
        pred = create_predictor(Config(prefix))
        x = np.random.RandomState(0).randn(3, 4).astype(np.float32)
        h = pred.get_input_handle(pred.get_input_names()[0])
        h.copy_from_cpu(x)
        pred.run()
        out = pred.get_output_handle(
            pred.get_output_names()[0]).copy_to_cpu()
        np.testing.assert_allclose(out, _np(net(paddle.to_tensor(x))),
                                   atol=1e-5)

    def test_direct_run_api(self, tmp_path):
        net, prefix = self._save_model(tmp_path)
        pred = Predictor(Config(prefix + ".pdmodel"))
        x = np.random.RandomState(1).randn(2, 4).astype(np.float32)
        (out,) = pred.run([x])
        np.testing.assert_allclose(out, _np(net(paddle.to_tensor(x))),
                                   atol=1e-5)

    def test_missing_model_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            Predictor(Config(str(tmp_path / "nope")))

    def test_dynamic_batch(self, tmp_path):
        net, prefix = self._save_model(tmp_path)
        pred = create_predictor(Config(prefix))
        for b in (1, 5):
            x = np.random.randn(b, 4).astype(np.float32)
            (out,) = pred.run([x])
            assert out.shape == (b, 2)


_FRESH_GEN = r"""
import sys
sys.path.insert(0, "/root/repo")
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
from paddle_tpu.inference import create_llm_predictor
pred = create_llm_predictor(sys.argv[1])
ids = np.load(sys.argv[2])
out = pred.generate(ids, max_new_tokens=5, temperature=0.0)
np.save(sys.argv[3], np.asarray(out))
"""


class TestLLMServing:
    def _fresh_process_generate(self, tmp_path, family, cfg, params, ids):
        pred = LLMPredictor(family, cfg, params)
        mdir = str(tmp_path / f"{family}_model")
        pred.save(mdir)
        np.save(str(tmp_path / "ids.npy"), ids)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out_path = str(tmp_path / "out.npy")
        r = subprocess.run(
            [sys.executable, "-c", _FRESH_GEN, mdir,
             str(tmp_path / "ids.npy"), out_path],
            env=env, capture_output=True, text=True, timeout=420)
        assert r.returncode == 0, r.stderr[-2000:]
        return np.load(out_path)

    @pytest.mark.slow
    def test_gpt_fresh_process_generate_equality(self, tmp_path):
        from paddle_tpu.models.generation import gpt_generate
        from paddle_tpu.models.gpt import GPTConfig, build_gpt_train_step
        from paddle_tpu import parallel as dist
        from paddle_tpu.parallel.topology import HybridTopology, set_topology
        cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_heads=4, max_position_embeddings=64)
        dist.init_topology()
        _, init_fn = build_gpt_train_step(cfg, None, num_microbatches=1)
        params = init_fn(0)["params"]
        set_topology(HybridTopology())
        ids = np.random.RandomState(0).integers(0, 97, (2, 8)) \
            if hasattr(np.random.RandomState(0), "integers") else \
            np.random.RandomState(0).randint(0, 97, (2, 8))
        ids = np.asarray(ids, np.int32)
        want = np.asarray(gpt_generate(params, cfg, ids, max_new_tokens=5,
                                       temperature=0.0))
        got = self._fresh_process_generate(tmp_path, "gpt", cfg, params,
                                           ids)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.slow
    def test_llama_fresh_process_generate_equality(self, tmp_path):
        from paddle_tpu.models.generation import llama_generate
        from paddle_tpu.models.llama import (LlamaConfig,
                                             build_llama_train_step)
        from paddle_tpu import parallel as dist
        from paddle_tpu.parallel.topology import HybridTopology, set_topology
        cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                          num_heads=4, num_kv_heads=2,
                          max_position_embeddings=64)
        dist.init_topology()
        _, init_fn = build_llama_train_step(cfg, None, num_microbatches=1)
        params = init_fn(0)["params"]
        set_topology(HybridTopology())
        ids = np.asarray(
            np.random.RandomState(1).randint(0, 97, (1, 6)), np.int32)
        want = np.asarray(llama_generate(params, cfg, ids,
                                         max_new_tokens=5, temperature=0.0))
        got = self._fresh_process_generate(tmp_path, "llama", cfg, params,
                                           ids)
        np.testing.assert_array_equal(got, want)
