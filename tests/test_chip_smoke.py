"""Rehearsal of ``chip_smoke.py`` on the CPU: the phase functions run
in-process at tiny sizes (Pallas kernels in interpret mode), so the
script's control flow is exercised in tier-1; the real sizes run only on
the chip. Also: the script refuses to report anything off-chip, and the
executable store leaves jax's compile cache where the outside put it.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import chip_smoke  # noqa: E402  (repo-root module)

from deeplearning4j_tpu.common.environment import environment  # noqa: E402
from deeplearning4j_tpu.models import bert, causal_lm  # noqa: E402
from deeplearning4j_tpu.runtime import compile_cache  # noqa: E402


_lm_config = causal_lm.CausalLMConfig.tiny


def _mlp():
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(DenseLayer(n_in=6, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=3)).build())
    return MultiLayerNetwork(conf).init()


_X = np.random.RandomState(0).randn(4, 6).astype(np.float32)


class TestPhases:
    def test_train(self):
        rec = chip_smoke.phase_train(bert.BertConfig.tiny(), B=8, T=32,
                                     steps=3)
        assert len(rec["losses"]) == 3
        assert rec["losses"][-1] < rec["losses"][0]
        json.dumps(rec)

    def test_serve(self):
        rec = chip_smoke.phase_serve(
            _lm_config(), slots=4, max_ctx=128, buckets=[16, 64],
            prompt_lens=[4, 24, 8, 40, 12, 32], gen_lens=[10, 6, 8, 4, 5, 4],
            greedy_prompt=24, greedy_tokens=12)
        assert rec["compiles_after_warmup"] == 0
        assert len(rec["greedy_tokens"]) == 12
        assert rec["repeat_same_share"] == 1.0  # f32: bitwise repeatable
        assert rec["prefix_hits"] >= 1  # the repeat rode the prefix cache
        json.dumps(rec)

    def test_store(self, tmp_path):
        before = environment().cache_dir()
        rec = chip_smoke.phase_store(_mlp, jnp.asarray(_X),
                                     str(tmp_path / "store"))
        assert rec["v1"]["compiles_by_cache"].get("miss", 0) >= 1
        assert set(rec["v2"]["compiles_by_cache"]) == {"hit"}
        assert environment().cache_dir() == before  # restored
        json.dumps(rec)

    def test_store_refuses_a_store_that_cannot_load(self, tmp_path,
                                                    monkeypatch):
        """The repaired path is checked, not caught: if a stored entry
        does not load, the phase fails."""
        monkeypatch.setattr(compile_cache, "_load_executor",
                            lambda *a, **k: None)
        with pytest.raises(chip_smoke.SmokeFailure, match="second deploy"):
            chip_smoke.phase_store(_mlp, jnp.asarray(_X),
                                   str(tmp_path / "store"))

    def test_kernels(self, flash_everywhere):
        env = environment()
        rec = chip_smoke.phase_kernels(
            interpret=True, flash_shape=(1, 2, 128, 32),
            lm_config=_lm_config(), slots=2, max_ctx=64, bucket=16,
            decode_steps=3, mm_shape=(8, 128, 256), dtype=jnp.float32)
        assert rec["flash_attention"]["dispatch"]["path"] == "flash"
        assert rec["paged_decode"]["dispatch"]["path"] == "paged_flash"
        assert rec["dequant_matmul"]["dispatch"]["path"] == "fused"
        assert env.paged_kernel() == "auto"
        assert env.fused_dequant() == "auto"
        json.dumps(rec)

    def test_kernels_refuse_the_wrong_mode(self):
        """On the chip the kernels must be compiled: a phase told to
        expect compiled kernels fails where they would be interpreted."""
        with pytest.raises(chip_smoke.SmokeFailure, match="interpret"):
            chip_smoke.phase_kernels(
                interpret=False, flash_shape=(1, 2, 128, 32),
                lm_config=_lm_config(), slots=2, max_ctx=64, bucket=16,
                decode_steps=1, mm_shape=(8, 128, 256), dtype=jnp.float32)

    def test_four_chips(self):
        rec = chip_smoke.phase_four_chips(
            bert.BertConfig.tiny(), _lm_config(), B=8, T=32, max_ctx=64,
            bucket=16, gen_tokens=8, devices=jax.devices()[:4])
        want = sorted(d.id for d in jax.devices()[:4])
        assert rec["train"]["param_device_ids"] == want
        assert rec["serve"]["param_device_ids"] == want
        assert rec["serve"]["pool_device_ids"] == want
        assert "model" in rec["serve"]["pool_spec"]
        json.dumps(rec)


class TestMarginRule:
    def test_close_fails_past_the_bound(self):
        ref = np.ones((4,), np.float32)
        chip_smoke.close(ref, ref + chip_smoke.TOL / 2, "x")
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.close(ref, ref + 2 * chip_smoke.TOL, "x")
        with pytest.raises(chip_smoke.SmokeFailure, match="non-finite"):
            chip_smoke.close(ref, ref * np.nan, "x")

    def test_tokens_bind_only_where_the_reference_is_decided(self):
        logits = np.zeros((3, 5), np.float32)
        logits[0, 1] = 1.0                # decided: token must be 1
        logits[1, 2] = chip_smoke.TOL / 2  # undecided: any token passes
        logits[2, 4] = 1.0
        rec = chip_smoke.tokens_agree(logits, [1, 0, 4], "x")
        assert rec["decided"] == round(2 / 3, 4)
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.tokens_agree(logits, [0, 0, 4], "x")

    def test_continuations_may_part_only_at_an_undecided_position(self):
        sure = np.array([True, False, True])
        assert chip_smoke.same_continuation([1, 2, 3], sure, [1, 2, 3],
                                            "x") == 1.0
        chip_smoke.same_continuation([1, 2, 3], sure, [1, 9, 9], "x")
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.same_continuation([1, 2, 3], sure, [9, 2, 3], "x")


class TestOffChip:
    def test_main_refuses_the_cpu(self, capsys, monkeypatch, tmp_path):
        # main() places jax's cache with setdefault: give it a value so
        # the test leaves the process environment as it found it
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert chip_smoke.main([]) != 0
        out = capsys.readouterr()
        assert '"ok"' not in out.out
        assert out.out.strip() == ""
        assert "needs a TPU" in out.err

    def test_main_refuses_the_cpu_with_four_chips(self, capsys, monkeypatch,
                                                  tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert chip_smoke.main(["--chips", "4"]) != 0
        assert capsys.readouterr().out.strip() == ""


class TestCompileCachePlacedFromOutside:
    def test_store_leaves_jax_cache_dir_alone(self, monkeypatch, tmp_path):
        """With JAX_COMPILATION_CACHE_DIR set, neither resolving nor
        resetting the executable store may move jax's own cache — even
        with the backstop forced on and the store's directory moved."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "outside"))
        monkeypatch.setenv("DL4J_TPU_XLA_CACHE", "on")
        before = jax.config.jax_compilation_cache_dir
        updates = []
        real = jax.config.update

        def spy(name, value):
            updates.append(name)
            return real(name, value)

        monkeypatch.setattr(jax.config, "update", spy)
        env = environment()
        from deeplearning4j_tpu.common.environment import SystemProperties
        prev = env.property_override(SystemProperties.CACHE_DIR)
        try:
            for d in ("a", "b"):
                env.set_cache_dir(str(tmp_path / d))
                assert compile_cache.cache() is not None
                compile_cache.reset_cache()
                assert not compile_cache._backstop_wanted()
        finally:
            if prev is None:
                env.clear_property(SystemProperties.CACHE_DIR)
            else:
                env.set_property(SystemProperties.CACHE_DIR, prev)
            compile_cache.reset_cache()
        assert "jax_compilation_cache_dir" not in updates
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "a" / "xla").exists()
