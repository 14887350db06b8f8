"""Pallas fast path round 2: the paged-flash-decode kernel and the fused
int8 dequant-matmul (kernels/paged_flash_decode + quant/transforms +
kernels dispatch plumbing).

Covers the acceptance contract of the kernel PR: the paged-flash kernel
is numerically a drop-in for the block-table gather it replaces (kernel
vs reference math, argmax-identical model logits for both the Q=1 decode
and the Q=k+1 speculative-verify shape, token-identical engine output
through greedy / speculative / prefix-cache warm attach); the fused
dequant-matmul matches the XLA cast-then-dot within the quant
deploy-gate divergence and keeps weights int8 at rest in the jitted HLO
(no full-precision weight tensor materializes); dispatch is decided at
trace time from pool tileability — never from the query length, so
spec-k configs cannot flap between paths (satellite 6) — and every
decision ticks ``dl4j_kernel_dispatch_total{kernel,path}`` and lands in
the ``/debug/decode`` snapshot; and a warm decode loop with the kernel
on performs zero steady-state recompiles.

CPU CI runs the kernel in Pallas interpret mode (``_interpret()`` —
identical math, XLA-inlined), which is exactly the fallback contract
MIGRATING.md documents.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common.environment import (SystemProperties,
                                                   environment)
from deeplearning4j_tpu.common.metrics import registry
from deeplearning4j_tpu.kernels import (attention_dispatch,
                                        dispatch_snapshot,
                                        paged_flash_decode)
from deeplearning4j_tpu.kernels.paged_flash_decode import tileable
from deeplearning4j_tpu.models import causal_lm
from deeplearning4j_tpu.quant.transforms import (QuantizedTensor,
                                                 dequant_matmul,
                                                 dequantize,
                                                 quantize_model,
                                                 quantize_tensor)
from deeplearning4j_tpu.runtime.generation import DecodeEngine
from deeplearning4j_tpu.runtime.inference import counted_jit

CFG = causal_lm.CausalLMConfig.tiny()

_KERNEL_HELP = ("Hand-written-kernel vs fallback path decisions per "
                "kernel family, evaluated at trace time")


@pytest.fixture(scope="module")
def model():
    return causal_lm.CausalLM(CFG, seed=0)


def _kernel_counter():
    return registry().counter("dl4j_kernel_dispatch_total", _KERNEL_HELP,
                              labels=("kernel", "path"))


def _paged_mode(mode):
    """Set DL4J_TPU_PAGED_KERNEL; caller restores via the returned fn."""
    env = environment()
    env.set_paged_kernel(mode)
    return lambda: env.clear_property(SystemProperties.PAGED_KERNEL)


def _reference_paged_attention(q, k_pages, v_pages, tables, lengths,
                               scale):
    """The exact XLA block-table-gather math the kernel replaces
    (mirrors models/causal_lm.paged_decode's fallback branch)."""
    S, Q, H, D = q.shape
    MB = tables.shape[1]
    Bs = k_pages.shape[1]
    C = MB * Bs
    ks = jnp.take(k_pages, tables, axis=0).reshape(S, C, H, D)
    vs = jnp.take(v_pages, tables, axis=0).reshape(S, C, H, D)
    att = jnp.einsum("sqhd,schd->shqc", q, ks) * scale
    pos = lengths[:, None] + jnp.arange(Q)[None, :]
    key_mask = jnp.arange(C)[None, None, :] <= pos[:, :, None]
    att = jnp.where(key_mask[:, None, :, :], att,
                    jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(
        q.dtype)
    return jnp.einsum("shqc,schd->sqhd", probs, vs)


def _kernel_inputs(Q, S=3, MB=2, Bs=8, H=2, D=128, seed=0):
    rng = np.random.RandomState(seed)
    N = S * MB + 1  # page 0 left as scratch, like the engine's pool
    q = jnp.asarray(rng.randn(S, Q, H, D).astype(np.float32) * 0.4)
    kp = jnp.asarray(rng.randn(N, Bs, H, D).astype(np.float32) * 0.4)
    vp = jnp.asarray(rng.randn(N, Bs, H, D).astype(np.float32) * 0.4)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, N)).reshape(S, MB).astype(np.int32))
    # committed lengths: empty slot, unaligned, and nearly-full
    lengths = jnp.asarray([0, 5, MB * Bs - Q][:S], jnp.int32)
    return q, kp, vp, tables, lengths


# ---------------------------------------------------------------------------
# tentpole (a): kernel vs the gather reference math
# ---------------------------------------------------------------------------

class TestPagedFlashKernelParity:
    @pytest.mark.parametrize("Q", [1, 3])
    def test_matches_gather_reference(self, Q):
        """Online-softmax block streaming == one-shot gather softmax, for
        the Q=1 decode and Q=3 speculative-verify shapes, across empty /
        unaligned / nearly-full slots."""
        q, kp, vp, tables, lengths = _kernel_inputs(Q)
        scale = 1.0 / np.sqrt(q.shape[-1])
        ref = _reference_paged_attention(q, kp, vp, tables, lengths, scale)
        out = paged_flash_decode(q, kp, vp, tables, lengths, scale=scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_matches_under_jit(self):
        q, kp, vp, tables, lengths = _kernel_inputs(Q=1, seed=7)
        scale = 1.0 / np.sqrt(q.shape[-1])
        ref = _reference_paged_attention(q, kp, vp, tables, lengths, scale)
        out = jax.jit(
            lambda *a: paged_flash_decode(*a, scale=scale))(
                q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_tileable_predicate(self):
        """The auto-gate: lane dim must fill the 128-wide VPU lanes and
        the page must tile the 8-row sublane."""
        assert tileable(128, 8)
        assert tileable(256, 16)
        assert not tileable(64, 8)     # head_dim under a lane tile
        assert not tileable(128, 6)    # page not sublane-aligned
        assert not tileable(CFG.head_dim, 16)  # the tiny test config


# ---------------------------------------------------------------------------
# tentpole (a): model-level identity, gather vs kernel
# ---------------------------------------------------------------------------

class TestModelTokenIdentity:
    @pytest.mark.parametrize("Q", [1, 3])
    def test_paged_decode_argmax_identical(self, model, Q):
        """CausalLM.paged_decode produces argmax-identical logits whether
        the read is the XLA gather or the forced (interpret-mode on CPU)
        Pallas kernel — for both the decode and spec-verify shapes."""
        S, MB, Bs = 2, 2, 16
        cache = model.init_paged_kv_cache(S * MB + 1, Bs)
        rng = np.random.RandomState(3)
        k_shape = cache["k"].shape
        cache = {
            "k": jnp.asarray(rng.randn(*k_shape).astype(np.float32) * .3),
            "v": jnp.asarray(rng.randn(*k_shape).astype(np.float32) * .3),
        }
        tables = jnp.asarray(
            np.arange(1, S * MB + 1).reshape(S, MB), np.int32)
        toks = jnp.asarray(rng.randint(0, CFG.vocab_size, (S, Q)),
                           jnp.int32)
        lengths = jnp.asarray([0, 9], jnp.int32)

        outs = {}
        for mode in ("off", "on"):
            restore = _paged_mode(mode)
            try:
                _, lg = model.paged_decode(model.params, cache, tables,
                                           toks, lengths)
                outs[mode] = np.asarray(lg)
            finally:
                restore()
        assert (outs["off"].argmax(-1) == outs["on"].argmax(-1)).all()
        np.testing.assert_allclose(outs["off"], outs["on"], atol=5e-4)


# ---------------------------------------------------------------------------
# tentpole (a): engine-level token identity, gather vs kernel
# ---------------------------------------------------------------------------

def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int32)


def _engine_tokens(model, mode, prompts, engine_kw=None):
    """Greedy-generate each prompt in sequence under one paged-kernel
    mode; returns the tuple-of-token-tuples."""
    restore = _paged_mode(mode)
    try:
        eng = DecodeEngine(model, slots=2, max_ctx=64, prompt_buckets=[16],
                           **(engine_kw or {}))
        try:
            out = []
            for p in prompts:
                r = eng.generate(p, max_tokens=8,
                                 temperature=0.0).result(timeout=120)
                out.append(tuple(r["tokens"]))
            return tuple(out)
        finally:
            eng.close(10)
    finally:
        restore()


class TestEngineTokenIdentity:
    def test_greedy_identical(self, model):
        prompts = [_prompt(7, seed=11), _prompt(13, seed=12)]
        assert (_engine_tokens(model, "off", prompts)
                == _engine_tokens(model, "on", prompts))

    def test_speculative_identical(self, model):
        """The Q=k+1 verify pass rides the same kernel: a drafted engine
        must emit the same greedy tokens on either read path."""
        kw = {"draft_model": causal_lm.CausalLM(CFG, seed=3), "spec_k": 3}
        prompts = [_prompt(9, seed=21)]
        assert (_engine_tokens(model, "off", prompts, kw)
                == _engine_tokens(model, "on", prompts, kw))

    def test_prefix_warm_attach_identical(self, model):
        """Second request shares a radix-cached prefix (warm attach skips
        prefill for the shared blocks) — still token-identical across
        read paths."""
        base = _prompt(24, seed=31)
        prompts = [base, np.concatenate([base[:16], _prompt(4, seed=32)])]
        assert (_engine_tokens(model, "off", prompts)
                == _engine_tokens(model, "on", prompts))


# ---------------------------------------------------------------------------
# tentpole (b): fused int8 dequant-matmul
# ---------------------------------------------------------------------------

def _fused_mode(mode):
    env = environment()
    env.set_fused_dequant(mode)
    return lambda: env.clear_property(SystemProperties.FUSED_DEQUANT)


class TestFusedDequantMatmul:
    def _w(self, k=256, n=256, seed=0):
        rng = np.random.RandomState(seed)
        return quantize_tensor(
            jnp.asarray(rng.randn(k, n).astype(np.float32) * 0.05))

    @pytest.mark.parametrize("x_shape", [(32, 256), (2, 5, 256), (256,)])
    def test_matches_xla_path(self, x_shape):
        """Forced-on fused kernel == the XLA cast-then-dot fallback, for
        2-D, batched 3-D, and vector activations."""
        w = self._w()
        x = jnp.asarray(
            np.random.RandomState(1).randn(*x_shape).astype(np.float32))
        restore = _fused_mode("off")
        try:
            ref = np.asarray(dequant_matmul(x, w))
        finally:
            restore()
        restore = _fused_mode("on")
        try:
            out = np.asarray(jax.jit(lambda a: dequant_matmul(a, w))(x))
        finally:
            restore()
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_plain_array_passthrough(self):
        """Non-quantized weights bypass both paths entirely — identity
        with a plain jnp.dot, whatever the knob says."""
        w = jnp.asarray(np.random.RandomState(2).randn(16, 8), jnp.float32)
        x = jnp.asarray(np.random.RandomState(3).randn(4, 16), jnp.float32)
        restore = _fused_mode("on")
        try:
            np.testing.assert_allclose(np.asarray(dequant_matmul(x, w)),
                                       np.asarray(jnp.dot(x, w)))
        finally:
            restore()

    def test_no_full_precision_weight_in_hlo(self):
        """int8 at rest: the jitted program holds the 512x512 weight only
        as i8; no full-size f32 copy of it materializes (the in-kernel
        dequant happens tile-by-tile in VMEM). StableHLO types are
        ``tensor<...xi8>``-style."""
        w = self._w(512, 512)
        x = jnp.asarray(
            np.random.RandomState(4).randn(8, 512).astype(np.float32))
        restore = _fused_mode("on")
        try:
            txt = jax.jit(lambda a: dequant_matmul(a, w)).lower(x).as_text()
        finally:
            restore()
        assert "tensor<512x512xi8>" in txt
        assert "tensor<512x512xf32>" not in txt

    def test_quantized_model_twin_within_divergence(self, model):
        """Full-model gate: an int8 twin's logits through the fused path
        stay within DL4J_TPU_QUANT_MAX_DIVERGENCE of the dequant-first
        path, with identical greedy argmax."""
        env = environment()
        qm = quantize_model(causal_lm.CausalLM(CFG, seed=0))
        ids = jnp.asarray(_prompt(12, seed=41)[None, :])
        restore = _fused_mode("off")
        try:
            ref = np.asarray(qm.forward(ids))
        finally:
            restore()
        restore = _fused_mode("on")
        try:
            out = np.asarray(qm.forward(ids))
        finally:
            restore()
        assert float(np.abs(out - ref).max()) <= env.quant_max_divergence()
        assert (out.argmax(-1) == ref.argmax(-1)).all()

    def test_dequantize_unchanged(self):
        """The at-rest representation round-trips independently of the
        matmul path (dequantize() is the scale*q contract)."""
        w = self._w(8, 8, seed=5)
        assert isinstance(w, QuantizedTensor)
        np.testing.assert_allclose(
            np.asarray(dequantize(w)),
            np.asarray(w.q.astype(jnp.float32) * w.scale))


# ---------------------------------------------------------------------------
# satellite 1 + 2: dispatch counters and the /debug/decode join
# ---------------------------------------------------------------------------

class TestKernelDispatchTelemetry:
    def test_paged_decision_ticks_both_counters(self):
        """A paged dispatch ticks the existing per-path attention counter
        AND the new per-kernel-family counter with matching labels."""
        att = registry().counter(
            "dl4j_attn_dispatch_total",
            "Attention path decisions for flash=True configs",
            labels=("path",))
        fam = _kernel_counter()
        b_att = att.labels(path="paged_flash").value()
        b_fam = fam.labels(kernel="paged_decode",
                           path="paged_flash").value()
        restore = _paged_mode("on")
        try:
            assert attention_dispatch(1, paged=True, head_dim=128,
                                      block_size=8) == "paged_flash"
        finally:
            restore()
        assert att.labels(path="paged_flash").value() == b_att + 1
        assert fam.labels(kernel="paged_decode",
                          path="paged_flash").value() == b_fam + 1

    def test_dequant_decision_ticks_kernel_counter(self):
        fam = _kernel_counter()
        before = fam.labels(kernel="dequant_matmul", path="fused").value()
        w = quantize_tensor(jnp.ones((128, 128), jnp.float32))
        x = jnp.ones((4, 128), jnp.float32)
        restore = _fused_mode("on")
        try:
            dequant_matmul(x, w)
        finally:
            restore()
        assert fam.labels(kernel="dequant_matmul",
                          path="fused").value() == before + 1

    def test_dispatch_snapshot_reports_last_decision(self):
        """dispatch_snapshot() (the /debug/decode "kernels" join) records
        kernel name, chosen path, and the human-readable fallback
        reason of the most recent decision per family."""
        restore = _paged_mode("off")
        try:
            attention_dispatch(1, paged=True, head_dim=128, block_size=8)
        finally:
            restore()
        snap = dispatch_snapshot()
        rec = snap["paged_decode"]
        assert rec["kernel"] == "paged_decode"
        assert rec["path"] == "paged"
        assert rec["reason"] == "DL4J_TPU_PAGED_KERNEL=off"
        # snapshot hands out copies, not live references
        rec["path"] = "tampered"
        assert dispatch_snapshot()["paged_decode"]["path"] == "paged"

    def test_debug_snapshot_joins_kernels(self, model):
        """DecodeEngine.debug_snapshot (served at /debug/decode) carries
        the kernels section so operators can see which read path served
        the last compiled dispatch and why."""
        eng = DecodeEngine(model, slots=2, max_ctx=64, prompt_buckets=[16])
        try:
            eng.generate(_prompt(6, seed=51),
                         max_tokens=2).result(timeout=120)
            snap = eng.debug_snapshot()
        finally:
            eng.close(10)
        assert "kernels" in snap
        pd = snap["kernels"].get("paged_decode")
        assert pd is not None and pd["path"] in ("paged", "paged_flash")
        if pd["path"] == "paged":
            assert pd["reason"]  # fallbacks always say why


# ---------------------------------------------------------------------------
# satellite 6: the pin decision comes from tileability, never seq_len
# ---------------------------------------------------------------------------

class TestSpecVerifyPathStability:
    @pytest.mark.parametrize("mode", ["auto", "on", "off"])
    @pytest.mark.parametrize("tile", [(128, 8), (CFG.head_dim, 16)])
    def test_q1_and_qk1_always_same_path(self, mode, tile):
        """Q=1 decode and Q=k+1 spec-verify land on the SAME paged path
        in every mode and for every pool layout: the decision reads only
        kernel tileability, so spec-k configs cannot flap between the
        gather and the kernel across draft lengths."""
        hd, bs = tile
        env = environment()
        prev = env.spec_draft_k() if hasattr(env, "spec_draft_k") else None
        restore = _paged_mode(mode)
        try:
            if prev is not None:
                env.set_property(SystemProperties.SPEC_DRAFT_K, 3)
            paths = {attention_dispatch(q, paged=True, head_dim=hd,
                                        block_size=bs)
                     for q in (1, 4, 9)}  # decode, k=3 verify, k=8 verify
        finally:
            restore()
            if prev is not None:
                env.clear_property(SystemProperties.SPEC_DRAFT_K)
        assert len(paths) == 1
        assert paths <= {"paged", "paged_flash"}

    def test_flash_rule_never_moves_paged(self, flash_everywhere):
        """An adversarial rule (flash for everything) must not pull the
        paged read onto the slab flash kernel."""
        restore = _paged_mode("off")
        try:
            assert attention_dispatch(512, paged=True, head_dim=128,
                                      block_size=8) == "paged"
        finally:
            restore()

    def test_prefill_view_stays_on_gather(self):
        """Callers with no pool tile info (paged_prefill's contiguous
        view) always get the gather path, even when the kernel is forced
        on — the kernel contract is decode-shaped queries only."""
        restore = _paged_mode("on")
        try:
            assert attention_dispatch(32, paged=True) == "paged"
        finally:
            restore()


# ---------------------------------------------------------------------------
# acceptance: zero steady-state recompiles with the kernel on
# ---------------------------------------------------------------------------

class TestSteadyStateCompiles:
    def test_warm_decode_loop_never_retraces(self, model):
        """The path decision is trace-time: after the first compile, a
        growing-lengths greedy loop through the kernel path compiles
        nothing (same invariant the engine's zero-recompile gate holds
        for the gather path)."""
        env = environment()
        S, MB, Bs = 2, 2, 16
        cache = model.init_paged_kv_cache(S * MB + 1, Bs)
        tables = jnp.asarray(
            np.arange(1, S * MB + 1).reshape(S, MB), np.int32)
        toks = jnp.ones((S, 1), jnp.int32)
        lengths = jnp.asarray([0, 3], jnp.int32)
        restore = _paged_mode("on")
        try:
            step = counted_jit(
                lambda c, t, ln: model.paged_decode(model.params, c,
                                                    tables, t, ln),
                "test_paged_kernel_steady_state")
            cache, lg = step(cache, toks, lengths)  # compile + warm
            jax.block_until_ready(lg)
            env.reset_compile_count()
            for _ in range(4):
                cache, lg = step(cache, toks, lengths)
                toks = lg[:, -1].argmax(-1).astype(jnp.int32)[:, None]
                lengths = lengths + 1
            jax.block_until_ready(lg)
            assert env.compile_count() == 0
        finally:
            restore()
            env.reset_compile_count()


# ---------------------------------------------------------------------------
# env knob plumbing
# ---------------------------------------------------------------------------

class TestKnobPlumbing:
    @pytest.mark.parametrize("accessor,prop", [
        ("paged_kernel", SystemProperties.PAGED_KERNEL),
        ("fused_dequant", SystemProperties.FUSED_DEQUANT),
    ])
    def test_tri_state_with_auto_fallback(self, accessor, prop):
        env = environment()
        get = getattr(env, accessor)
        assert get() == "auto"  # shipped default
        try:
            for v in ("on", "off", "auto"):
                env.set_property(prop, v)
                assert get() == v
            env.set_property(prop, "bogus")  # unparseable → auto
            assert get() == "auto"
        finally:
            env.clear_property(prop)
        assert get() == "auto"
