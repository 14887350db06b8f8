"""Generative serving fast path (models/causal_lm + runtime/generation +
serving /generate).

Covers the acceptance contract of the generative PR: KV-cached
prefill/decode is token-identical to the full-recompute forward;
continuous batching admits/leaves per token (no head-of-line blocking,
deterministic under concurrency, no stale-KV leakage across slot reuse);
steady-state decode performs zero recompiles after warmup (one prefill
executable per prompt bucket + one decode executable); seq-len-1 decode
shapes always dispatch to the XLA attention path; donated-cache steps
record cache=bypass instead of silently missing from compile telemetry;
and POST /v1/models/<name>/generate works end-to-end through admission +
trace context with reconstructable prefill/decode spans.
"""
import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common.environment import environment
from deeplearning4j_tpu.common.metrics import registry
from deeplearning4j_tpu.models import causal_lm
from deeplearning4j_tpu.runtime import compile_cache
from deeplearning4j_tpu.runtime.generation import (DecodeEngine,
                                                   is_generative_model,
                                                   sample_tokens)
from deeplearning4j_tpu.runtime.inference import EngineClosedError

CFG = causal_lm.CausalLMConfig.tiny()


@pytest.fixture(scope="module")
def model():
    return causal_lm.CausalLM(CFG, seed=0)


@pytest.fixture(scope="module")
def shared_engine(model):
    """One warmed engine shared by the read-only decode tests (engine
    construction compiles executables; lifecycle/poison tests build their
    own)."""
    eng = DecodeEngine(model, slots=3, max_ctx=64, prompt_buckets=[32])
    yield eng
    eng.close(10)


def _wait_until(fn, timeout=5.0):
    """Poll for an eventually-true read (ring records are written after
    the response bytes reach the client)."""
    t0 = time.time()
    while time.time() - t0 < timeout:
        v = fn()
        if v:
            return v
        time.sleep(0.02)
    return fn()


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int32)


_REF_JIT = {}


def _ref_greedy(model, prompt, n):
    """Greedy continuation via the full-recompute forward (the O(T²)
    reference the cached path must match token for token). One fixed
    [1, 64] executable per model so the whole module pays one compile."""
    fwd = _REF_JIT.get(id(model))
    if fwd is None:
        fwd = jax.jit(lambda ids: causal_lm.forward(model.params, ids,
                                                    model.config))
        _REF_JIT[id(model)] = fwd
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        ids = np.zeros((1, 64), np.int32)
        ids[0, :len(toks)] = toks
        logits = fwd(jnp.asarray(ids))
        tok = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(tok)
        toks.append(tok)
    return out


def _engine(model, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_ctx", 64)
    kw.setdefault("prompt_buckets", [32])
    return DecodeEngine(model, **kw)


# ---------------------------------------------------------------------------
# model: causal forward + cache-aware attention
# ---------------------------------------------------------------------------

class TestCausalLM:
    def test_forward_shapes_and_dtype(self, model):
        logits = model.forward(jnp.zeros((2, 5), jnp.int32))
        assert logits.shape == (2, 5, CFG.vocab_size)
        assert logits.dtype == jnp.float32

    def test_causality(self, model):
        """Changing a later token must not change earlier positions'
        logits — the causal-mask contract autoregression rests on."""
        ids = _prompt(10, seed=1)
        a = model.forward(jnp.asarray(ids[None]))
        ids2 = ids.copy()
        ids2[7] = (ids2[7] + 1) % CFG.vocab_size
        b = model.forward(jnp.asarray(ids2[None]))
        np.testing.assert_allclose(np.asarray(a[0, :7]),
                                   np.asarray(b[0, :7]), atol=1e-5)
        assert not np.allclose(np.asarray(a[0, 7:]), np.asarray(b[0, 7:]))

    def test_prefill_then_decode_matches_forward(self, model):
        """prefill(padded prompt) + N cached decode steps == the full
        forward's greedy continuation, token for token."""
        prompt = _prompt(6, seed=2)
        ref = _ref_greedy(model, prompt, 6)
        cache = model.init_kv_cache(slots=2, max_ctx=32)
        ids = np.zeros((1, 16), np.int32)
        ids[0, :6] = prompt
        cache, logits = model.prefill(
            model.params, cache, jnp.asarray(ids),
            jnp.asarray(1, jnp.int32), jnp.asarray(6, jnp.int32))
        got = [int(jnp.argmax(logits))]
        decode = jax.jit(model.decode)  # one executable for the loop
        tokens = np.zeros(2, np.int32)
        lengths = np.zeros(2, np.int32)
        for i in range(5):
            tokens[1], lengths[1] = got[-1], 6 + i
            cache, logits = decode(model.params, cache,
                                   jnp.asarray(tokens),
                                   jnp.asarray(lengths))
            got.append(int(jnp.argmax(logits[1])))
        assert got == ref

    def test_kv_cache_shape_and_ctx_cap(self, model):
        cache = model.init_kv_cache(slots=3, max_ctx=16)
        assert cache["k"].shape == (3, CFG.num_layers, 16, CFG.num_heads,
                                    CFG.head_dim)
        with pytest.raises(ValueError, match="max_position_embeddings"):
            model.init_kv_cache(slots=1,
                                max_ctx=CFG.max_position_embeddings + 1)

    def test_protocol_detection(self, model):
        assert is_generative_model(model)
        assert not is_generative_model(object())


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

class TestSampling:
    def test_greedy_at_zero_temperature(self):
        logits = jnp.asarray(np.random.RandomState(0).randn(3, 17),
                             jnp.float32)
        toks = sample_tokens(logits, jnp.zeros(3), jnp.zeros(3, jnp.int32),
                             jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.argmax(np.asarray(logits), -1))

    def test_top_k_one_is_greedy(self):
        logits = jnp.asarray(np.random.RandomState(1).randn(4, 11),
                             jnp.float32)
        toks = sample_tokens(logits, jnp.ones(4),
                             jnp.ones(4, jnp.int32),
                             jax.random.PRNGKey(7))
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.argmax(np.asarray(logits), -1))

    def test_top_k_restricts_support(self):
        logits = jnp.asarray(np.random.RandomState(2).randn(1, 50),
                             jnp.float32)
        top3 = set(np.argsort(np.asarray(logits[0]))[-3:])
        for seed in range(20):
            t = sample_tokens(logits, jnp.ones(1) * 2.0,
                              jnp.full(1, 3, jnp.int32),
                              jax.random.PRNGKey(seed))
            assert int(t[0]) in top3

    def test_per_slot_mixed_configs(self):
        # slot 0 greedy, slot 1 sampled — one call, fixed shapes
        logits = jnp.asarray(np.random.RandomState(3).randn(2, 29),
                             jnp.float32)
        toks = sample_tokens(logits, jnp.asarray([0.0, 1.5]),
                             jnp.asarray([0, 0], jnp.int32),
                             jax.random.PRNGKey(11))
        assert int(toks[0]) == int(np.argmax(np.asarray(logits[0])))
        assert 0 <= int(toks[1]) < 29


# ---------------------------------------------------------------------------
# DecodeEngine: correctness, continuous batching, lifecycle
# ---------------------------------------------------------------------------

class TestDecodeEngine:
    def test_greedy_matches_recompute_reference(self, model,
                                                shared_engine):
        prompt = _prompt(7, seed=3)
        ref = _ref_greedy(model, prompt, 8)
        res = shared_engine.generate(prompt, max_tokens=8).result(
            timeout=60)
        assert res["tokens"] == ref
        assert res["finish_reason"] == "length"
        assert res["prompt_tokens"] == 7
        assert res["completion_tokens"] == 8
        assert res["ttft_s"] > 0

    def test_eos_stop(self, model, shared_engine):
        prompt = _prompt(5, seed=4)
        ref = _ref_greedy(model, prompt, 1)
        res = shared_engine.generate(prompt, max_tokens=16,
                                     eos_token=ref[0]).result(timeout=60)
        assert res["tokens"] == ref[:1]
        assert res["finish_reason"] == "eos"

    def test_concurrent_equals_sequential(self, model, shared_engine):
        """Continuous batching must not change outputs: N requests
        submitted together decode to exactly what each decodes alone."""
        prompts = [_prompt(n, seed=10 + n) for n in (4, 9, 14)]
        refs = [_ref_greedy(model, p, 5) for p in prompts]
        futs = [shared_engine.generate(p, max_tokens=5) for p in prompts]
        for fut, ref in zip(futs, refs):
            assert fut.result(timeout=60)["tokens"] == ref

    def test_no_head_of_line_blocking(self, model, shared_engine):
        """A short request admitted after a long one must finish first —
        the whole point of per-token join/leave."""
        done = []
        long_fut = shared_engine.generate(_prompt(4, seed=20),
                                          max_tokens=30)
        long_fut.add_done_callback(lambda f: done.append("long"))
        short_fut = shared_engine.generate(_prompt(4, seed=21),
                                           max_tokens=3)
        short_fut.add_done_callback(lambda f: done.append("short"))
        short_fut.result(timeout=60)
        long_fut.result(timeout=60)
        assert done[0] == "short", done

    def test_slot_recycling_no_stale_kv_leakage(self, model):
        """Poison-value check: after a slot is recycled, rows a previous
        occupant wrote (and rows poisoned outright) must never reach a
        new request's attention — lengths-masking is the containment."""
        prompt = _prompt(6, seed=30)
        ref = _ref_greedy(model, prompt, 6)
        eng = _engine(model, slots=1)
        try:
            # occupy and release the only slot
            eng.generate(_prompt(10, seed=31), max_tokens=8).result(60)
            # poison EVERY cache row outright: only masking (not luck)
            # can keep the next request clean; prefill overwrites rows
            # [0, bucket) and decode masks everything past `lengths`
            with eng._dispatch_lock:
                eng._cache = {k: jnp.full_like(v, 1e9)
                              for k, v in eng._cache.items()}
            res = eng.generate(prompt, max_tokens=6).result(timeout=60)
            assert res["tokens"] == ref
        finally:
            eng.close(10)

    def test_streaming_callback(self, model, shared_engine):
        seen = []
        res = shared_engine.generate(_prompt(5, seed=40), max_tokens=5,
                                     on_token=seen.append).result(
            timeout=60)
        assert seen == res["tokens"]

    def test_prompt_validation(self, model, shared_engine):
        with pytest.raises(ValueError, match="at least one"):
            shared_engine.generate([])
        with pytest.raises(ValueError, match="no room"):
            shared_engine.generate(list(range(64)))  # == max_ctx

    def test_max_tokens_capped_by_context(self, model):
        eng = _engine(model, max_ctx=16, prompt_buckets=[8])
        try:
            res = eng.generate(_prompt(8, seed=41),
                               max_tokens=500).result(timeout=60)
            # cap = max_ctx - prompt_len
            assert res["completion_tokens"] == 8
            assert res["finish_reason"] == "length"
        finally:
            eng.close(10)

    def test_drain_rejects_and_start_reopens(self, model):
        eng = _engine(model)
        eng.generate(_prompt(4, seed=42), max_tokens=2).result(60)
        assert eng.drain(timeout_s=30)
        with pytest.raises(EngineClosedError):
            eng.generate(_prompt(4, seed=42))
        eng.start()
        assert eng.generate(_prompt(4, seed=42),
                            max_tokens=2).result(60)["tokens"]
        assert eng.close(30)
        with pytest.raises(EngineClosedError):
            eng.start()

    def test_admission_timeout_expires_queued_request(self, model):
        """A request whose deadline passes before a slot frees must fail
        with TimeoutError without any model work."""
        eng = _engine(model, slots=1, max_ctx=128, prompt_buckets=[8])
        try:
            blocker = eng.generate(_prompt(4, seed=43), max_tokens=80)
            late = eng.generate(_prompt(4, seed=44), max_tokens=2,
                                timeout_s=0.0)
            with pytest.raises(TimeoutError):
                late.result(timeout=60)
            blocker.result(timeout=60)
        finally:
            eng.close(10)

    def test_stats_surface(self, model, shared_engine):
        before = shared_engine.stats()
        shared_engine.generate(_prompt(4, seed=45), max_tokens=3).result(60)
        s = shared_engine.stats()
        assert s["requests"] == before["requests"] + 1
        assert s["tokens"] == before["tokens"] + 3
        assert s["prefills"] == before["prefills"] + 1
        assert s["slots"] == 3
        # explicit buckets, plus the always-present max_ctx top rung
        # (preempted riders' prefixes must stay admittable)
        assert s["prompt_buckets"] == [32, 64]


class TestCompileCounting:
    def test_one_executable_per_bucket_plus_one_decode(self, model):
        """Warmup compiles exactly len(ladder) * len(batch ladder)
        prefill executables + 1 decode executable; steady-state traffic
        then compiles NOTHING — the zero-recompile acceptance
        invariant."""
        env = environment()
        eng = DecodeEngine(model, slots=2, max_ctx=64,
                           prompt_buckets=[8, 32], prefill_batch=2)
        expected = len(eng.ladder) * len(eng.batch_ladder) + 1
        try:
            env.reset_compile_count()
            eng.warmup()
            # ladder (8, 32, + max_ctx rung) x batch ladder (1, 2)
            # prefill executables, + 1 decode
            assert env.compile_count() == expected
            eng.warmup()  # idempotent
            assert env.compile_count() == expected
            env.reset_compile_count()
            futs = [eng.generate(_prompt(n, seed=50 + n), max_tokens=4)
                    for n in (3, 8, 20, 5)]
            for f in futs:
                f.result(timeout=60)
            assert env.compile_count() == 0
        finally:
            eng.close(10)
            env.reset_compile_count()


# ---------------------------------------------------------------------------
# satellite: decode shapes always dispatch to the XLA attention path
# ---------------------------------------------------------------------------

class TestDecodeAttentionDispatch:
    def test_seq_len_one_always_xla(self, flash_everywhere):
        from deeplearning4j_tpu.kernels import attention_dispatch
        # even a rule that would send EVERYTHING to flash must not move
        # the decode shape off the XLA path
        assert attention_dispatch(1) == "xla"
        assert attention_dispatch(0) == "xla"
        assert attention_dispatch(2) == "flash"

    def test_decode_shape_ticks_dispatch_counter(self, model,
                                                 flash_everywhere):
        """Tracing the decode step records dl4j_attn_dispatch_total with
        path=xla (once per compiled executable)."""
        from deeplearning4j_tpu.kernels import attention_dispatch

        fam = registry().counter(
            "dl4j_attn_dispatch_total",
            "Attention path decisions for flash=True configs",
            labels=("path",))
        before = fam.labels(path="xla").value()
        # adversarial rule: flash for everything
        assert attention_dispatch(1) == "xla"
        assert fam.labels(path="xla").value() == before + 1

    def test_paged_path_ticks_paged_label(self, flash_everywhere):
        """The block-table gather attention of paged_decode records its
        own path=paged label — paged and slab decode executables stay
        distinguishable in telemetry — and never takes the flash kernel,
        whatever the query length or the rule."""
        from deeplearning4j_tpu.kernels import attention_dispatch

        fam = registry().counter(
            "dl4j_attn_dispatch_total",
            "Attention path decisions for flash=True configs",
            labels=("path",))
        before = fam.labels(path="paged").value()
        assert attention_dispatch(1, paged=True) == "paged"
        assert attention_dispatch(512, paged=True) == "paged"
        assert fam.labels(path="paged").value() == before + 2


# ---------------------------------------------------------------------------
# satellite: donated-cache steps are store-ineligible, never silent
# ---------------------------------------------------------------------------

class TestDonatedDecodeCompileCache:
    def test_decode_steps_bypass_store_with_histogram_evidence(self, model):
        """Donated-KV-cache prefill/decode entries must (a) never land in
        the raw executable store and (b) still record the *reasoned*
        cache=bypass:donation on the dl4j_compile_seconds histogram —
        observable, not silently missing, and attributable."""
        fam = registry().histogram(
            "dl4j_compile_seconds",
            "Wall time to materialize + first-run an executable, by cache "
            "outcome", labels=("kind", "cache"))

        def bypass_count(kind):
            return sum(child.count() for key, child in fam.children()
                       if key == (kind, "bypass:donation"))

        pre_prefill = bypass_count("prefill")
        pre_decode = bypass_count("decode")
        eng = DecodeEngine(model, slots=2, max_ctx=64,
                           prompt_buckets=[16], prefill_batch=1)
        try:
            eng.warmup()
        finally:
            eng.close(10)
        # one prefill executable per ladder rung ([16] + max_ctx top
        # rung), one decode executable — every one a store bypass
        assert bypass_count("prefill") == pre_prefill + len(eng.ladder)
        assert bypass_count("decode") == pre_decode + 1
        inv = compile_cache.inventory()
        assert inv["enabled"]  # conftest pins a live per-run cache dir
        kinds = {e.get("tag_kind") for e in inv["entries"]}
        assert "prefill" not in kinds and "decode" not in kinds


# ---------------------------------------------------------------------------
# serving: registry + HTTP /generate end to end
# ---------------------------------------------------------------------------

def _get(url, timeout=10):
    try:
        r = urllib.request.urlopen(url, timeout=timeout)
        return r.status, r.headers, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers, e.read()


def _post(url, doc, timeout=30, headers=()):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json", **dict(headers)})
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, r.headers, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers, e.read()


@pytest.fixture(scope="module")
def served_lm(model):
    """One served registry shared by the endpoint tests (each deploy
    compiles executables; the hot-swap test runs last and restores v1)."""
    from deeplearning4j_tpu.serving import ModelRegistry, ModelServer

    reg = ModelRegistry(manifest_dir=None, retain=1)
    reg.deploy("lm", "v1", model, decode_slots=2, decode_max_ctx=64,
               decode_prompt_buckets=[32])
    srv = ModelServer(reg)
    port = srv.start()
    yield reg, srv, f"http://127.0.0.1:{port}"
    srv.stop()
    reg.drain_all(save_manifests=False)


class TestRegistryGenerate:
    def test_deploy_detects_generative_and_describes(self, model):
        from deeplearning4j_tpu.serving import ModelRegistry

        reg = ModelRegistry(manifest_dir=None, retain=0)
        try:
            mv = reg.deploy("lm", "v1", model, decode_slots=2,
                            decode_max_ctx=64,
                            decode_prompt_buckets=[8])
            assert isinstance(mv.engine, DecodeEngine)
            assert mv.describe()["generative"] is True
            assert reg.ready()
            prompt = _prompt(5, seed=60)
            ref = _ref_greedy(model, prompt, 4)
            res = reg.generate("lm", prompt, max_tokens=4)
            assert res["tokens"] == ref
            with pytest.raises(TypeError, match="generative"):
                reg.predict("lm", np.zeros((1, 4), np.float32))
        finally:
            reg.drain_all(save_manifests=False)

    def test_generate_on_non_generative_raises(self):
        from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                       OutputLayer)
        from deeplearning4j_tpu.serving import ModelRegistry

        conf = (NeuralNetConfiguration.builder().seed(0).list()
                .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
                .layer(OutputLayer(n_in=8, n_out=2))
                .build())
        net = MultiLayerNetwork(conf).init()
        reg = ModelRegistry(manifest_dir=None, retain=0)
        try:
            reg.deploy("mlp", "v1", net,
                       example=np.zeros((2, 4), np.float32))
            with pytest.raises(TypeError, match="not generative"):
                reg.generate("mlp", [1, 2, 3])
        finally:
            reg.drain_all(save_manifests=False)


class TestGenerateEndpoint:
    def test_end_to_end_with_trace_and_debug_spans(self, served_lm, model):
        """The acceptance path: POST /generate through admission + trace
        context; the response echoes X-Trace-Id and the request's
        prefill/decode spans are reconstructable via /debug/requests."""
        reg, srv, base = served_lm
        prompt = _prompt(5, seed=70)
        ref = _ref_greedy(model, prompt, 6)
        status, headers, body = _post(
            base + "/v1/models/lm/generate",
            {"prompt": [int(t) for t in prompt], "max_tokens": 6})
        assert status == 200
        trace_id = headers.get("X-Trace-Id")
        assert trace_id
        doc = json.loads(body)
        assert doc["tokens"] == ref
        assert doc["model"] == "lm" and doc["version"] == "v1"
        assert doc["finish_reason"] == "length"
        assert doc["ttft_s"] > 0

        # the ring record lands after the response bytes reach the
        # client: poll, same as the PR-6 tracing tests
        doc = _wait_until(lambda: (lambda d: d["count"] == 1 and d)(
            json.loads(_get(
                base + f"/debug/requests?trace_id={trace_id}")[2])))
        assert doc and doc["count"] == 1
        rec = doc["requests"][0]
        assert rec["kind"] == "generate"
        names = []

        def walk(spans):
            for s in spans:
                names.append(s["name"])
                walk(s.get("children", []))

        walk(rec["spans"])
        assert "serving/request" in names
        assert "serving/admission" in names
        assert "generation/prefill" in names
        assert "generation/decode" in names

    def test_traceparent_joined(self, served_lm, model):
        reg, srv, base = served_lm
        tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        status, headers, _ = _post(
            base + "/v1/models/lm/generate",
            {"prompt": [1, 2, 3], "max_tokens": 2},
            headers={"traceparent": tp})
        assert status == 200
        assert headers.get("X-Trace-Id") == "ab" * 16

    def test_streaming_chunks(self, served_lm, model):
        reg, srv, base = served_lm
        prompt = _prompt(4, seed=71)
        ref = _ref_greedy(model, prompt, 5)
        req = urllib.request.Request(
            base + "/v1/models/lm/generate",
            data=json.dumps({"prompt": [int(t) for t in prompt],
                             "max_tokens": 5, "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        r = urllib.request.urlopen(req, timeout=30)
        assert r.status == 200
        assert r.headers.get("X-Trace-Id")
        assert "ndjson" in r.headers.get("Content-Type", "")
        lines = [json.loads(l) for l in r.read().splitlines() if l.strip()]
        streamed = [l["token"] for l in lines if "token" in l]
        assert streamed == ref
        tail = lines[-1]
        assert tail["done"] is True and tail["tokens"] == ref

    def test_error_mapping(self, served_lm):
        reg, srv, base = served_lm
        status, _, _ = _post(base + "/v1/models/nope/generate",
                             {"prompt": [1]})
        assert status == 404
        status, _, body = _post(base + "/v1/models/lm/generate", {})
        assert status == 400 and b"prompt" in body
        status, _, _ = _post(base + "/v1/models/lm/generate",
                             {"prompt": "not ids"})
        assert status == 400
        # predict on a generative model is a client error, not a 500
        status, _, body = _post(base + "/v1/models/lm/predict",
                                {"inputs": [[1.0]]})
        assert status == 400 and b"generative" in body

    def test_sampled_generation_within_vocab(self, served_lm):
        reg, srv, base = served_lm
        status, _, body = _post(
            base + "/v1/models/lm/generate",
            {"prompt": [3, 7], "max_tokens": 6, "temperature": 0.8,
             "top_k": 10})
        assert status == 200
        toks = json.loads(body)["tokens"]
        assert len(toks) == 6
        assert all(0 <= t < CFG.vocab_size for t in toks)

    def test_generate_feeds_slo_with_ttft(self, served_lm):
        reg, srv, base = served_lm
        _post(base + "/v1/models/lm/generate",
              {"prompt": [1, 2], "max_tokens": 2})
        assert _wait_until(lambda: any(
            w["total"] >= 1
            for w in srv.slo_for("lm").snapshot()["windows"]))

    def test_debug_decode_endpoint(self, served_lm):
        """GET /debug/decode joins every current generative engine's
        slot map + block pool + speculative state into the debug
        surface (and, via decode_snapshots(), the flight recorder)."""
        reg, srv, base = served_lm
        _post(base + "/v1/models/lm/generate",
              {"prompt": [5, 6, 7], "max_tokens": 2})
        status, _, body = _get(base + "/debug/decode")
        assert status == 200
        snaps = json.loads(body)["decode"]
        snap = next(s for s in snaps if s["model"] == "lm")
        assert snap["version"] == "v1"
        assert snap["pool"]["scratch_block"] == 0
        assert snap["pool"]["free_blocks"] <= snap["pool"]["total_blocks"]
        assert len(snap["slots"]) == 2
        assert snap["prefill"]["batch"] >= 1
        assert snap["speculative"]["enabled"] is False
        assert snap["queue_depth"] >= 0

    def test_hot_swap_generative_version(self, served_lm, model):
        """Warm-before-cutover + rollback work for DecodeEngine versions
        exactly as for predict engines."""
        reg, srv, base = served_lm
        model2 = causal_lm.CausalLM(CFG, seed=9)
        reg.deploy("lm", "v2", model2, decode_slots=2, decode_max_ctx=64,
                   decode_prompt_buckets=[32])
        status, _, body = _post(base + "/v1/models/lm/generate",
                                {"prompt": [4, 4, 4], "max_tokens": 3})
        assert status == 200
        assert json.loads(body)["version"] == "v2"
        reg.rollback("lm")
        status, _, body = _post(base + "/v1/models/lm/generate",
                                {"prompt": [4, 4, 4], "max_tokens": 3})
        assert status == 200
        assert json.loads(body)["version"] == "v1"


class TestDecodeEnvKnobs:
    def test_defaults_and_overrides(self):
        env = environment()
        assert env.decode_slots() == 8
        assert env.decode_max_ctx() == 256
        assert env.decode_max_tokens() == 128
        try:
            env.set_decode_slots(3)
            env.set_decode_max_ctx(64)
            env.set_decode_max_tokens(16)
            assert env.decode_slots() == 3
            assert env.decode_max_ctx() == 64
            assert env.decode_max_tokens() == 16
        finally:
            from deeplearning4j_tpu.common.environment import \
                SystemProperties
            env.clear_property(SystemProperties.DECODE_SLOTS)
            env.clear_property(SystemProperties.DECODE_MAX_CTX)
            env.clear_property(SystemProperties.DECODE_MAX_TOKENS)

    def test_engine_reads_env_defaults(self, model):
        env = environment()
        try:
            env.set_decode_slots(3)
            env.set_decode_max_ctx(48)
            eng = DecodeEngine(model)
            assert eng.slots == 3
            assert eng.max_ctx == 48
            eng.close(5)
        finally:
            from deeplearning4j_tpu.common.environment import \
                SystemProperties
            env.clear_property(SystemProperties.DECODE_SLOTS)
            env.clear_property(SystemProperties.DECODE_MAX_CTX)


# ---------------------------------------------------------------------------
# tentpole: paged KV block pool
# ---------------------------------------------------------------------------

class TestPagedKVBlocks:
    def test_blocks_track_sequence_length(self, model):
        """The reservation the paging PR exists for: a sequence holds
        ceil((rows written + 1) / block_size) blocks at every step —
        never the slab layout's full max_ctx worth."""
        # prefix cache off: this test pins the raw paging accounting,
        # where completion returns every block to the pool
        eng = _engine(model, slots=2, prompt_buckets=[16], kv_block_size=8,
                      prefix_cache=False)
        samples = []

        def cb(_tok):
            samples.append((int(eng._nblocks.sum()),
                            int(eng._lengths.sum())))

        try:
            total = eng.stats()["kv_blocks_free"]
            assert total == eng.kv_blocks == 2 * eng.max_blocks
            res = eng.generate(_prompt(12, seed=80), max_tokens=20,
                               on_token=cb).result(timeout=60)
            assert len(res["tokens"]) == 20
            for nblocks, length in samples:
                # within one block of committed rows (+1 for the write
                # horizon the scheduler pre-allocates)
                assert 0 <= nblocks * eng.block_size - length \
                    <= eng.block_size
            peak = max(nb for nb, _ in samples)
            # final length 32 rows -> 4 blocks; slab would pin all 8
            assert peak < eng.max_blocks
            # every block returned on completion
            assert eng.stats()["kv_blocks_free"] == total
        finally:
            eng.close(10)

    def test_blocks_free_gauge_tracks_pool(self, model):
        fam = registry().gauge(
            "dl4j_kv_blocks_free",
            "Free KV-cache blocks in the paged decode pool",
            labels=("model",))
        eng = _engine(model, kv_block_size=8, model_name="kvgauge",
                      prefix_cache=False)
        child = fam.labels(model="kvgauge")
        dips = []
        try:
            assert child.value() == eng.kv_blocks
            eng.generate(_prompt(10, seed=81), max_tokens=8,
                         on_token=lambda t: dips.append(child.value())
                         ).result(timeout=60)
            assert min(dips) < eng.kv_blocks  # held while decoding
            assert child.value() == eng.kv_blocks  # returned on finish
        finally:
            eng.close(10)

    def test_over_pool_request_rejected_at_submit(self, model):
        """A request whose worst case cannot fit the pool must fail at
        generate(), not deadlock the scheduler mid-decode."""
        eng = _engine(model, kv_block_size=8, kv_blocks=4)  # 32 rows
        try:
            with pytest.raises(ValueError, match="KV blocks"):
                # prompt 8 + capped max_tokens 56 -> 8 blocks > 4
                eng.generate(_prompt(8, seed=82), max_tokens=56)
            res = eng.generate(_prompt(8, seed=82),
                               max_tokens=8).result(timeout=60)
            assert len(res["tokens"]) == 8  # 16 rows = 2 blocks: fits
        finally:
            eng.close(10)

    def test_slab_layout_is_block_size_max_ctx(self, model):
        """kv_block_size >= max_ctx reproduces the legacy slab: one
        block per slot, admission == slot availability."""
        eng = _engine(model, kv_block_size=4096)
        try:
            assert eng.block_size == eng.max_ctx
            assert eng.max_blocks == 1
            assert eng.kv_blocks == eng.slots
        finally:
            eng.close(10)

    def test_debug_snapshot_surface(self, model):
        eng = _engine(model, kv_block_size=8, model_name="snap")
        gate, release = threading.Event(), threading.Event()

        def cb(_tok):
            gate.set()
            release.wait(30)

        try:
            fut = eng.generate(_prompt(6, seed=83), max_tokens=4,
                               on_token=cb)
            assert gate.wait(30)
            snap = eng.debug_snapshot()
            assert snap["model"] == "snap"
            assert snap["pool"]["scratch_block"] == 0
            assert snap["pool"]["block_size"] == 8
            assert snap["pool"]["free_blocks"] < snap["pool"]["total_blocks"]
            occupied = [s for s in snap["slots"] if s["active"]]
            assert len(occupied) == 1
            assert occupied[0]["prompt_tokens"] == 6
            assert occupied[0]["blocks"]  # non-scratch ids
            assert all(b > 0 for b in occupied[0]["blocks"])
            assert snap["speculative"]["enabled"] is False
            release.set()
            fut.result(timeout=60)
        finally:
            release.set()
            eng.close(10)


class TestPreemption:
    def test_pool_exhaustion_preempts_lifo_and_recomputes(self, model):
        """Two riders whose combined growth exceeds the pool: the later-
        admitted one is preempted (blocks reclaimed, requeued at the
        queue head), then recomputed from prompt + committed tokens —
        greedy output stays token-identical for BOTH."""
        fam = registry().counter(
            "dl4j_decode_preempted_total",
            "Sequences preempted (blocks reclaimed, requeued for "
            "recompute) because the KV block pool ran dry mid-decode")
        before = fam.value()
        # pool of 5 blocks = 40 rows; each request's worst case is 4
        # blocks (32 rows), so both fit alone but not together
        eng = _engine(model, slots=2, prompt_buckets=[16],
                      kv_block_size=8, kv_blocks=5)
        pa, pb = _prompt(8, seed=84), _prompt(8, seed=85)
        ra, rb = _ref_greedy(model, pa, 24), _ref_greedy(model, pb, 24)
        try:
            fa = eng.generate(pa, max_tokens=24)
            fb = eng.generate(pb, max_tokens=24)
            assert fa.result(timeout=120)["tokens"] == ra
            assert fb.result(timeout=120)["tokens"] == rb
            s = eng.stats()
            assert s["preempted"] >= 1
            assert fam.value() >= before + 1
            # nothing leaked: completed prefixes legitimately stay in
            # the radix cache; free + cached must cover the whole pool
            assert (s["kv_blocks_free"]
                    + s["prefix_cached_blocks"]) == 5
        finally:
            eng.close(10)


# ---------------------------------------------------------------------------
# tentpole: batched prefill
# ---------------------------------------------------------------------------

class TestBatchedPrefill:
    def _gated_long(self, eng, seed):
        """Start a request whose first on_token blocks the decode loop:
        everything submitted while it is blocked is queued together, so
        the next admission's grouping is deterministic."""
        entered, release = threading.Event(), threading.Event()

        def gate(_tok):
            entered.set()
            release.wait(30)

        fut = eng.generate(_prompt(5, seed=seed), max_tokens=8,
                           on_token=gate)
        assert entered.wait(30)
        return fut, release

    def test_same_bucket_prompts_share_one_dispatch(self, model):
        eng = _engine(model, slots=4, prompt_buckets=[16],
                      prefill_batch=4)
        prompts = [_prompt(6, seed=90 + i) for i in range(3)]
        refs = [_ref_greedy(model, p, 4) for p in prompts]
        long_ref = _ref_greedy(model, _prompt(5, seed=89), 8)
        try:
            before = eng.stats()
            long_fut, release = self._gated_long(eng, 89)
            futs = [eng.generate(p, max_tokens=4) for p in prompts]
            release.set()
            for f, ref in zip(futs, refs):
                assert f.result(timeout=60)["tokens"] == ref
            assert long_fut.result(timeout=60)["tokens"] == long_ref
            s = eng.stats()
            assert s["prefills"] - before["prefills"] == 4
            # one dispatch for the long prompt + ONE for the group of 3
            assert (s["prefill_dispatches"]
                    - before["prefill_dispatches"]) == 2
        finally:
            eng.close(10)

    def test_mixed_buckets_do_not_share_a_dispatch(self, model):
        """Coalescing is per bucket: padding a 20-token prompt into a
        16-bucket dispatch would corrupt it, so it gets its own."""
        eng = _engine(model, slots=4, prompt_buckets=[16, 32],
                      prefill_batch=4)
        p16a, p32, p16b = (_prompt(6, seed=94), _prompt(20, seed=95),
                           _prompt(7, seed=96))
        refs = [_ref_greedy(model, p, 3) for p in (p16a, p32, p16b)]
        try:
            before = eng.stats()
            long_fut, release = self._gated_long(eng, 93)
            futs = [eng.generate(p, max_tokens=3)
                    for p in (p16a, p32, p16b)]
            release.set()
            for f, ref in zip(futs, refs):
                assert f.result(timeout=60)["tokens"] == ref
            long_fut.result(timeout=60)
            # long alone + {p16a, p16b} grouped + p32 alone
            assert (eng.stats()["prefill_dispatches"]
                    - before["prefill_dispatches"]) == 3
        finally:
            eng.close(10)


# ---------------------------------------------------------------------------
# tentpole: greedy speculative decoding
# ---------------------------------------------------------------------------

class TestSpeculativeDecode:
    def test_same_model_draft_token_identical(self, model):
        eng = _engine(model, draft_model=model, spec_k=3)
        prompts = [_prompt(n, seed=100 + n) for n in (5, 9)]
        refs = [_ref_greedy(model, p, 10) for p in prompts]
        try:
            futs = [eng.generate(p, max_tokens=10) for p in prompts]
            for f, ref in zip(futs, refs):
                assert f.result(timeout=60)["tokens"] == ref
            s = eng.stats()
            assert s["spec_steps"] > 0
            assert s["spec_proposed"] > 0
            # an identical draft should verify nearly everything
            assert s.get("spec_acceptance", 0) >= 0.9
            snap = eng.debug_snapshot()
            assert snap["speculative"]["enabled"]
            assert snap["speculative"]["k"] == 3
            assert snap["speculative"]["acceptance_rate"] is not None
        finally:
            eng.close(10)

    def test_truncated_draft_token_identical(self, model):
        """The production shape: a cheaper draft sharing the target's
        first layer + embeddings. Whatever it proposes, verification
        must keep the greedy output byte-for-byte the target's own."""
        dcfg = dataclasses.replace(CFG, num_layers=1)
        draft = causal_lm.CausalLM(dcfg, params={
            "embeddings": model.params["embeddings"],
            "layers": model.params["layers"][:1]})
        eng = _engine(model, draft_model=draft, spec_k=2)
        prompt = _prompt(6, seed=110)
        ref = _ref_greedy(model, prompt, 12)
        try:
            res = eng.generate(prompt, max_tokens=12).result(timeout=60)
            assert res["tokens"] == ref
            s = eng.stats()
            assert s["spec_steps"] > 0
            assert s.get("spec_acceptance") is not None
        finally:
            eng.close(10)

    def test_sampled_rider_falls_back_to_plain_decode(self, model):
        """Speculation is greedy-only: any sampled rider in the batch
        sends the whole step down the plain path."""
        eng = _engine(model, draft_model=model, spec_k=3)
        try:
            res = eng.generate(_prompt(5, seed=111), max_tokens=8,
                               temperature=0.8, top_k=10
                               ).result(timeout=60)
            assert len(res["tokens"]) == 8
            assert all(0 <= t < CFG.vocab_size for t in res["tokens"])
            assert eng.stats()["spec_steps"] == 0
        finally:
            eng.close(10)

    def test_non_generative_draft_rejected(self, model):
        with pytest.raises(TypeError, match="draft_model"):
            _engine(model, draft_model=object(), spec_k=2)


class TestPagedEnvKnobs:
    def test_defaults_and_overrides(self):
        from deeplearning4j_tpu.common.environment import SystemProperties
        env = environment()
        assert env.kv_block_size() == 16
        assert env.spec_draft_k() == 0
        try:
            env.set_kv_block_size(4)
            env.set_spec_draft_k(2)
            assert env.kv_block_size() == 4
            assert env.spec_draft_k() == 2
        finally:
            env.clear_property(SystemProperties.KV_BLOCK_SIZE)
            env.clear_property(SystemProperties.SPEC_DRAFT_K)

    def test_engine_reads_env_knobs(self, model):
        from deeplearning4j_tpu.common.environment import SystemProperties
        env = environment()
        try:
            env.set_kv_block_size(4)
            env.set_spec_draft_k(2)
            eng = _engine(model, draft_model=model)
            assert eng.block_size == 4
            assert eng.max_blocks == 16
            assert eng.spec_k == 2 and eng._spec_enabled
            eng.close(5)
            # spec_k=0 disables even with a draft wired
            eng = _engine(model, draft_model=model, spec_k=0)
            assert not eng._spec_enabled
            eng.close(5)
        finally:
            env.clear_property(SystemProperties.KV_BLOCK_SIZE)
            env.clear_property(SystemProperties.SPEC_DRAFT_K)


# ---------------------------------------------------------------------------
# tentpole: prefix-aware KV reuse (radix cache over the paged pool)
# ---------------------------------------------------------------------------

class TestPrefixCache:
    def test_warm_repeat_reuses_and_stays_token_identical(self, model):
        """The headline: a repeated prompt attaches its block-aligned
        cached prefix (all but the final block run — one tail token must
        still prefill to produce logits) and decodes the exact tokens of
        the cold run."""
        eng = _engine(model, kv_block_size=8, kv_blocks=16)
        prompt = _prompt(23, seed=120)
        ref = _ref_greedy(model, prompt, 6)
        try:
            cold = eng.generate(prompt, max_tokens=6).result(timeout=60)
            s0 = eng.stats()
            assert cold["tokens"] == ref
            assert s0["prefix_hits"] == 0 and s0["prefix_misses"] == 1
            assert s0["prefix_cached_blocks"] > 0
            warm = eng.generate(prompt, max_tokens=6).result(timeout=60)
            s1 = eng.stats()
            assert warm["tokens"] == ref
            assert s1["prefix_hits"] == 1
            # 23-token prompt, block 8: blocks [0:8) and [8:16) cached;
            # the 22-row cap never binds here (16 <= 22)
            assert s1["prefix_reused_rows"] == 16
            # warm prefill computed only the 7-row tail
            assert s1["prefill_rows"] - s0["prefill_rows"] == 7
        finally:
            eng.close(10)

    def test_multi_turn_history_reattaches(self, model):
        """Turn 2 re-sends turn 1's prompt + generated reply + new user
        tokens: the cached run covers the whole committed history
        (prompt AND generated tokens), so only the new tail prefills."""
        eng = _engine(model, kv_block_size=8, kv_blocks=16,
                      prompt_buckets=[32, 64], max_ctx=64)
        p1 = _prompt(12, seed=121)
        try:
            t1 = eng.generate(p1, max_tokens=8).result(timeout=60)
            turn2 = np.concatenate(
                [p1, np.asarray(t1["tokens"], np.int32),
                 _prompt(6, seed=122)])
            ref = _ref_greedy(model, turn2, 5)
            s0 = eng.stats()
            t2 = eng.generate(turn2, max_tokens=5).result(timeout=60)
            s1 = eng.stats()
            assert t2["tokens"] == ref
            assert s1["prefix_hits"] - s0["prefix_hits"] == 1
            # committed history = 12 + 8 = 20 rows -> 2 full blocks
            assert s1["prefix_reused_rows"] - s0["prefix_reused_rows"] == 16
        finally:
            eng.close(10)

    def test_divergent_suffix_forks_not_corrupts(self, model):
        """Two prompts sharing 16 tokens then diverging: the second
        attaches the shared run and prefills its own suffix into fresh
        blocks — the first request's cached blocks must stay intact
        (verified by decoding both against the recompute reference)."""
        eng = _engine(model, kv_block_size=8, kv_blocks=16)
        common = _prompt(16, seed=123)
        a = np.concatenate([common, _prompt(7, seed=124)])
        b = np.concatenate([common, _prompt(7, seed=125)])
        ra, rb = _ref_greedy(model, a, 6), _ref_greedy(model, b, 6)
        try:
            assert eng.generate(a, max_tokens=6).result(60)["tokens"] == ra
            s0 = eng.stats()
            assert eng.generate(b, max_tokens=6).result(60)["tokens"] == rb
            s1 = eng.stats()
            assert s1["prefix_reused_rows"] - s0["prefix_reused_rows"] == 16
            # replaying A after B's fork must still see A's blocks
            assert eng.generate(a, max_tokens=6).result(60)["tokens"] == ra
        finally:
            eng.close(10)

    def test_lru_eviction_reclaims_unattached_leaves(self, model):
        """A pool sized for ~2 cached prompts: filling it with distinct
        prompts forces leaf eviction (counted on the engine and the
        dl4j_kv_prefix_evictions_total counter) and decode stays
        correct throughout."""
        fam = registry().counter(
            "dl4j_kv_prefix_evictions_total",
            "KV prefix-cache blocks reclaimed by LRU leaf eviction")
        before = fam.value()
        eng = _engine(model, kv_block_size=8, kv_blocks=8)
        prompts = [_prompt(14, seed=130 + i) for i in range(4)]
        refs = [_ref_greedy(model, p, 4) for p in prompts]
        try:
            for p, ref in zip(prompts, refs):
                assert eng.generate(p, max_tokens=4
                                    ).result(60)["tokens"] == ref
            s = eng.stats()
            assert s["prefix_evictions"] > 0
            assert fam.value() - before == s["prefix_evictions"]
            # the pool never leaked: all blocks free or cached
            assert (s["kv_blocks_free"] + s["prefix_cached_blocks"]
                    == eng.kv_blocks)
        finally:
            eng.close(10)

    def test_disabled_engine_never_caches(self, model):
        eng = _engine(model, kv_block_size=8, prefix_cache=False)
        prompt = _prompt(23, seed=126)
        ref = _ref_greedy(model, prompt, 6)
        try:
            for _ in range(2):
                assert eng.generate(prompt, max_tokens=6
                                    ).result(60)["tokens"] == ref
            s = eng.stats()
            assert s["prefix_cache"] is False
            assert s["prefix_hits"] == 0 and s["prefix_misses"] == 0
            assert s["prefix_cached_blocks"] == 0
            assert eng.debug_snapshot()["prefix_cache"]["enabled"] is False
        finally:
            eng.close(10)

    def test_debug_snapshot_exposes_radix(self, model):
        eng = _engine(model, kv_block_size=8, model_name="radix-snap")
        try:
            eng.generate(_prompt(20, seed=127), max_tokens=4).result(60)
            snap = eng.debug_snapshot()["prefix_cache"]
            assert snap["enabled"] is True
            assert snap["cached_blocks"] == len(snap["nodes"]) > 0
            for nd in snap["nodes"]:
                assert nd["block"] > 0          # never the scratch block
                assert len(nd["digest"]) == 12  # chained sha1, truncated
                assert nd["refs"] == 0          # nothing attached now
        finally:
            eng.close(10)

    def test_prefix_blocks_gauge_tracks_cache(self, model):
        fam = registry().gauge(
            "dl4j_kv_prefix_blocks",
            "KV blocks currently held by the prefix cache's radix tree",
            labels=("model",))
        eng = _engine(model, kv_block_size=8, model_name="pfxgauge")
        child = fam.labels(model="pfxgauge")
        try:
            eng.generate(_prompt(17, seed=128), max_tokens=3).result(60)
            assert child.value() == eng.stats()["prefix_cached_blocks"] > 0
        finally:
            eng.close(10)


class TestPrefixCacheEnvKnobs:
    def test_default_and_override(self):
        from deeplearning4j_tpu.common.environment import SystemProperties
        env = environment()
        assert env.prefix_cache_enabled() is True
        try:
            env.set_prefix_cache(False)
            assert env.prefix_cache_enabled() is False
        finally:
            env.clear_property(SystemProperties.PREFIX_CACHE)

    def test_engine_reads_env_knob(self, model):
        from deeplearning4j_tpu.common.environment import SystemProperties
        env = environment()
        try:
            env.set_prefix_cache(False)
            eng = _engine(model)
            assert eng.stats()["prefix_cache"] is False
            eng.close(5)
            # the constructor kwarg wins over the env default
            eng = _engine(model, prefix_cache=True)
            assert eng.stats()["prefix_cache"] is True
            eng.close(5)
        finally:
            env.clear_property(SystemProperties.PREFIX_CACHE)


class TestPrefixCachePreemption:
    def test_preempted_request_reattaches_cached_prefix(self, model):
        """Satellite regression (preemption/fork interplay): a LIFO-
        preempted request publishes its regrown prefix (prompt +
        committed tokens) into the radix cache before releasing its
        blocks, so the re-admit attaches that run and prefills ONLY the
        uncached tail instead of recomputing from scratch."""
        # pool of 6 blocks = 48 rows; both requests' worst case is 4
        # blocks, so the later one is preempted mid-decode (empirically
        # stable: the re-admit re-attaches 2 full cached blocks)
        eng = _engine(model, slots=2, prompt_buckets=[16, 32],
                      kv_block_size=8, kv_blocks=6)
        pa, pb = _prompt(8, seed=84), _prompt(8, seed=85)
        ra, rb = _ref_greedy(model, pa, 24), _ref_greedy(model, pb, 24)
        try:
            fa = eng.generate(pa, max_tokens=24)
            fb = eng.generate(pb, max_tokens=24)
            assert fa.result(timeout=120)["tokens"] == ra
            assert fb.result(timeout=120)["tokens"] == rb
            s = eng.stats()
            assert s["preempted"] >= 1
            # the re-admit was a cache hit on its own regrown prefix:
            # at least its full prompt block came back from the tree
            assert s["prefix_hits"] >= 1
            assert s["prefix_reused_rows"] >= 8
            # and the re-prefill computed fewer rows than a cold
            # recompute of both requests' full prefixes would have
            cold_rows = 2 * 8 + 8 + s["prefix_reused_rows"]
            assert s["prefill_rows"] < cold_rows
            # nothing leaked: every block is free or cached
            assert (s["kv_blocks_free"] + s["prefix_cached_blocks"]
                    == eng.kv_blocks)
        finally:
            eng.close(10)


class TestPrefixCacheSpeculative:
    def test_spec_with_prefix_sharing_token_identical(self, model):
        """Satellite regression (spec compat): draft+target decode with
        prefix sharing enabled — including a warm request attached to
        cached blocks the draft cache knows nothing about — must stay
        token-identical to the plain greedy reference. The target's
        verify pass is authoritative, so stale draft KV for reused rows
        can cost acceptance but never change tokens."""
        dcfg = dataclasses.replace(CFG, num_layers=1)
        draft = causal_lm.CausalLM(dcfg, params={
            "embeddings": model.params["embeddings"],
            "layers": model.params["layers"][:1]})
        eng = _engine(model, kv_block_size=8, kv_blocks=16,
                      draft_model=draft, spec_k=3)
        prompt = _prompt(19, seed=140)
        ref = _ref_greedy(model, prompt, 10)
        try:
            cold = eng.generate(prompt, max_tokens=10).result(timeout=60)
            warm = eng.generate(prompt, max_tokens=10).result(timeout=60)
            assert cold["tokens"] == ref
            assert warm["tokens"] == ref
            s = eng.stats()
            assert s["prefix_hits"] == 1      # the warm run reused blocks
            assert s["spec_steps"] > 0        # and speculation really ran
        finally:
            eng.close(10)
