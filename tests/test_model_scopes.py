"""Names for device time (PR 25): `model_scope` inside the jitted steps and
live `span()`s on the decode scheduler's loop.

- every row of the scope table is in the lowered programs' `op_name`s,
  forward and (training) backward;
- scopes are metadata only: with `model_scope` a no-op the compiled HLO,
  metadata stripped, is the same text;
- a tiny `DecodeEngine` run leaves the loop's spans in the ring with the
  right parents, one `generation/queue` in each request's tree, and under
  a `jax.profiler` trace the live ones are host events by name;
- a bare `jax.named_scope` under `models/` or `runtime/` would lose the
  `dl4j.` prefix a reader finds scopes by, so there is none.
"""
import contextlib
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common import tracing
from deeplearning4j_tpu.common.tracing import (MODEL_SCOPE_PREFIX,
                                               TraceContext, model_scope,
                                               new_span_id, new_trace_id,
                                               tracer, use_context)
from deeplearning4j_tpu.models import _optim, bert, causal_lm
from deeplearning4j_tpu.runtime import generation
from deeplearning4j_tpu.runtime.generation import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LM = causal_lm.CausalLMConfig.tiny()
TRAIN_SCOPES = ("embed", "attn", "attn_core", "mlp", "ln", "head", "loss",
                "optimizer")
SERVE_SCOPES = ("embed", "attn", "attn_core", "kv_write", "kv_read", "mlp",
                "ln", "head", "sample")
SCOPE = re.compile(re.escape(MODEL_SCOPE_PREFIX) + r"(\w+)")


def leaf_scopes(lowered):
    """{(leaf scope, is backward)} over the lowered program's op names."""
    names = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    out = set()
    for n in names:
        found = SCOPE.findall(n)
        if found:
            out.add((found[-1], "transpose(" in n))
    return out


def strip_metadata(hlo_text):
    """The HLO text without what names where an operation came from: each
    instruction's `metadata={...}` and the tables of files and stack
    frames between the module line and the first computation."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo_text)
    return re.sub(r"\nFileNames\n.*?\n\n\n", "\n", text, flags=re.S)


# -- the programs ---------------------------------------------------------

def lower_bert_step():
    c = bert.BertConfig.tiny()
    params = bert.init_params(jax.random.key(0), c)
    batch = {"input_ids": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32),
             "attention_mask": jnp.ones((2, 16), jnp.int32)}
    step = bert.make_train_step(c, None, remat=False)
    return step.lower(params, bert.init_opt_state(params), batch, 0)


def lower_engine_steps():
    """The engine's own `decode_fn` and `prefill_fn` (paged_decode /
    paged_prefill plus `sample_tokens`), lowered as `_run_decode` and
    `_run_prefill` call them."""
    eng = DecodeEngine(causal_lm.CausalLM(LM, seed=0), slots=2, max_ctx=64,
                       prompt_buckets=[32])
    try:
        i32 = lambda x: jnp.asarray(x, jnp.int32)
        sampling = (jnp.zeros(2, jnp.float32), i32(np.zeros(2)), i32(0),
                    i32(0))
        decode = eng._decode.lower(
            eng._params, eng._cache, i32(eng._tables), i32(eng._tokens),
            i32(eng._lengths), jnp.zeros(2, bool), *sampling)
        prefill = eng._prefill.lower(
            eng._params, eng._cache, i32(np.zeros((2, 32))),
            i32(np.zeros((2, eng.max_blocks))), i32(np.ones(2)),
            i32(np.zeros(2)), *sampling)
    finally:
        eng.close(10)
    return decode, prefill


@pytest.fixture(scope="module")
def engine_steps():
    return lower_engine_steps()


@pytest.fixture(scope="module")
def bert_step():
    return lower_bert_step()


@pytest.mark.parametrize("scope", TRAIN_SCOPES)
def test_train_step_names_every_scope(bert_step, scope):
    found = leaf_scopes(bert_step)
    assert (scope, False) in found
    if scope != "optimizer":        # nothing differentiates through Adam
        assert (scope, True) in found, f"no backward op under {scope}"


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serving_steps_name_every_scope(engine_steps, program):
    lowered = engine_steps[program == "prefill"]
    found = {s for s, backward in leaf_scopes(lowered)}
    assert found == set(SERVE_SCOPES)
    assert not any(b for _, b in leaf_scopes(lowered))


def test_nested_scope_is_inside_its_parent(engine_steps):
    """`attn_core`, `kv_write` and `kv_read` are leaves inside `attn`."""
    txt = engine_steps[0].as_text(debug_info=True)
    for inner in ("attn_core", "kv_write", "kv_read"):
        paths = [n for n in re.findall(r'loc\("([^"]*)"', txt)
                 if f"dl4j.{inner}" in n]
        assert paths and all("dl4j.attn" in p.split(f"dl4j.{inner}")[0]
                             for p in paths)


def test_program_names_are_kept(bert_step, engine_steps):
    """The benchmark finds its programs as jit_step / jit_decode_fn /
    jit_prefill_fn."""
    for lowered, name in zip((bert_step,) + engine_steps,
                             ("jit_step", "jit_decode_fn", "jit_prefill_fn")):
        assert name in lowered.as_text()[:200]


@pytest.mark.parametrize("program", ["train", "decode", "prefill"])
def test_scopes_change_no_computation(monkeypatch, bert_step, engine_steps,
                                      program):
    scoped = (bert_step if program == "train"
              else engine_steps[program == "prefill"])
    for mod in (bert, causal_lm, _optim, generation):
        monkeypatch.setattr(mod, "model_scope",
                            lambda name: contextlib.nullcontext())
    if program == "train":
        bare = lower_bert_step()
    else:
        bare = lower_engine_steps()[program == "prefill"]
    assert not leaf_scopes(bare)
    a = strip_metadata(scoped.compile().as_text())
    b = strip_metadata(bare.compile().as_text())
    assert "dl4j." not in a and a == b


def test_model_scope_is_a_prefixed_named_scope():
    def f(x):
        with model_scope("probe"):
            return x * 2
    txt = jax.jit(f).lower(1.0).as_text(debug_info=True)
    assert "dl4j.probe" in txt


def test_no_bare_named_scope_in_models_or_runtime():
    """Only the helper calls `jax.named_scope`, so every scope keeps the
    prefix that `benchmark/scope_reduce.py` finds it by."""
    hits = []
    for sub in ("models", "runtime", "kernels", "parallel", "quant"):
        for path in glob.glob(os.path.join(
                ROOT, "deeplearning4j_tpu", sub, "**", "*.py"),
                recursive=True):
            with open(path) as f:
                if "named_scope" in f.read():
                    hits.append(os.path.relpath(path, ROOT))
    assert hits == []


# -- the scheduler's loop -------------------------------------------------

LOOP_SPANS = ("generation/admit", "generation/prefill_dispatch",
              "generation/step", "generation/ensure_blocks",
              "generation/decode_dispatch", "generation/readback",
              "generation/emit", "generation/reconcile")


@pytest.fixture(scope="module")
def served():
    """One short generation under a request context and a CPU profile:
    (ring events, the request's context, host event names in the
    profile)."""
    from jax.profiler import ProfileData
    import tempfile
    eng = DecodeEngine(causal_lm.CausalLM(LM, seed=0), slots=2, max_ctx=64,
                       prompt_buckets=[32])
    ctx = TraceContext(new_trace_id(), new_span_id())
    tracer().clear()
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with use_context(ctx):
                fut = eng.generate(np.arange(1, 6), max_tokens=4)
            out = fut.result(60)
            time.sleep(0.05)        # the loop reaches its idle wait, and
            # a second request (of no trace) ends that wait inside the
            # profile
            eng.generate(np.arange(1, 4), max_tokens=2).result(60)
        finally:
            jax.profiler.stop_trace()
            eng.close(10)
        events = tracer().events()
        pb = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                    "*.xplane.pb"))[-1]
        host = {e.name for p in ProfileData.from_file(pb).planes
                if p.name.startswith("/host:")
                for line in p.lines for e in line.events}
    assert len(out["tokens"]) == 4
    return events, ctx, host


@pytest.mark.parametrize("name", LOOP_SPANS + ("generation/idle",))
def test_loop_phase_is_a_live_span(served, name):
    events, _, host = served
    assert any(e["name"] == name for e in events), "not in the ring"
    assert name in host, "not on the profiler's host plane"


def test_loop_spans_nest_under_the_scheduler_trace(served):
    events, ctx, _ = served
    loop = [e for e in events if e["name"] in LOOP_SPANS]
    ids = {e["args"]["trace_id"] for e in loop}
    assert len(ids) == 1 and ctx.trace_id not in ids
    by_id = {e["args"]["span_id"]: e for e in loop}
    parent = lambda e: by_id.get(e["args"].get("parent_span_id"), {}) \
        .get("name")
    for e in loop:
        want = {"generation/prefill_dispatch": "generation/admit",
                "generation/ensure_blocks": "generation/step",
                "generation/decode_dispatch": "generation/step",
                "generation/readback": "generation/step",
                "generation/emit": "generation/step"}.get(e["name"])
        assert parent(e) == want, e["name"]
    # the prefill samples each request's first token; 3 + 1 decode steps
    # give the rest
    assert sum(e["name"] == "generation/decode_dispatch" for e in loop) == 4


def test_queue_wait_is_a_span_of_the_request(served):
    events, ctx, _ = served
    mine = [e for e in events
            if e.get("args", {}).get("trace_id") == ctx.trace_id]
    names = [e["name"] for e in mine]
    assert sorted(names) == ["generation/decode", "generation/prefill",
                             "generation/queue"]
    queue = mine[names.index("generation/queue")]
    prefill = mine[names.index("generation/prefill")]
    assert queue["args"]["parent_span_id"] == ctx.span_id
    # the queue span ends where the prefill span starts
    assert queue["ts"] + queue["dur"] == pytest.approx(prefill["ts"])
    roots = tracing.span_tree(mine)
    assert len(roots) == 3      # siblings under the submitter's span


def test_spans_cost_nothing_when_metrics_are_off(monkeypatch):
    from deeplearning4j_tpu.common.metrics import registry
    monkeypatch.setattr(registry(), "enabled", False)
    assert tracing.span("generation/step") is tracing._NULL_SPAN
