"""Driver for `kind: serve_generate`: a causal decoder deployed through
the program's `ModelRegistry` behind `ModelServer`, driven over HTTP
(`POST /v1/models/<name>/generate`, streamed) by the load generator in a
child process that never touches jax.

Set-up makes the weights on the device in one jitted call from the seed,
deploys (which warms every prefill rung and the decode step), starts the
server and the load generator, and ends when the window opens: the
generator's lead-in, which brings the system to its steady state, is
set-up. After the window the generator waits for what is in flight; then
the memory peak is read, the deployment is dropped, and the reference
(`benchmark/reference/bertgen_decoder.py`) is run over a sample of the
requests the window finished, drawn from the seed, the longest among
them.
"""
from __future__ import annotations

import gc
import json
import os
import random
import subprocess
import sys
import time

from benchmark import harness, loadgen, stats
from benchmark.reference import bert_mlm as base
from benchmark.reference import bertgen_decoder as ref

MODEL = "lm"
FAILED_MS = 60000.0     # what a refused or failed request counts as


def _program_config(cfg):
    from deeplearning4j_tpu.models import causal_lm
    return causal_lm.CausalLMConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        layer_norm_eps=cfg["layer_norm_eps"])


def make_weights(cfg, seed):
    import jax
    lo, hi = harness.seed_words(seed)

    @jax.jit
    def weights(lo, hi):
        return ref.make_flat_params(
            jax.random.fold_in(jax.random.key(lo), hi), cfg)

    return lambda: weights(lo, hi)


def _counters(engine) -> dict:
    """The program's counters the per-layer readers use: the metrics
    registry's unlabelled families and the engine's own stats."""
    from deeplearning4j_tpu.common.metrics import registry
    out = {}
    for name in ("dl4j_decode_tokens_total", "dl4j_decode_steps_total",
                 "dl4j_decode_requests_total", "dl4j_decode_preempted_total",
                 "dl4j_decode_expired_total"):
        fam = registry().get(name)
        if fam is not None:
            out[name] = float(fam.value())
    for k, v in engine.stats().items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out["engine." + k] = float(v)
    return out


def setup(cell, seed):
    import jax
    from deeplearning4j_tpu.models import causal_lm
    from deeplearning4j_tpu.serving import ModelRegistry, ModelServer

    cfg = cell["config"]
    traffic = cell["traffic"]
    weights = make_weights(cfg, seed)
    L = cfg["num_hidden_layers"]
    params = jax.jit(lambda f: base.nest(f, L))(weights())
    model = causal_lm.CausalLM(_program_config(cfg), params=params)
    registry = ModelRegistry(manifest_dir=None)
    server = ModelServer(registry, **cfg["server"])
    registry.deploy(MODEL, "v1", model, **cfg["deploy"])
    port = server.start()
    engine = registry.get(MODEL).engine
    lead = float(traffic.get("lead_in_s", 0.0))
    return {"cell": cell, "seed": seed, "weights": weights, "model": model,
            "registry": registry, "server": server, "engine": engine,
            "port": port, "lead": lead}


def start_load(session, seconds):
    """Start the generator; the window opens at the returned t0."""
    cell = session["cell"]
    t0 = time.monotonic() + 1.0 + session["lead"]
    job = {"port": session["port"],
           "path": f"/v1/models/{MODEL}/generate",
           "traffic": cell["traffic"], "seed": session["seed"], "t0": t0,
           "seconds": seconds, "vocab_size": cell["config"]["vocab_size"],
           "max_ctx": cell["config"]["deploy"]["decode_max_ctx"]}
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(loadgen.__file__)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    child.stdin.write(json.dumps(job).encode())
    child.stdin.close()
    session["child"] = child
    return t0


def _sleep_until(t):
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))


def measure(session, seconds, profile):
    traffic = session["cell"]["traffic"]
    engine = session["engine"]
    t0 = start_load(session, seconds)
    child = session["child"]
    try:
        _sleep_until(t0)
        session["window_starts_at"] = t0
        before = _counters(engine)
        traced = None
        if profile is not None:
            # the last trace_seconds of the window, so that stopping the
            # profiler (seconds of serialisation) falls after its close
            _sleep_until(t0 + max(seconds - traffic["trace_seconds"], 0.0))
            a = time.monotonic() - t0
            profile.start()
        _sleep_until(t0 + seconds)
        after = _counters(engine)
        if profile is not None:
            traced = (a, time.monotonic() - t0)
            profile.stop()
        raw = child.stdout.read()
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    out = json.loads(raw)
    window = reduce_records(out["records"], session["cell"], seconds)
    window["counters"] = {k: after[k] - before.get(k, 0.0) for k in after}
    window["gauges"] = after
    window["traced"] = traced
    window["notes"].update(unfinished=out["unfinished"],
                           planned=out["planned"])
    session["records"] = out["records"]
    return window


def reduce_records(records, cell, seconds):
    """From the generator's records to the window's numbers. A request
    belongs to the window if it was due (open loop) or sent (closed loop)
    inside it; a token gap if its later token arrived inside it; a
    completion if its closing line arrived inside it."""
    open_loop = cell["traffic"]["loop"] == "open"
    ttft, engine_ttft, overhead, late, itl = [], [], [], [], []
    attempted = failed = 0
    done_tokens, completed = 0, []
    for r in records:
        ok = (r["status"] == 200 and r["error"] is None and r.get("closing")
              and not r["closing"].get("error")
              and len(r["tokens"]) == r["max_tokens"])
        begun = r["due"] if open_loop else r["sent"]
        if 0.0 <= begun < seconds:
            attempted += 1
            if not ok:
                failed += 1
                ttft.append(FAILED_MS)
            else:
                first = 1e3 * (r["stamps"][0] - begun)
                ttft.append(first)
                e = r["closing"].get("ttft_s")
                if e is not None:
                    engine_ttft.append(1e3 * e)
                    overhead.append(1e3 * (r["stamps"][0] - r["sent"]) -
                                    1e3 * e)
            if open_loop:
                late.append(1e3 * (r["sent"] - r["due"]))
        for a, b in zip(r["stamps"], r["stamps"][1:]):
            if 0.0 <= b < seconds:
                itl.append(1e3 * (b - a))
        if ok and 0.0 <= r["done"] < seconds:
            done_tokens += r["prompt_tokens"] + len(r["tokens"])
            completed.append((r["prompt_tokens"], len(r["tokens"])))
    e2e = {"serve_tokens_per_s": done_tokens / seconds,
           "ttft_p95_ms": stats.percentile(ttft, 95),
           "itl_p95_ms": stats.percentile(itl, 95)}
    client = {"ttft_ms": ttft, "engine_ttft_ms": engine_ttft,
              "http_overhead_ms": overhead, "late_ms": late, "itl_ms": itl}
    notes = {"requests_in_window": attempted, "failed": failed,
             "completed_in_window": len(completed),
             "ttft_p50_ms": stats.percentile(ttft, 50),
             "itl_p50_ms": stats.percentile(itl, 50),
             "itl_samples": len(itl),
             "loadgen_late_p95_ms": stats.percentile(late, 95)}
    return {"end_to_end": e2e, "attempted": attempted, "failed": failed,
            "client": client, "completed": completed, "notes": notes,
            "seconds": seconds, "records": records}


def release(session):
    """Stop serving and free the deployment before the reference runs."""
    session["server"].stop()
    session["registry"].drain_all(save_manifests=False)
    for k in ("model", "registry", "server", "engine"):
        session.pop(k, None)
    gc.collect()


def sample(records, seed, count, seconds):
    """`count` of the requests the window finished, drawn from the seed,
    the longest (prompt + served tokens) always among them."""
    done = [r for r in records
            if r["status"] == 200 and r["tokens"] and r.get("done") is not None
            and 0.0 <= r["done"] < seconds]
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_tokens"] + len(r["tokens"]),
                                       -r["id"]))
    rest = [r for r in done if r is not longest]
    random.Random(int(seed) * 1000003 + 99).shuffle(rest)
    return [longest] + rest[:max(count - 1, 0)]


def gaps_for(cfg, weights, picked, seed, control=None):
    import jax
    flat32 = jax.jit(base.as_f32)(weights())
    chk = cfg["reference"]
    reqs = [(loadgen.prompt_ids(seed, r["id"], r["prompt_tokens"],
                                cfg["vocab_size"]), r["tokens"])
            for r in picked]
    return ref.served_gaps(flat32, reqs, eps=cfg["layer_norm_eps"],
                           seq_len=chk["seq_len"], gen_len=chk["gen_len"],
                           rows=chk["rows"], control=control)


def check(session, window):
    cell = session["cell"]
    cfg = cell["config"]
    picked = sample(session["records"], session["seed"],
                    cell["traffic"]["check_requests"], window["seconds"])
    limit = cfg["limits"].get("served_logit_gap", 0.0)
    if not picked:
        return [{"name": "served_logit_gap", "value": None, "limit": limit}]
    out = gaps_for(cfg, session["weights"], picked, session["seed"])
    widest = max(float(g.max()) for g in out["served"])
    window["notes"]["checked_requests"] = len(picked)
    window["notes"]["checked_tokens"] = int(sum(len(g)
                                                for g in out["served"]))
    return [{"name": "served_logit_gap", "value": widest, "limit": limit}]


def readings(session, window, faults=False):
    """Lower and upper readings for the limit (benchmark/readings.py):
    the widest gap of the served tokens, and of the tokens that the
    control (fp8 matmul operands) puts first at the same positions."""
    cell = session["cell"]
    picked = sample(session["records"], session["seed"],
                    cell["traffic"]["check_requests"], window["seconds"])
    out = gaps_for(cell["config"], session["weights"], picked,
                   session["seed"], control="fp8")
    import numpy as np
    served = np.concatenate(out["served"])
    control = np.concatenate(out["control"])
    return {"program": {"served_logit_gap": float(served.max()),
                        "served_nonzero_share": float((served > 0).mean()),
                        "tokens": float(served.size)},
            "control_fp8": {"served_logit_gap": float(control.max()),
                            "served_nonzero_share":
                                float((control > 0).mean())}}
