"""Tests of the benchmark's own code, on the CPU, outside tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The end-to-end rehearsals drive each driver at a tiny configuration kept
in this directory (`rehearsal.json`; never in BENCHMARK.json) with the
harness's look for a chip skipped. Nothing here is a device number.
"""
import json
import os
import re
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, loadgen, stats, trace_reduce, work  # noqa: E402

BENCH = harness.load_benchmark(ROOT)
REHEARSAL = harness.load_json(HERE, "rehearsal.json")
LARGE = harness.load_json(ROOT, "benchmark/configs/bert-large-mlm.json")
# BertGeneration L-24 (google/bert_for_seq_generation_L-24_bbc_encoder):
# the decoder whose cells PERF.md keeps for later
GEN = {"hidden_size": 1024, "num_hidden_layers": 24,
       "num_attention_heads": 16, "intermediate_size": 4096,
       "vocab_size": 50358, "max_position_embeddings": 512}


def rehearse(workload, seed=5, seconds=1.5, trace=False):
    return harness.run_cell(workload, seed, seconds, trace,
                            t_start=time.monotonic(), need_chip=False,
                            bench=REHEARSAL, root=ROOT)


# -- trace reduction ------------------------------------------------------

def test_union_merges_overlaps():
    assert trace_reduce.union([(3, 4), (0, 1), (0.5, 2)]) == [[0, 2], [3, 4]]


def test_reduce_events_by_hand():
    dev = {"/device:TPU:0": {
        "ops": [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("a", 3.0, 1.0)],
        "modules": [("jit_f(12)", 0.0, 1.5), ("jit_f(12)", 3.0, 1.0)]}}
    host = [("serving/request", 0.0, 5.0), ("generation/admit", 1.6, 1.0)]
    r = trace_reduce.reduce_events(dev, host, window=(0.0, 5.0))
    assert r["busy_s"] == pytest.approx(2.5)
    assert r["window_s"] == pytest.approx(5.0)
    assert r["programs"] == {"jit_f": [1.5, 1.0]}
    assert r["device_ops"] == [["a", 2.0], ["b", 1.0]]
    # the gap 1.5-3.0 goes to the innermost span covering half of it, the
    # gap 4.0-5.0 to the only span over it
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"generation/admit": 1.5, "serving/request": 1.0})


def test_recorded_tpu_trace():
    """`tiny_tpu.xplane.pb` was recorded on a v5e chip by
    record_trace.py: four executions of one program, a 20 ms pause under
    `bench/pause` after the second."""
    raw = trace_reduce.read_xplane(os.path.join(HERE, "tiny_tpu.xplane.pb"))
    assert list(raw["devices"]) == ["/device:TPU:0"]
    r = trace_reduce.reduce_events(raw["devices"], raw["host"])
    assert len(r["programs"]["jit_tiny_step"]) == 4
    assert 0 < r["busy_s"] < 1e-4 < r["window_s"]
    assert 1 - r["busy_s"] / r["window_s"] > 0.99
    assert r["idle_gaps"][0][0] == "bench/pause"
    assert r["idle_gaps"][0][1] > 0.015
    assert r["device_ops"][0][0] == "convolution_tanh_fusion"
    # no gap is lost: busy + gaps = window
    assert r["busy_s"] + sum(g for _, g in r["idle_gaps"]) == pytest.approx(
        r["window_s"])


# -- work -----------------------------------------------------------------

def test_work_bert_large_by_hand():
    E, F, L, V, T = 1024, 4096, 24, 30522, 512
    per_layer = 2 * (4 * E * E + 2 * E * F) + 4 * T * E
    forward = L * per_layer + 2 * E * E + 2 * E * V
    assert forward == 718_917_632
    assert work.mlm_forward_flops_per_token(LARGE, T) == forward
    assert work.mlm_train_flops_per_token(LARGE, T) == 3 * forward


def test_work_decoder_by_hand():
    E, F, L, V = 1024, 4096, 24, 50358
    mats = 4 * E * E + 2 * E * F
    # prompt 3, 2 generated: tokens at positions 0..3 go through the
    # blocks, attending 1+2+3+4 = 10 keys; the head runs twice
    want = L * (4 * 2 * mats + 4 * 10 * E) + 2 * 2 * E * V
    assert work.decoder_request_flops(GEN, 3, 2) == want
    assert work.decoder_weight_bytes(GEN) == 2 * (L * mats + V * E)
    peak = harness.peak_for("TPU v5 lite")
    least = work.decode_step_min_seconds(GEN, 96, 96 * 128, peak)
    kv = 96 * 128 * 2 * E * L * 2
    assert least["bytes"] == work.decoder_weight_bytes(GEN) + kv
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(least["bytes"] / 819e9)


def test_peaks_unknown_device_is_an_error():
    assert harness.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peak_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        harness.peak_for("_source")


# -- load generator -------------------------------------------------------

@pytest.mark.parametrize("name", ["chat-tiny", "closed-tiny"])
def test_plan_is_a_function_of_the_seed(name):
    traffic = harness.load_json(HERE, "traffic", name + ".json")
    a = loadgen.plan(traffic, 2_500_000_011, 20.0, 64)
    b = loadgen.plan(traffic, 2_500_000_011, 20.0, 64)
    c = loadgen.plan(traffic, 7, 20.0, 64)
    assert a == b and a != c
    # every seed: the same sizes and arrivals, in another order
    sizes = lambda p: sorted((r["prompt_tokens"]) for r in p)
    assert sizes(a) == sizes(c)
    for r in a:
        assert r["prompt_tokens"] + r["max_tokens"] <= 64
    if traffic["loop"] == "open":
        due = [r["due"] for r in a]
        assert due == sorted(due) and due[0] >= -traffic["lead_in_s"]
        gaps = lambda p: sorted(y["due"] - x["due"]
                                for x, y in zip(p, p[1:]))
        # the first gap counts from the lead-in's start: leave both ends
        assert gaps(a)[1:-1] == pytest.approx(gaps(c)[1:-1], abs=1e-9) \
            or sum(gaps(a)) == pytest.approx(sum(gaps(c)), rel=0.05)
        rate = len([d for d in due if 0 <= d < 20.0]) / 20.0
        assert rate == pytest.approx(traffic["rate_per_s"], rel=0.1)
    late = [r["due"] for r in a if r["due"] is not None]
    assert (traffic["loop"] == "open") == bool(late)
    assert loadgen.prompt_ids(3, 5, 40, 50358) == loadgen.prompt_ids(
        3, 5, 40, 50358)
    assert loadgen.prompt_ids(3, 5, 40, 50358) != loadgen.prompt_ids(
        3, 6, 40, 50358)


def test_percentile():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([0, 10], 95) == pytest.approx(9.5)
    assert stats.percentile([], 95) is None


# -- BENCHMARK.json resolves ----------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_resolves_to_files():
    for w in BENCH["workloads"]:
        cell = harness.load_cell(BENCH, w["name"], ROOT)
        assert cell["config"]["kind"]
        harness.driver_for(cell["config"]["kind"])
        assert cell["traffic"]["why"]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert harness.cell_metrics(BENCH, w["name"], "per_layer")
        e2e = [m["name"] for m in
               harness.cell_metrics(BENCH, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2


def test_metrics_resolve_and_keep_the_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert e2e["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in BENCH["per_layer"]:
        spec = harness.load_json(ROOT, "benchmark/layer_metrics",
                                 m["name"] + ".json")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark/layer_metrics", spec["reader"] + ".py"))
        for k in ("layer", "source", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        # each listed cell reports the end-to-end metric it moves
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_run_py_names_no_cell():
    for f in ("run.py", "harness.py"):
        src = open(os.path.join(ROOT, "benchmark", f)).read()
        for w in BENCH["workloads"]:
            assert w["name"] not in src and w["config"] not in src


def test_off_the_chip_it_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark/run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


# -- end-to-end rehearsals ------------------------------------------------

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("workload", [w["name"]
                                      for w in REHEARSAL["workloads"]])
def test_rehearsal_prints_the_contracts_last_line(workload, capsys):
    out = rehearse(workload)
    harness.emit(out["result"], out["checks"], out["notes"])
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(last) and list(last)[-1] == "compared"
    assert last["correct"] is True, last["compared"]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "setup_s" in last["metrics"] and len(last["metrics"]) >= 2
    for v in last["metrics"].values():
        assert v["value"] > 0 and v["unit"]
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # the numbers compared are the last lines of stderr, each by its limit
    tail = captured.err.strip().splitlines()[-len(last["compared"]):]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)


def test_traced_rehearsal_reports_layer_metrics_it_can_read():
    """On the CPU there is no device plane: trace readers return nothing
    and are left out (never 0); counter and client readers still read."""
    bench = json.loads(json.dumps(REHEARSAL))
    name = "bertgen-tiny.closed-tiny"
    # the serving metrics' files are kept for the cells PERF.md keeps for
    # later; here every one of them is asked of the tiny closed loop
    serving = ["decode_batch_occupancy_pct", "ttft_p95_ms.closed",
               "itl_p95_ms.closed", "device_idle_pct.serve",
               "prefill_share_pct", "mfu.serve", "decode_step_device_ms",
               "decode_step_roofline", "engine_ttft_p50_ms",
               "http_ttft_overhead_ms"]
    bench["per_layer"] = [
        {"name": n, "unit": "x", "better": "lower", "workloads": [name],
         "source": "host_clock", "layer": "x", "moves": "serve_tokens_per_s"}
        for n in serving]
    out = harness.run_cell(name, 9, 1.5, True, t_start=time.monotonic(),
                           need_chip=False, bench=bench, root=ROOT)
    got = out["result"]["metrics"]
    assert "decode_batch_occupancy_pct" in got
    assert 0 < got["decode_batch_occupancy_pct"]["value"] <= 100
    assert "ttft_p95_ms.closed" in got and "itl_p95_ms.closed" in got
    assert got["engine_ttft_p50_ms"]["value"] > 0
    for trace_sourced in ("device_idle_pct.serve", "prefill_share_pct",
                          "decode_step_device_ms", "decode_step_roofline",
                          "mfu.serve"):
        assert trace_sourced not in got
    assert {"busy_s", "window_s"} <= set(out["result"]["device"])
    assert set(out["result"]["breakdown"]) == {"device_ops", "idle_gaps"}


# -- the control and the planted faults come out as not correct ------------

def test_training_control_fails_and_program_passes():
    from benchmark.drivers import train_mlm
    cell = harness.load_cell(REHEARSAL, "bert-tiny-mlm.train-tiny", ROOT)
    session = train_mlm.setup(cell, 11)
    got = train_mlm.readings(session, None, faults=True)
    limits = cell["config"]["limits"]
    over = lambda numbers: [k for k, v in numbers.items() if v > limits[k]]
    assert not over(got["program"])
    assert over(got["control_fp8"])
    assert "grad_norm_gap" in over(got["fault_half_batch"])


def _broken_train_step(kind):
    from deeplearning4j_tpu.models import bert
    real = bert.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def unchanged(params, opt, batch, it):
            return params, opt, step(params, opt, batch, it)[2]

        def half(params, opt, batch, it):
            n = batch["input_ids"].shape[0] // 2
            return step(params, opt, {k: v[:n] for k, v in batch.items()},
                        it)

        return {"unchanged": unchanged, "half": half}[kind]

    return make


@pytest.mark.parametrize("kind,caught_by", [
    ("unchanged", "change_norm_gap"), ("half", "grad_norm_gap")])
def test_broken_train_step_is_not_correct(kind, caught_by, monkeypatch):
    import jax
    from deeplearning4j_tpu.models import bert
    if kind == "unchanged":
        # the broken step hands back what it was given: nothing may be
        # donated away under it
        monkeypatch.setattr(bert, "_jit_step",
                            lambda fn, *a, **kw: jax.jit(fn))
    monkeypatch.setattr(bert, "make_train_step", _broken_train_step(kind))
    out = rehearse("bert-tiny-mlm.train-tiny")
    assert out["result"]["correct"] is False
    failed = [c["name"] for c in out["checks"] if c["value"] > c["limit"]]
    assert caught_by in failed


def test_altered_token_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.runtime.generation import DecodeEngine
    real = DecodeEngine._run_decode

    def altered(self, active):
        nxt = real(self, active).copy()
        nxt[0] = (nxt[0] + 1) % self.model.config.vocab_size
        return nxt

    monkeypatch.setattr(DecodeEngine, "_run_decode", altered)
    out = rehearse("bertgen-tiny.chat-tiny", seconds=2.0)
    assert out["result"]["correct"] is False


def test_serving_control_reads_above_the_program():
    from benchmark.drivers import serve_generate
    cell = harness.load_cell(REHEARSAL, "bertgen-tiny.closed-tiny", ROOT)
    session = serve_generate.setup(cell, 13)
    window = serve_generate.measure(session, 1.5, None)
    serve_generate.release(session)
    got = serve_generate.readings(session, window)
    limit = cell["config"]["limits"]["served_logit_gap"]
    assert got["program"]["served_logit_gap"] <= limit
    assert got["control_fp8"]["served_logit_gap"] > limit
