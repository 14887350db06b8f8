"""Tests of what the Mellum2 cell adds to the benchmark, on the CPU, outside
tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mellum.py -q

The rehearsal drives `drivers/train_window_lm.py` at a tiny configuration
kept in this directory (`rehearsal_mellum.json`; never in BENCHMARK.json)
with the harness's look for a chip skipped. Nothing here is a device
number.
"""
import json
import math
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, work_mellum  # noqa: E402

BENCH = harness.load_benchmark(ROOT)
REHEARSAL = harness.load_json(HERE, "rehearsal_mellum.json")
CELL = "mellum2-12b-a2.5b.train-t16384"
TINY = "mellum-tiny.train-tiny-window"
REAL = harness.load_json(ROOT, "benchmark/configs/mellum2-12b-a2.5b.json")
MELLUM_METRICS = [m for m in BENCH["per_layer"]
                  if m["name"].endswith(".mellum")]
COMPARED = {"loss_gap", "grad_norm_gap", "change_norm_gap",
            "expert_grad_norm_gap", "expert_change_norm_gap"}


def rehearse(seed=5, seconds=1.0, trace=False):
    return harness.run_cell(TINY, seed, seconds, trace,
                            t_start=time.monotonic(), need_chip=False,
                            bench=REHEARSAL, root=ROOT)


# -- the cell and its files ---------------------------------------------------

def test_the_cell_resolves_to_files():
    cell = harness.load_cell(BENCH, CELL, ROOT)
    assert cell["chips"] == 1 and cell["traffic"]["run_ahead"] == 2
    assert cell["traffic"]["kind"] == "train_back_to_back"
    cfg = cell["config"]
    assert cfg["kind"] == "train_window_lm"
    driver = harness.driver_for(cfg["kind"])
    for fn in ("setup", "measure", "release", "check", "readings"):
        assert callable(getattr(driver, fn))
    assert cfg["train"] == {**cfg["train"], "batch": 1, "seq_len": 16384,
                            "remat": True, "batches": 16,
                            "warmup_steps": 2000, "learning_rate": 1e-4}
    e2e = [m["name"] for m in harness.cell_metrics(BENCH, CELL, "end_to_end")]
    assert sorted(e2e) == ["setup_s", "train_tokens_per_s"]
    per_layer = {m["name"] for m in
                 harness.cell_metrics(BENCH, CELL, "per_layer")}
    assert {"train_step_device_ms", "device_idle_pct.train",
            "setup_step_compile_s", "setup_outside_step_s"} <= per_layer
    assert not any(n.endswith((".hybrid", ".granite", ".joyai"))
                   or n == "mfu.train" for n in per_layer)
    assert len(MELLUM_METRICS) == 12
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "mellum2-12b-a2.5b"] == [CELL]


def test_configuration_keeps_the_published_keys():
    """Every key of the catalog's config is in the file, equal unless
    `reduced` lists it; no width is reduced."""
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "mellum2-12b-a2.5b")
    assert entry["source"] == REAL["source"]
    assert entry["reduced"] == REAL["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_experts", "vocab_size"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "sliding_window": 1024,
        "tie_word_embeddings": False, "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}}}
    for k, v in published.items():
        assert REAL[k] == v and k not in REAL["reduced"], k
    pub = REAL["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (28, 64, 98304)
    assert pub["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 7
    assert pub["mlp_layer_types"] == ["sparse"] * 28
    # the cut: one whole period, 16 of 64 experts (a 4-way share), a
    # quarter of the vocabulary
    assert REAL["num_hidden_layers"] == 4
    assert REAL["layer_types"] == pub["layer_types"][:4]
    assert REAL["mlp_layer_types"] == pub["mlp_layer_types"][:4]
    assert REAL["num_experts"] == 16 and REAL["vocab_size"] * 4 == 98304
    assert REAL["deployment"]["expert_parallel"] * REAL["num_experts"] \
        == pub["num_experts"]
    for k in ("departures", "assumed", "deployment", "limits", "limits_from"):
        assert REAL[k], k
    for k in ("window", "rotary_order", "qk_norm", "initialisation",
              "dropout", "optimizer"):
        assert REAL["assumed"][k], k
    assert "mtp" in REAL["departures"]
    assert set(REAL["limits"]) == COMPARED


def test_every_new_metric_file_names_a_reader_that_exists():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in MELLUM_METRICS:
        spec = harness.load_json(ROOT, "benchmark/layer_metrics",
                                 m["name"] + ".json")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark/layer_metrics", spec["reader"] + ".py")), m
        for k in ("layer", "source", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert m["workloads"] == [CELL] and m["moves"] in e2e
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert ([m["name"] for m in REHEARSAL["per_layer"]
             if m["name"].endswith(".mellum")]
            == [m["name"] for m in MELLUM_METRICS])


def test_the_parameter_count():
    """595,153,152 parameters here: a layer 120,476,160 (attention
    21,233,664, router 147,456, 16 experts of 6,193,152, two norms), the
    embedding and the head's slices 113,246,208, the final norm."""
    from benchmark.reference import mellum2 as ref
    d = ref.dims(REAL)
    size = lambda shapes: sum(math.prod(s) for s, _ in shapes.values())
    attn = size(ref.block_shapes(d, "W"))
    experts = size(ref.block_shapes(d, "E"))
    assert attn - 2304 == 21_233_664
    assert experts - 2304 == 147_456 + 16 * 6_193_152
    assert attn + experts == 120_476_160
    assert d["pattern"] == "WEWEWE*E"
    assert size(ref.block_shapes(d, "*")) == attn
    # four layers, the embedding and head slices, the final norm
    total = 4 * (attn + experts) + 2 * 24576 * 2304 + 2304
    assert total == 595_153_152


# -- work -----------------------------------------------------------------

def test_work_mellum_by_hand():
    T, E = 16384, 2304
    assert work_mellum.window_pairs(T, 1024) == (1024 * 1025 // 2
                                                 + (T - 1024) * 1024)
    assert work_mellum.window_pairs(100, 1024) == 100 * 101 // 2
    proj = 2 * E * (2 * 4096 + 2 * 512)
    assert work_mellum.proj_flops_per_token(REAL) == proj == 42_467_328
    pair = 2 * 32 * 256
    assert work_mellum.core_flops_per_pair(REAL) == pair
    assert work_mellum.expert_visits_per_token(REAL) == 8 * 16 / 64 == 2.0
    layer = 2 * E * 64 + 2.0 * 6 * E * 896
    assert work_mellum.expert_layer_flops_per_token(REAL) == layer
    window = work_mellum.window_pairs(T, 1024)
    full = T * (T + 1) // 2
    forward = (4 * proj + 3 * pair * window / T + pair * full / T
               + 4 * layer + 2 * E * 24576)
    assert work_mellum.lm_forward_flops_per_token(REAL, T) == \
        pytest.approx(forward)
    train = work_mellum.lm_train_flops_per_token(REAL, T)
    # about 1.70 GFLOP a token, 27.8 TFLOP a step
    assert train == pytest.approx(1.70e9, rel=3e-3)
    assert train * T == pytest.approx(27.8e12, rel=3e-3)
    # the shares of the step, as the cell's reasons state them
    share = lambda part: 3 * part / train
    assert share(4 * proj) == pytest.approx(0.30, abs=0.01)
    assert share(pair * full / T) == pytest.approx(0.24, abs=0.01)
    assert share(2 * E * 24576) == pytest.approx(0.20, abs=0.01)
    assert share(4 * 2.0 * 6 * E * 896) == pytest.approx(0.175, abs=0.005)
    assert share(3 * pair * window / T) == pytest.approx(0.086, abs=0.003)
    # without the band the three window layers would cost 134M a token
    assert pair * full / T == pytest.approx(134.2e6, rel=1e-3)
    assert 3 * pair * window / T == pytest.approx(48.8e6, rel=2e-3)


def test_work_mellum_least_times_by_hand():
    peak = harness.peak_for("TPU v5 lite")
    T = 16384
    win = work_mellum.core_step_min_seconds(REAL, 1, T, peak, True)
    full = work_mellum.core_step_min_seconds(REAL, 1, T, peak, False)
    pair = 2 * 32 * 256
    assert win["flops"] == 3 * 3 * work_mellum.window_pairs(T, 1024) * pair
    assert full["flops"] == 3 * 1 * (T * (T + 1) // 2) * pair
    q, kv = 4096, 512
    per_token = (2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv)
    assert win["bytes"] == 2 * 3 * T * per_token
    assert full["bytes"] == 2 * 1 * T * per_token
    assert win["bound"] == full["bound"] == "flops"
    assert win["seconds"] == pytest.approx(win["flops"] / 197e12)
    rows = 4 * 2 * T
    mm = work_mellum.expert_mm_step_min_seconds(REAL, rows, peak)
    assert mm["flops"] == 3 * rows * 6 * 2304 * 896
    assert mm["bytes"] == 3 * (4 * 2 * 16 * 3 * 2304 * 896
                               + 2 * rows * (2304 + 3 * 896))


# -- readers --------------------------------------------------------------

def _ctx(window, cfg=REAL):
    return {"cell": {"config": cfg}, "window": window, "chips": 1,
            "peak": harness.peak_for("TPU v5 lite"),
            "end_to_end": {"train_tokens_per_s": 40000.0}}


def test_readers_by_hand_and_silent_without_the_programs_part():
    from benchmark.layer_metrics import (window_scope_time_ms,
                                         work_mfu_train,
                                         work_scope_roofline_pct)
    scopes = {"programs": {"jit_step": {
        "executions": 4, "op_s": 4.0, "scopes": {
            "attn_window": {"device_s": 0.08},
            "attn_core": {"device_s": 0.4},
            "moe_experts": {"device_s": 0.2}}}}}
    window = {"scopes": scopes, "attempted": 10, "counters": {},
              "traced": {"steps": 4, "counters": {
                  "dl4j_moe_held_assignments_total": 4 * 4 * 32768.0}}}
    ctx = _ctx(window)
    spec = lambda name: harness.load_json(
        ROOT, "benchmark", "layer_metrics", name + ".json")["params"]
    w, f = spec("attn_window_roofline_pct.mellum"), spec(
        "attn_full_roofline_pct.mellum")
    e, m = spec("expert_mm_roofline_pct.mellum"), spec("mfu.train.mellum")
    assert (w["scopes"], f["scopes"]) == (["attn_window"], ["attn_core"])
    assert window_scope_time_ms.read(ctx, spec(
        "attn_window_device_ms.mellum")) == pytest.approx(20.0)
    win = work_mellum.core_step_min_seconds(REAL, 1, 16384, ctx["peak"], True)
    assert work_scope_roofline_pct.read(ctx, w) == pytest.approx(
        100 * win["seconds"] / 0.02)
    full = work_mellum.core_step_min_seconds(REAL, 1, 16384, ctx["peak"],
                                             False)
    assert work_scope_roofline_pct.read(ctx, f) == pytest.approx(
        100 * full["seconds"] / 0.1)
    mm = work_mellum.expert_mm_step_min_seconds(REAL, 4 * 32768.0,
                                                ctx["peak"])
    assert work_scope_roofline_pct.read(ctx, e) == pytest.approx(
        100 * mm["seconds"] / 0.05)
    assert work_mfu_train.read(ctx, m) == pytest.approx(
        100 * work_mellum.lm_train_flops_per_token(REAL, 16384) * 40000
        / 197e12)
    # at the chip's peak the window core's own least time reads 100
    at_peak = {"scopes": {"programs": {"jit_step": {
        "executions": 1, "op_s": 1.0, "scopes": {
            "attn_window": {"device_s": win["seconds"]}}}}}}
    assert work_scope_roofline_pct.read(_ctx(at_peak), w) == \
        pytest.approx(100.0)
    # a program without the scopes or the counters (the parent): nothing,
    # and no raise
    bare = _ctx({"attempted": 10})
    for p in (w, f, e):
        assert work_scope_roofline_pct.read(bare, p) is None
    assert window_scope_time_ms.read(bare, spec(
        "attn_window_device_ms.mellum")) is None
    assert work_mfu_train.read(dict(ctx, peak=None), m) is None


# -- the rehearsal ----------------------------------------------------------

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_rehearsal_prints_the_contracts_last_line(capsys):
    out = rehearse()
    harness.emit(out["result"], out["checks"], out["notes"])
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(last) and list(last)[-1] == "compared"
    assert last["correct"] is True, last["compared"]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert set(last["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert set(last["compared"]) == COMPARED
    tail = captured.err.strip().splitlines()[-5:]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)
    notes = out["notes"]
    assert notes["tokens_per_step"] == 37 and notes["last_loss"] > 0
    assert len(notes["first_expert_tokens"]) == 3


def test_traced_rehearsal_reports_what_it_can_read():
    """On the CPU there is no device plane and no peak: the scope and
    whole-step readers return nothing and are left out (never 0); the
    counters' reader reads."""
    out = rehearse(seed=3_500_000_011, trace=True)
    got = out["result"]["metrics"]
    assert set(got) == {"moe_max_load_ratio.mellum"}
    assert got["moe_max_load_ratio.mellum"]["value"] >= 1.0
    assert out["result"]["correct"] is True


# -- the control and the planted faults come out as not correct ------------

def test_control_and_faults_fail_and_program_passes():
    from benchmark.drivers import train_window_lm
    from benchmark.reference import mellum2 as ref
    assert ref.FAULTS == ("nowindow", "window_off", "yarn", "interleave",
                          "sigmoid")
    cell = harness.load_cell(REHEARSAL, TINY, ROOT)
    session = train_window_lm.setup(cell, 11)
    got = train_window_lm.readings(session, None, faults=True)
    limits = cell["config"]["limits"]
    over = lambda numbers: [k for k, v in numbers.items()
                            if k in limits and v > limits[k]]
    assert not over(got["program"])
    assert over(got["control_fp8"])
    for fault in ref.FAULTS:
        assert over(got["fault_" + fault]), fault
    assert "change_norm_gap" in over(got["fault_unchanged"])
    assert "expert_change_norm_gap" in over(got["fault_unchanged"])
    assert "grad_norm_gap" in over(got["fault_row"])


@pytest.mark.parametrize("kind,caught_by", [
    ("unchanged", "change_norm_gap"), ("other_row", "grad_norm_gap")])
def test_broken_train_step_is_not_correct(kind, caught_by, monkeypatch):
    """Faults planted in the program's place: a step that hands back the
    parameters it was given, and one that reads its row backwards."""
    from deeplearning4j_tpu.models import hybrid_lm
    real = hybrid_lm.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def unchanged(params, opt, batch, it):
            import jax
            import jax.numpy as jnp
            keep = jax.tree_util.tree_map(jnp.copy, params)
            _, opt, aux = step(params, opt, batch, it)
            return keep, opt, aux

        def other_row(params, opt, batch, it):
            return step(params, opt, {"input_ids": batch["input_ids"][
                :, ::-1]}, it)

        return {"unchanged": unchanged, "other_row": other_row}[kind]

    monkeypatch.setattr(hybrid_lm, "make_train_step", make)
    out = rehearse()
    assert out["result"]["correct"] is False
    failed = [c["name"] for c in out["checks"] if c["value"] > c["limit"]]
    assert caught_by in failed
