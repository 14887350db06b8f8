"""Tests of what the hybrid-model cell adds to the benchmark, on the CPU,
outside tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hybrid.py -q

The rehearsal drives `drivers/train_hybrid_lm.py` at a tiny configuration
kept in this directory (`rehearsal_hybrid.json`; never in BENCHMARK.json)
with the harness's look for a chip skipped. Nothing here is a device
number.
"""
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, work_hybrid  # noqa: E402

BENCH = harness.load_benchmark(ROOT)
REHEARSAL = harness.load_json(HERE, "rehearsal_hybrid.json")
CELL = "nemotron-twotower-30b-a3b.train-t8192"
TINY = "nemotron-tiny.train-tiny-hybrid"
REAL = harness.load_json(ROOT, "benchmark/configs/nemotron-twotower-30b-a3b.json")
HYBRID_METRICS = [m for m in BENCH["per_layer"]
                  if m["name"].endswith(".hybrid")]


def rehearse(seed=5, seconds=1.0, trace=False):
    return harness.run_cell(TINY, seed, seconds, trace,
                            t_start=time.monotonic(), need_chip=False,
                            bench=REHEARSAL, root=ROOT)


# -- the cell and its files ---------------------------------------------------

def test_the_cell_resolves_to_files():
    cell = harness.load_cell(BENCH, CELL, ROOT)
    assert cell["chips"] == 1 and cell["traffic"]["run_ahead"] == 2
    cfg = cell["config"]
    assert cfg["kind"] == "train_hybrid_lm"
    driver = harness.driver_for(cfg["kind"])
    for fn in ("setup", "measure", "release", "check", "readings"):
        assert callable(getattr(driver, fn))
    assert cfg["train"] == {**cfg["train"], "batch": 1, "seq_len": 8192,
                            "remat": True, "batches": 16}
    e2e = [m["name"] for m in harness.cell_metrics(BENCH, CELL, "end_to_end")]
    assert sorted(e2e) == ["setup_s", "train_tokens_per_s"]
    per_layer = {m["name"] for m in
                 harness.cell_metrics(BENCH, CELL, "per_layer")}
    assert {"train_step_device_ms", "device_idle_pct.train"} <= per_layer
    assert "mfu.train" not in per_layer         # BERT's FLOPs, BERT's cell
    assert len(HYBRID_METRICS) == 11


def test_configuration_keeps_the_published_widths():
    """Every key of the catalog's config is in the file, equal unless
    `reduced` lists it; no width is reduced."""
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "nemotron-twotower-30b-a3b")
    assert entry["source"] == REAL["source"]
    assert sorted(entry["reduced"]) == sorted(REAL["reduced"])
    published = {"hidden_size": 2688, "moe_intermediate_size": 1856,
                 "moe_shared_expert_intermediate_size": 3712,
                 "mamba_num_heads": 64, "mamba_head_dim": 64,
                 "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
                 "chunk_size": 128, "num_attention_heads": 32,
                 "num_key_value_heads": 2, "head_dim": 128,
                 "num_experts_per_tok": 6, "routed_scaling_factor": 2.5}
    for k, v in published.items():
        assert REAL[k] == v and k not in REAL["reduced"], k
    assert REAL["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    # the cut: the first 13 blocks, 8 experts, an eighth of the vocabulary
    assert REAL["published"]["hybrid_override_pattern"].startswith(
        REAL["hybrid_override_pattern"])
    assert len(REAL["hybrid_override_pattern"]) == REAL["num_hidden_layers"]
    assert REAL["n_routed_experts"] == 8
    assert REAL["vocab_size"] * 8 == REAL["published"]["vocab_size"]
    for k in ("departures", "assumed", "deployment", "limits",
              "limits_from"):
        assert REAL[k], k


def test_every_new_metric_file_names_a_reader_that_exists():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in HYBRID_METRICS:
        spec = harness.load_json(ROOT, "benchmark/layer_metrics",
                                 m["name"] + ".json")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark/layer_metrics", spec["reader"] + ".py")), m
        for k in ("layer", "source", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert m["workloads"] == [CELL] and m["moves"] in e2e
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the rehearsal asks the tiny cell for the same metrics
    assert ([m["name"] for m in REHEARSAL["per_layer"]]
            == [m["name"] for m in HYBRID_METRICS])


# -- work -----------------------------------------------------------------

def test_work_hybrid_by_hand():
    E, T = 2688, 8192
    d_inner, conv_dim, H = 64 * 64, 64 * 64 + 2 * 8 * 128, 64
    scan = 2 * 128 * 128 * 8 + 2 * 128 * 64 * 64 + 4 * 64 * 128 * 64
    assert work_hybrid.scan_flops_per_token(REAL) == scan == 3_407_872
    mamba = 2 * (E * (d_inner + conv_dim + H) + d_inner * E) + scan
    assert work_hybrid.mamba_block_flops_per_token(REAL) == mamba
    assert work_hybrid.expert_visits_per_token(REAL) == 6 * 8 / 128
    expert = 2 * E * 128 + 4 * E * 3712 + 0.375 * 4 * E * 1856
    assert work_hybrid.expert_block_flops_per_token(REAL) == expert
    attn = 2 * (2 * E * 4096 + 2 * E * 256) + 4 * (T // 2) * 4096
    assert work_hybrid.attention_block_flops_per_token(REAL, T) == attn
    head = 2 * E * 16384
    forward = 6 * mamba + 5 * expert + 2 * attn + head
    assert work_hybrid.lm_forward_flops_per_token(REAL, T) == forward
    assert forward == pytest.approx(1041.2e6, rel=1e-3)     # ISSUE 27's
    assert work_hybrid.lm_train_flops_per_token(REAL, T) == 3 * forward
    # shares of the step, as the cell's reasons state them
    assert 6 * mamba / forward == pytest.approx(0.47, abs=0.01)
    assert 5 * expert / forward == pytest.approx(0.23, abs=0.01)
    assert 2 * attn / forward == pytest.approx(0.22, abs=0.01)


def test_work_hybrid_least_times_by_hand():
    peak = harness.peak_for("TPU v5 lite")
    T = 8192
    scan = work_hybrid.scan_step_min_seconds(REAL, T, peak)
    inputs = 2 * (4096 + 2 * 8 * 128) + 4 * 64
    assert scan["bytes"] == 6 * T * ((inputs + 8192) + (2 * inputs + 8192))
    assert scan["flops"] == 3 * 6 * T * 3_407_872
    assert scan["bound"] == "bytes"
    assert scan["seconds"] == pytest.approx(scan["bytes"] / 819e9)
    rows = 5 * 3072
    mm = work_hybrid.expert_mm_step_min_seconds(REAL, rows, peak)
    assert mm["flops"] == 3 * rows * 4 * 2688 * 1856
    assert mm["bytes"] == 6 * (5 * 2 * 8 * 2688 * 1856
                               + 2 * rows * (2688 + 1856))
    assert mm["seconds"] == pytest.approx(max(mm["flops"] / 197e12,
                                              mm["bytes"] / 819e9))


# -- readers --------------------------------------------------------------

def _ctx(window, cfg=REAL):
    return {"cell": {"config": cfg}, "window": window, "chips": 1,
            "peak": harness.peak_for("TPU v5 lite"),
            "end_to_end": {"train_tokens_per_s": 20000.0}}


def test_readers_by_hand_and_silent_without_the_programs_part():
    from benchmark.layer_metrics import (expert_load_ratio, mfu_train_hybrid,
                                         window_scope_roofline_pct,
                                         window_scope_time_ms)
    scopes = {"programs": {"jit_step": {
        "executions": 4, "op_s": 2.0, "scopes": {
            "ssm_scan": {"device_s": 0.4}, "moe_experts": {"device_s": 0.2}}}}}
    # the grouped product's rows are the traced steps', not the window's
    window = {"scopes": scopes, "attempted": 10, "counters": {
        "dl4j_moe_held_assignments_total": 10 * 5 * 4000.0,
        "expert_tokens/0/0": 30.0, "expert_tokens/0/1": 10.0},
        "traced": {"steps": 4, "counters": {
            "dl4j_moe_held_assignments_total": 4 * 5 * 3072.0}}}
    ctx = _ctx(window)
    p = {"program": "jit_step", "scopes": ["ssm_scan"]}
    assert window_scope_time_ms.read(ctx, p) == pytest.approx(100.0)
    least = work_hybrid.scan_step_min_seconds(REAL, 8192, ctx["peak"])
    assert window_scope_roofline_pct.read(ctx, dict(p, work="scan")) == \
        pytest.approx(100 * least["seconds"] / 0.1)
    mm = work_hybrid.expert_mm_step_min_seconds(REAL, 5 * 3072.0, ctx["peak"])
    assert window_scope_roofline_pct.read(
        ctx, {"program": "jit_step", "scopes": ["moe_experts"],
              "work": "expert_mm"}) == pytest.approx(100 * mm["seconds"] / 0.05)
    assert expert_load_ratio.read(ctx, {}) == pytest.approx(30 / 20)
    assert mfu_train_hybrid.read(ctx, {}) == pytest.approx(
        100 * 3 * 1041.231872e6 * 20000 / 197e12)
    # a program without the scopes or the counters: nothing, and no raise
    bare = _ctx({"attempted": 10})
    for reader, params in (
            (window_scope_time_ms, p),
            (window_scope_roofline_pct, dict(p, work="scan")),
            (window_scope_roofline_pct, dict(p, work="expert_mm")),
            (expert_load_ratio, {})):
        assert reader.read(bare, params) is None
    assert window_scope_time_ms.read(
        ctx, {"program": "jit_step", "scopes": ["attn_core"]}) is None


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
    assert set(last["compared"]) == {
        "loss_gap", "grad_norm_gap", "change_norm_gap",
        "expert_grad_norm_gap", "expert_change_norm_gap"}
    tail = captured.err.strip().splitlines()[-5:]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)
    notes = out["notes"]
    assert notes["tokens_per_step"] == 2 * 37 and notes["last_loss"] > 0
    assert len(notes["first_expert_tokens"]) == 2


def test_traced_rehearsal_reports_what_it_can_read():
    """On the CPU there is no device plane and no peak: the scope and
    whole-step readers return nothing and are left out (never 0); the
    counters' reader reads."""
    out = rehearse(seed=2_500_000_011, trace=True)
    got = out["result"]["metrics"]
    assert set(got) == {"moe_max_load_ratio.hybrid"}
    assert got["moe_max_load_ratio.hybrid"]["value"] >= 1.0
    assert out["result"]["correct"] is True


# -- the control and the planted faults come out as not correct ------------

def test_control_and_faults_fail_and_program_passes():
    from benchmark.drivers import train_hybrid_lm
    cell = harness.load_cell(REHEARSAL, TINY, ROOT)
    session = train_hybrid_lm.setup(cell, 11)
    got = train_hybrid_lm.readings(session, None, faults=True)
    limits = cell["config"]["limits"]
    over = lambda numbers: [k for k, v in numbers.items() if v > limits[k]]
    assert not over(got["program"])
    assert over(got["control_fp8"])
    # a missing expert shows on the experts' leaves, a fault in the scan
    # on the leaves every token reaches, missing tokens on both
    assert "expert_grad_norm_gap" in over(got["fault_expert"])
    assert "change_norm_gap" in over(got["fault_state"])
    assert {"grad_norm_gap", "expert_grad_norm_gap"} <= set(
        over(got["fault_half"]))


@pytest.mark.parametrize("kind,caught_by", [
    ("unchanged", "change_norm_gap"), ("half", "grad_norm_gap")])
def test_broken_train_step_is_not_correct(kind, caught_by, monkeypatch):
    """Faults planted in the program's place: a step that hands back the
    parameters it was given, and one that trains on half the sequence."""
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

        def half(params, opt, batch, it):
            ids = batch["input_ids"]
            return step(params, opt,
                        {"input_ids": ids[:, :ids.shape[1] // 2]}, it)

        return {"unchanged": unchanged, "half": half}[kind]

    monkeypatch.setattr(hybrid_lm, "make_train_step", make)
    out = rehearse()
    assert out["result"]["correct"] is False
    failed = [c["name"] for c in out["checks"] if c["value"] > c["limit"]]
    assert caught_by in failed
