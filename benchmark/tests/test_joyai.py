"""Tests of what the JoyAI-LLM-Flash cell adds to the benchmark, on the CPU,
outside tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_joyai.py -q

The rehearsal drives `drivers/train_latent_lm.py` at a tiny configuration
kept in this directory (`rehearsal_joyai.json`; never in BENCHMARK.json)
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

from benchmark import harness, work_joyai  # noqa: E402

BENCH = harness.load_benchmark(ROOT)
REHEARSAL = harness.load_json(HERE, "rehearsal_joyai.json")
CELL = "joyai-llm-flash.train-t8192-b2"
TINY = "joyai-tiny.train-tiny-latent"
REAL = harness.load_json(ROOT, "benchmark/configs/joyai-llm-flash.json")
JOYAI_METRICS = [m for m in BENCH["per_layer"]
                 if m["name"].endswith(".joyai")]
COMPARED = {"loss_gap", "mtp_loss_gap", "grad_norm_gap", "change_norm_gap",
            "expert_grad_norm_gap", "expert_change_norm_gap"}


def rehearse(seed=5, seconds=1.0, trace=False):
    return harness.run_cell(TINY, seed, seconds, trace,
                            t_start=time.monotonic(), need_chip=False,
                            bench=REHEARSAL, root=ROOT)


# -- the cell and its files ---------------------------------------------------

def test_the_cell_resolves_to_files():
    cell = harness.load_cell(BENCH, CELL, ROOT)
    assert cell["chips"] == 1 and cell["traffic"]["run_ahead"] == 2
    assert cell["traffic"]["trace_seconds"] == 4.0
    cfg = cell["config"]
    assert cfg["kind"] == "train_latent_lm"
    driver = harness.driver_for(cfg["kind"])
    for fn in ("setup", "measure", "release", "check", "readings"):
        assert callable(getattr(driver, fn))
    assert cfg["train"] == {**cfg["train"], "batch": 2, "seq_len": 8192,
                            "remat": True, "batches": 16,
                            "mtp_loss_weight": 0.3}
    e2e = [m["name"] for m in harness.cell_metrics(BENCH, CELL, "end_to_end")]
    assert sorted(e2e) == ["setup_s", "train_tokens_per_s"]
    per_layer = {m["name"] for m in
                 harness.cell_metrics(BENCH, CELL, "per_layer")}
    assert {"train_step_device_ms", "device_idle_pct.train"} <= per_layer
    assert not any(n.endswith((".hybrid", ".granite")) or n == "mfu.train"
                   for n in per_layer)
    assert len(JOYAI_METRICS) == 13
    # exactly one cell of this configuration
    assert [w["name"] for w in BENCH["workloads"]
            if w["config"] == "joyai-llm-flash"] == [CELL]


def test_configuration_keeps_the_published_keys():
    """Every key of the catalog's config is in the file, equal unless
    `reduced` lists it; no width is reduced."""
    entry = next(c for c in BENCH["configs"] if c["name"] == "joyai-llm-flash")
    assert entry["source"] == REAL["source"]
    assert sorted(entry["reduced"]) == sorted(REAL["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    published = {
        "hidden_size": 2048, "intermediate_size": 7168, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "qk_head_dim": 192, "v_head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 32, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "n_shared_experts": 1, "n_group": 1,
        "topk_group": 1, "routed_scaling_factor": 2.5, "head_dim": 64,
        "rope_theta": 32000000, "rope_interleave": True,
        "rope_scaling": None, "rms_norm_eps": 1e-06,
        "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "tie_word_embeddings": False, "max_position_embeddings": 131072}
    for k, v in published.items():
        assert REAL[k] == v and k not in REAL["reduced"], k
    pub = REAL["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (40, 256, 129280)
    # the cut and its floors: the dense layer and 5 expert layers, 16 of 256
    # experts (a 16-way share), an eighth of the vocabulary
    assert REAL["num_hidden_layers"] == 6 and REAL["n_routed_experts"] == 16
    assert REAL["vocab_size"] * 8 == pub["vocab_size"]
    assert REAL["deployment"]["expert_parallel"] * REAL["n_routed_experts"] \
        == pub["n_routed_experts"]
    assert REAL["num_hidden_layers"] - REAL["first_k_dense_replace"] >= 4
    for k in ("departures", "assumed", "deployment", "limits", "limits_from"):
        assert REAL[k], k
    for k in ("mtp_loss_weight", "mtp_hidden_state", "mtp_concatenation"):
        assert REAL["assumed"][k], k
    assert set(REAL["limits"]) == COMPARED


def test_every_new_metric_file_names_a_reader_that_exists():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in JOYAI_METRICS:
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
    assert ([m["name"] for m in REHEARSAL["per_layer"]
             if m["name"].endswith(".joyai")]
            == [m["name"] for m in JOYAI_METRICS])


def test_the_parameter_count_is_the_issues():
    """787.5M parameters here: latent attention 26.35M a layer, the dense
    layer 70.39M, an expert layer 107.09M, the MTP module 115.48M, the
    slice 66.19M."""
    from benchmark.reference import joyai_flash as ref
    d = ref.dims(REAL)
    size = lambda shapes: sum(
        __import__("math").prod(s) for s, _ in shapes.values())
    attn = size(ref.block_shapes(d, "L")) - 2048     # without the block's norm
    mlp = size(ref.block_shapes(d, "-")) - 2048
    experts = size(ref.block_shapes(d, "E")) - 2048
    assert attn == pytest.approx(26.35e6, rel=1e-3)
    assert attn + mlp == pytest.approx(70.39e6, rel=1e-3)
    assert attn + experts == pytest.approx(107.09e6, rel=1e-3)
    assert d["pattern"] == "L-" + "LE" * 5
    total = (attn + mlp + 5 * (attn + experts)
             + (attn + experts + 2 * 2048 * 2048)
             + 2 * 16160 * 2048)
    assert total == pytest.approx(787.5e6, rel=1e-3)


# -- work -----------------------------------------------------------------

def test_work_joyai_by_hand():
    E, T = 2048, 8192
    proj = 2 * (E * 1536 + 1536 * 32 * 192 + E * 576 + 512 * 32 * 256
                + 32 * 128 * E)
    assert work_joyai.latent_proj_flops_per_token(REAL) == proj == 52_690_944
    assert work_joyai.causal_pairs(T) == T * (T + 1) // 2 == 33_558_528
    core = 2 * 33_558_528 * 32 * (192 + 128)
    assert work_joyai.core_flops_per_row(REAL, T) == core
    assert work_joyai.expert_visits_per_token(REAL) == 8 * 16 / 256 == 0.5
    layer = 2 * E * 256 + 6 * E * 768 + 0.5 * 6 * E * 768
    assert work_joyai.expert_layer_flops_per_token(REAL) == layer
    forward = (7 * proj + 7 * core / T + 6 * E * 7168 + 6 * layer
               + 2 * 2 * E * 16160 + 2 * 2 * E * E)
    assert work_joyai.lm_forward_flops_per_token(REAL, T) == forward
    train = work_joyai.lm_train_flops_per_token(REAL, T)
    assert train == 3 * forward
    # ISSUE 35's: 3.85 GFLOP a token, 63 TFLOP a step of 16,384 tokens
    assert train == pytest.approx(3.85e9, rel=2e-3)
    step = train * 2 * T
    assert step == pytest.approx(63.1e12, rel=2e-3)
    # the shares of the step, as the cell's reasons state them
    assert 3 * 7 * core * 2 / step == pytest.approx(0.46, abs=0.01)
    assert 3 * 7 * proj * 2 * T / step == pytest.approx(0.29, abs=0.01)
    assert 3 * 2 * 2 * E * 16160 * 2 * T / step == pytest.approx(0.103,
                                                                 abs=0.005)
    assert 3 * 6 * 0.5 * 6 * E * 768 * 2 * T / step == pytest.approx(
        0.022, abs=0.003)
    # no module, no second head pass, merge or seventh layer
    bare = dict(REAL, num_nextn_predict_layers=0)
    assert work_joyai.lm_forward_flops_per_token(bare, T) == (
        6 * proj + 6 * core / T + 6 * E * 7168 + 5 * layer + 2 * E * 16160)


def test_work_joyai_least_times_by_hand():
    peak = harness.peak_for("TPU v5 lite")
    T = 8192
    core = work_joyai.core_step_min_seconds(REAL, 2, T, peak)
    assert core["flops"] == 3 * 7 * 2 * work_joyai.core_flops_per_row(REAL, T)
    q, k, v = 32 * 192, 32 * 128 + 64, 32 * 128
    assert core["bytes"] == 2 * 7 * 2 * T * (
        (q + k + 2 * v) + (q + k + 3 * v) + (q + k + v))
    assert core["bound"] == "flops"
    assert core["seconds"] == pytest.approx(core["flops"] / 197e12)
    rows = 6 * 8192
    mm = work_joyai.expert_mm_step_min_seconds(REAL, rows, peak)
    assert mm["flops"] == 3 * rows * 6 * 2048 * 768
    assert mm["bytes"] == 3 * (6 * 2 * 16 * 3 * 2048 * 768
                               + 2 * rows * (2048 + 3 * 768))
    assert mm["seconds"] == pytest.approx(max(mm["flops"] / 197e12,
                                              mm["bytes"] / 819e9))


# -- readers --------------------------------------------------------------

def _ctx(window, cfg=REAL):
    return {"cell": {"config": cfg}, "window": window, "chips": 1,
            "peak": harness.peak_for("TPU v5 lite"),
            "end_to_end": {"train_tokens_per_s": 17000.0}}


def test_readers_by_hand_and_silent_without_the_programs_part():
    from benchmark.layer_metrics import (work_mfu_train,
                                         work_scope_roofline_pct)
    scopes = {"programs": {"jit_step": {
        "executions": 4, "op_s": 4.0, "scopes": {
            "attn_core": {"device_s": 2.0},
            "moe_experts": {"device_s": 0.2}}}}}
    window = {"scopes": scopes, "attempted": 10, "counters": {},
              "traced": {"steps": 4, "counters": {
                  "dl4j_moe_held_assignments_total": 4 * 6 * 8000.0}}}
    ctx = _ctx(window)
    # the parameters are the metric files' own
    spec = lambda name: harness.load_json(
        ROOT, "benchmark", "layer_metrics", name + ".json")
    p = spec("mla_core_roofline_pct.joyai")["params"]
    q = spec("expert_mm_roofline_pct.joyai")["params"]
    m = spec("mfu.train.joyai")["params"]
    assert (p["scopes"], q["scopes"]) == (["attn_core"], ["moe_experts"])
    core = work_joyai.core_step_min_seconds(REAL, 2, 8192, ctx["peak"])
    assert work_scope_roofline_pct.read(ctx, p) == pytest.approx(
        100 * core["seconds"] / 0.5)
    mm = work_joyai.expert_mm_step_min_seconds(REAL, 6 * 8000.0, ctx["peak"])
    assert work_scope_roofline_pct.read(ctx, q) == pytest.approx(
        100 * mm["seconds"] / 0.05)
    assert work_mfu_train.read(ctx, m) == pytest.approx(
        100 * work_joyai.lm_train_flops_per_token(REAL, 8192) * 17000
        / 197e12)
    # a share of a roofline cannot pass 100: at the chip's peak the core's
    # own least time reads exactly 100
    at_peak = {"scopes": {"programs": {"jit_step": {
        "executions": 1, "op_s": 1.0, "scopes": {
            "attn_core": {"device_s": core["seconds"]}}}}}}
    assert work_scope_roofline_pct.read(_ctx(at_peak), p) == \
        pytest.approx(100.0)
    # a program without the scopes or the counters (the parent): nothing,
    # and no raise
    bare = _ctx({"attempted": 10})
    assert work_scope_roofline_pct.read(bare, p) is None
    assert work_scope_roofline_pct.read(bare, q) is None
    no_rows = _ctx({"scopes": scopes, "attempted": 10})
    assert work_scope_roofline_pct.read(no_rows, q) is None
    off_chip = dict(ctx, peak=None)
    assert work_mfu_train.read(off_chip, m) is None
    assert work_scope_roofline_pct.read(off_chip, p) is None


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
    tail = captured.err.strip().splitlines()[-6:]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)
    notes = out["notes"]
    assert notes["tokens_per_step"] == 2 * 37 and notes["last_loss"] > 0
    # two expert layers of the main stack and the module's
    assert len(notes["first_expert_tokens"]) == 3
    assert notes["first_mtp_loss"] > 0
    # every step the program observed counted its B x (T - 2) positions
    assert notes["mtp_positions_total"] % (2 * 35) == 0
    assert notes["mtp_positions_total"] >= (notes["steps"] + 3) * 2 * 35


def test_traced_rehearsal_reports_what_it_can_read():
    """On the CPU there is no device plane and no peak: the scope and
    whole-step readers return nothing and are left out (never 0); the
    counters' reader reads."""
    out = rehearse(seed=3_500_000_011, trace=True)
    got = out["result"]["metrics"]
    assert set(got) == {"moe_max_load_ratio.joyai"}
    assert got["moe_max_load_ratio.joyai"]["value"] >= 1.0
    assert out["result"]["correct"] is True


# -- the control and the planted faults come out as not correct ------------

def test_control_and_faults_fail_and_program_passes():
    from benchmark.drivers import train_latent_lm
    from benchmark.reference import joyai_flash as ref
    assert ref.FAULTS == ("rope", "vwidth", "mtp_shift", "shared")
    cell = harness.load_cell(REHEARSAL, TINY, ROOT)
    session = train_latent_lm.setup(cell, 11)
    got = train_latent_lm.readings(session, None, faults=True)
    limits = cell["config"]["limits"]
    over = lambda numbers: [k for k, v in numbers.items()
                            if k in limits and v > limits[k]]
    assert not over(got["program"])
    assert over(got["control_fp8"])
    for fault in ref.FAULTS:
        assert over(got["fault_" + fault]), fault
    # the two faults in the program's place, each by the number for it
    assert "change_norm_gap" in over(got["fault_unchanged"])
    assert "expert_change_norm_gap" in over(got["fault_unchanged"])
    assert "grad_norm_gap" in over(got["fault_half"])
    # a module that predicts the wrong position fails the number that is
    # there for it, by far; its mean loss alone hardly moves
    assert "mtp_loss_gap" in over(got["fault_mtp_shift"])
    # (at this width a logit's spread is 0.1; at the published width, 0.9)
    shift = got["fault_mtp_shift"]
    assert shift["mtp_loss_gap"] > 10 * limits["mtp_loss_gap"]
    assert shift["mtp_mean_loss_gap"] < shift["mtp_loss_gap"] / 3


@pytest.mark.parametrize("kind,caught_by", [
    ("unchanged", "change_norm_gap"), ("half", "grad_norm_gap")])
def test_broken_train_step_is_not_correct(kind, caught_by, monkeypatch):
    """Faults planted in the program's place: a step that hands back the
    parameters it was given, and one whose second row is its first."""
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
            import jax.numpy as jnp
            ids = batch["input_ids"]
            return step(params, opt, {"input_ids": jnp.concatenate(
                [ids[:1], ids[:1]])}, it)

        return {"unchanged": unchanged, "half": half}[kind]

    monkeypatch.setattr(hybrid_lm, "make_train_step", make)
    out = rehearse()
    assert out["result"]["correct"] is False
    failed = [c["name"] for c in out["checks"] if c["value"] > c["limit"]]
    assert caught_by in failed
