"""Tests of what the packed granite cell adds to the benchmark, on the CPU,
outside tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_granite.py -q

The rehearsal drives `drivers/train_packed_lm.py` at a tiny configuration
kept in this directory (`rehearsal_granite.json`; never in BENCHMARK.json)
with the harness's look for a chip skipped. Nothing here is a device
number.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, work_granite  # noqa: E402

BENCH = harness.load_benchmark(ROOT)
REHEARSAL = harness.load_json(HERE, "rehearsal_granite.json")
CELL = "granite-4.0-h-micro.train-packed-t16384"
TINY = "granite-tiny.train-tiny-packed"
REAL = harness.load_json(ROOT, "benchmark/configs/granite-4.0-h-micro.json")
TRAFFIC = harness.load_json(ROOT, "benchmark/traffic/train-packed-t16384.json")
GRANITE_METRICS = [m for m in BENCH["per_layer"]
                   if m["name"].endswith(".granite")]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def rehearse(seed=5, seconds=1.0, trace=False):
    return harness.run_cell(TINY, seed, seconds, trace,
                            t_start=time.monotonic(), need_chip=False,
                            bench=REHEARSAL, root=ROOT)


# -- the cell and its files ---------------------------------------------------

def test_the_cell_resolves_to_files():
    cell = harness.load_cell(BENCH, CELL, ROOT)
    assert cell["chips"] == 1 and cell["traffic"]["run_ahead"] == 2
    cfg = cell["config"]
    assert cfg["kind"] == "train_packed_lm"
    driver = harness.driver_for(cfg["kind"])
    for fn in ("setup", "measure", "release", "check", "readings"):
        assert callable(getattr(driver, fn))
    assert cfg["train"] == {**cfg["train"], "batch": 1, "seq_len": 16384,
                            "remat": True, "batches": 16}
    e2e = [m["name"] for m in harness.cell_metrics(BENCH, CELL, "end_to_end")]
    assert sorted(e2e) == ["setup_s", "train_tokens_per_s"]
    per_layer = {m["name"] for m in
                 harness.cell_metrics(BENCH, CELL, "per_layer")}
    assert {"train_step_device_ms", "device_idle_pct.train"} <= per_layer
    assert not [n for n in per_layer if n.endswith(".hybrid")
                or n == "mfu.train"]
    assert sorted(m["name"] for m in GRANITE_METRICS) == sorted([
        "mfu.train.granite", "ssm_scan_device_ms.granite",
        "ssm_proj_device_ms.granite", "mlp_device_ms.granite",
        "attn_core_device_ms.granite", "optimizer_device_ms.granite",
        "ssm_scan_roofline_pct.granite", "attn_core_roofline_pct.granite",
        "docs_per_row.granite"])
    # the other cells report none of them
    for other in ("bert-large-mlm.train-t512",
                  "nemotron-twotower-30b-a3b.train-t8192"):
        assert not [m for m in harness.cell_metrics(BENCH, other, "per_layer")
                    if m["name"].endswith(".granite")]


def test_the_traffic_file_holds_the_issues_packing():
    assert TRAFFIC["packing"] == {**TRAFFIC["packing"],
                                  "lengths": "log-normal", "median": 1024,
                                  "sigma": 1.5, "min": 32, "max": 16384}
    assert TRAFFIC["run_ahead"] == 2


def test_configuration_keeps_the_published_widths():
    """Every key of the catalog's config is in the file, equal unless
    `reduced` lists it; no width is reduced."""
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    assert entry["source"] == REAL["source"]
    assert entry["reduced"] == REAL["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(line) for line in f
                       if '"granite-4.0-h-micro"' in line)
        assert row["source_url"] == REAL["source"]
        for k, v in row["config"].items():
            if k in REAL["reduced"]:
                assert REAL["published"][k] == v, k
            else:
                assert REAL[k] == v, k
    published = {"hidden_size": 2048, "intermediate_size": 8192,
                 "shared_intermediate_size": 8192, "mamba_n_heads": 64,
                 "mamba_d_head": 64, "mamba_d_state": 128,
                 "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
                 "mamba_chunk_size": 256, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "embedding_multiplier": 12,
                 "residual_multiplier": 0.22,
                 "attention_multiplier": 0.015625, "logits_scaling": 8,
                 "tie_word_embeddings": True}
    for k, v in published.items():
        assert REAL[k] == v and k not in REAL["reduced"], k
    # the cut: the first period of ten layers, an eighth of the vocabulary
    assert REAL["layer_types"] == REAL["published"]["layer_types"][:10]
    assert REAL["layer_types"].count("mamba") == 9
    assert REAL["published"]["layer_types"].count("attention") == 4
    assert len(REAL["layer_types"]) == REAL["num_hidden_layers"] == 10
    assert REAL["vocab_size"] * 8 == REAL["published"]["vocab_size"] == 100352
    for k in ("assumed", "deployment", "limits", "limits_from"):
        assert REAL[k], k
    assert "departures" in REAL


def test_every_new_metric_file_names_a_reader_that_exists():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in GRANITE_METRICS:
        spec = harness.load_json(ROOT, "benchmark/layer_metrics",
                                 m["name"] + ".json")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark/layer_metrics", spec["reader"] + ".py")), m
        for k in ("layer", "source", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert m["workloads"] == [CELL] and m["moves"] in e2e
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert ([m["name"] for m in REHEARSAL["per_layer"]]
            == [m["name"] for m in GRANITE_METRICS])


def test_parameter_count_is_the_issues():
    import jax
    from benchmark.reference import granite_hybrid as ref
    flat = jax.eval_shape(
        lambda: ref.make_flat_params(jax.random.key(0), REAL))
    n = sum(int(np.prod(v.shape)) for v in flat.values())
    mamba = 2048 * 8512 + 4096 * 2048 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 2048
    mlp = 3 * 2048 * 8192 + 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2048
    assert n == 9 * mamba + attn + 10 * mlp + 12544 * 2048 + 2048
    assert n == pytest.approx(772.2e6, rel=1e-3)


# -- work -----------------------------------------------------------------

FULL_ROW = {"rows": 1, "documents": 1, "tokens_per_row": 16384,
            "attended_pairs": 16384 * 16385 // 2}
SIX_DOCS = {"rows": 2, "documents": 12, "tokens_per_row": 16384,
            "attended_pairs": 2 * sum(n * (n + 1) // 2 for n in
                                      (8192, 4096, 2048, 1024, 512, 512))}


def test_work_granite_by_hand():
    E, F = 2048, 8192
    scan = 2 * 256 * 128 * 1 + 2 * 256 * 64 * 64 + 4 * 64 * 128 * 64
    assert work_granite.scan_flops_per_token(REAL) == scan == 4_259_840
    mamba = 2 * (E * 8512 + 4096 * E) + scan
    assert work_granite.mamba_mixer_flops_per_token(REAL) == mamba
    assert work_granite.mlp_flops_per_token(REAL) == 6 * E * F
    assert work_granite.pairs_per_token(FULL_ROW) == 16385 / 2
    pairs = work_granite.pairs_per_token(SIX_DOCS)
    assert pairs == pytest.approx((8192 * 8193 + 4096 * 4097 + 2048 * 2049
                                   + 1024 * 1025 + 2 * 512 * 513) / 2 / 16384)
    core = 4 * pairs * 2048
    assert work_granite.attention_core_flops_per_token(REAL, SIX_DOCS) == core
    attn = 2 * (2 * E * 2048 + 2 * E * 512) + core
    head = 2 * E * 12544
    forward = 9 * mamba + attn + 10 * 6 * E * F + head
    assert work_granite.lm_forward_flops_per_token(REAL, SIX_DOCS) == forward
    assert work_granite.lm_train_flops_per_token(REAL, SIX_DOCS) == 3 * forward
    # about 4.8 GFLOP a token at six such documents a row, 5.0 for a row
    # that is one document: attention over same-document pairs only
    assert 3 * forward == pytest.approx(4.82e9, rel=0.01)
    whole = work_granite.lm_train_flops_per_token(REAL, FULL_ROW)
    assert whole == pytest.approx(4.98e9, rel=0.01) and whole > 3 * forward


def test_work_granite_least_times_by_hand():
    peak = harness.peak_for("TPU v5 lite")
    T = 16384
    scan = work_granite.scan_step_min_seconds(REAL, T, peak)
    inputs = 2 * (4096 + 2 * 128) + 4 * 64
    assert scan["bytes"] == 9 * T * ((inputs + 8192) + (2 * inputs + 8192))
    assert scan["flops"] == 3 * 9 * T * 4_259_840
    assert scan["bound"] == "flops"        # one group: few bytes a token
    core = work_granite.attn_core_step_min_seconds(REAL, T, SIX_DOCS, peak)
    assert core["flops"] == pytest.approx(
        3 * T * 4 * work_granite.pairs_per_token(SIX_DOCS) * 2048)
    fwd = 2 * (2 * 2048 + 2 * 512)
    assert core["bytes"] == T * (2 * fwd + 2 * 2048 + 2 * (2048 + 1024))
    assert core["bound"] == "flops"
    assert core["seconds"] == pytest.approx(core["flops"] / 197e12)
    # a row of one document asks for more
    assert work_granite.attn_core_step_min_seconds(
        REAL, T, FULL_ROW, peak)["flops"] > core["flops"]


# -- readers --------------------------------------------------------------

def _ctx(window, cfg=REAL):
    return {"cell": {"config": cfg}, "window": window, "chips": 1,
            "peak": harness.peak_for("TPU v5 lite"),
            "end_to_end": {"train_tokens_per_s": 18000.0}}


def test_readers_by_hand_and_silent_without_the_programs_part():
    from benchmark.layer_metrics import (counter_ratio,
                                         granite_scope_roofline_pct,
                                         mfu_train_granite,
                                         window_scope_time_ms)
    scopes = {"programs": {"jit_step": {
        "executions": 4, "op_s": 4.0, "scopes": {
            "ssm_scan": {"device_s": 0.8}, "attn_core": {"device_s": 0.2}}}}}
    window = {"scopes": scopes, "attempted": 30, "packing": SIX_DOCS,
              "counters": {"dl4j_packed_documents_total": 12.0,
                           "dl4j_packed_rows_total": 2.0},
              "traced": {"steps": 4, "packing": FULL_ROW}}
    ctx = _ctx(window)
    p = {"program": "jit_step", "scopes": ["ssm_scan"]}
    assert window_scope_time_ms.read(ctx, p) == pytest.approx(200.0)
    least = work_granite.scan_step_min_seconds(REAL, 16384, ctx["peak"])
    assert granite_scope_roofline_pct.read(ctx, dict(p, work="scan")) == \
        pytest.approx(100 * least["seconds"] / 0.2)
    # the attention core's pairs are the traced steps', not the window's
    core = work_granite.attn_core_step_min_seconds(REAL, 16384, FULL_ROW,
                                                   ctx["peak"])
    assert granite_scope_roofline_pct.read(
        ctx, {"program": "jit_step", "scopes": ["attn_core"],
              "work": "attn_core"}) == pytest.approx(
                  100 * core["seconds"] / 0.05)
    assert mfu_train_granite.read(ctx, {}) == pytest.approx(
        100 * work_granite.lm_train_flops_per_token(REAL, SIX_DOCS) * 18000
        / 197e12)
    docs = {"num": "dl4j_packed_documents_total",
            "den": "dl4j_packed_rows_total"}
    assert counter_ratio.read(ctx, docs) == 6.0
    # a program without the scopes or the counters: nothing, and no raise
    bare = _ctx({"attempted": 10})
    for reader, params in (
            (window_scope_time_ms, p),
            (granite_scope_roofline_pct, dict(p, work="scan")),
            (granite_scope_roofline_pct, dict(p, work="attn_core")),
            (mfu_train_granite, {}), (counter_ratio, docs)):
        assert reader.read(bare, params) is None


def test_boundary_positions_and_gap_by_hand():
    from benchmark.drivers import train_packed_lm as drv
    seg = np.array([0, 0, 0, 1, 1, 1, 1, 1, 2, 3, 3, 3])
    near = drv.boundary_positions(seg, 3)
    # the row's own first document has no boundary before it; a document
    # shorter than the reach ends the run
    assert np.flatnonzero(near).tolist() == [3, 4, 5, 8, 9, 10, 11]
    assert np.flatnonzero(drv.boundary_positions(seg, 1)).tolist() == [3, 8, 9]
    want = np.full((1, 12), 2.0)
    want[0, [2, 7, 8, 11]] = 0.0           # positions that predict nothing
    got = want.copy()
    got[0, 4] += 0.3
    got[0, 0] += 5.0                        # far from any boundary
    gap = drv.boundary_loss_gap(got, want, seg, 3)
    assert gap == pytest.approx(np.sqrt(0.09 / 5) / 2.0)
    assert drv.boundary_loss_gap(got, want, np.zeros(12, int), 3) is None
    assert drv.reaches({"loss_gap": 1, "boundary_loss_gap_64": 1,
                        "boundary_loss_gap_3": 1}) == [3, 64]


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
    assert set(last["compared"]) == {"loss_gap", "grad_norm_gap",
                                     "change_norm_gap", "boundary_loss_gap_3"}
    tail = captured.err.strip().splitlines()[-4:]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)
    notes = out["notes"]
    assert notes["tokens_per_step"] == 96 and notes["last_loss"] > 0
    assert notes["compared_row_documents"] >= 2
    assert notes["docs_per_row"] >= 1


def test_traced_rehearsal_reports_what_it_can_read():
    """On the CPU there is no device plane and no peak: the scope and
    whole-step readers return nothing and are left out (never 0); the
    counters' reader reads."""
    out = rehearse(seed=2_500_000_011, trace=True)
    got = out["result"]["metrics"]
    assert set(got) == {"docs_per_row.granite"}
    assert got["docs_per_row.granite"]["value"] >= 1.0
    assert out["result"]["correct"] is True


def test_the_window_counts_its_own_rows():
    from benchmark.drivers import train_packed_lm as drv
    cell = harness.load_cell(REHEARSAL, TINY, ROOT)
    session = drv.setup(cell, 3)
    window = drv.measure(session, 0.3, None)
    lengths, steps = session["lengths"], window["attempted"]
    rows = [lengths[i % len(lengths)] for i in range(3, 3 + steps)]
    assert window["packing"] == {
        "rows": steps, "documents": sum(len(r) for r in rows),
        "attended_pairs": sum(n * (n + 1) // 2 for r in rows for n in r),
        "tokens_per_row": 96}


# -- the control and the planted faults come out as not correct ------------

def test_control_and_faults_fail_and_program_passes():
    from benchmark.drivers import train_packed_lm as drv
    cell = harness.load_cell(REHEARSAL, TINY, ROOT)
    session = drv.setup(cell, 11)
    got = drv.readings(session, None, faults=True)
    limits = cell["config"]["limits"]
    over = lambda numbers: [k for k in limits if numbers[k] > limits[k]]
    assert not over(got["program"])
    assert over(got["control_fp8"])
    assert "boundary_loss_gap_3" in over(got["fault_scan"])
    assert "boundary_loss_gap_3" in over(got["fault_conv"])


@pytest.mark.parametrize("kind,caught_by", [
    ("unchanged", "change_norm_gap"), ("one_document", "boundary_loss_gap_3")])
def test_broken_train_step_is_not_correct(kind, caught_by, monkeypatch):
    """Faults planted in the program's place: a step that hands back the
    parameters it was given, and one that is not told the documents."""
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

        def one_document(params, opt, batch, it):
            import jax.numpy as jnp
            new, opt, aux = step(params, opt, {"input_ids":
                                               batch["input_ids"]}, it)
            return new, opt, dict(aux, token_loss=jnp.zeros(
                batch["input_ids"].shape))

        return {"unchanged": unchanged, "one_document": one_document}[kind]

    monkeypatch.setattr(hybrid_lm, "make_train_step", make)
    out = rehearse()
    assert out["result"]["correct"] is False
    failed = [c["name"] for c in out["checks"] if c["value"] > c["limit"]]
    assert caught_by in failed
