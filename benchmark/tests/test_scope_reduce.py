"""Tests of the scope reduction (PR 25), on the CPU, outside tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

`scoped_tpu.xplane.pb` was recorded on a v5e chip by
`record_scoped_trace.py`; `tiny_tpu.xplane.pb` is PR 24's, which has no
scope in it. Nothing here is a device number.
"""
import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, scope_reduce, trace_reduce  # noqa: E402
from benchmark.layer_metrics import (host_span_ms_per,  # noqa: E402
                                     scope_share_pct, scope_time_ms)

BENCH = harness.load_benchmark(ROOT)
TINY = os.path.join(HERE, "tiny_tpu.xplane.pb")
SCOPED = os.path.join(HERE, "scoped_tpu.xplane.pb")
NEW_FILES = ("attn_core_device_ms.train", "mlp_device_ms.train",
             "head_loss_device_ms.train", "optimizer_device_ms.train",
             "unscoped_device_pct.train", "kv_write_device_ms.decode",
             "kv_read_device_ms.decode", "attn_core_device_ms.decode",
             "sched_host_ms_per_step.serve")


# -- leaf scopes and self times, by hand ----------------------------------

@pytest.mark.parametrize("tf_op, want", [
    ("jit(step)/jvp(dl4j.attn)/dl4j.attn_core/sub", ("attn_core", False)),
    ("jit(step)/transpose(jvp(dl4j.attn))/dl4j.attn_core/mul:",
     ("attn_core", True)),
    ("jit(step)/transpose(jvp(dl4j.attn))/bqhd,hde->bqe/transpose",
     ("attn", True)),
    ("jit(step)/jvp(checkpoint(dl4j.head))/dl4j.ln/rsqrt", ("ln", False)),
    ("jit(step)/dl4j.optimizer/sub", ("optimizer", False)),
    ("jit(decode_fn)/dl4j.attn/dl4j.kv_write/scatter", ("kv_write", False)),
    ("jit(step)/reduce_sum", ("unscoped", False)),
    ("jit(step)/transpose(jvp(mul))", ("unscoped", True)),
    ("", ("compiler", False)),
    (None, ("compiler", False)),
])
def test_leaf_scope(tf_op, want):
    assert scope_reduce.leaf_scope(tf_op) == want


def test_self_times_take_nested_operations_out():
    # a `while` (id 1) from 0 to 10 holds two body operations; id 4 follows
    ops = [(1, 0.0, 10.0), (2, 1.0, 3.0), (3, 5.0, 2.0), (4, 10.0, 1.0)]
    assert scope_reduce.self_times(ops) == [(1, 5.0), (2, 3.0), (3, 2.0),
                                            (4, 1.0)]
    # order of the list does not matter, and the sum is the union
    back = scope_reduce.self_times(ops[::-1])
    assert sorted(back) == [(1, 5.0), (2, 3.0), (3, 2.0), (4, 1.0)]
    assert sum(t for _, t in back) == 11.0


def by_hand():
    meta = {
        1: {"tf_op": "jit(f)/jvp(dl4j.a)/dot", "program_id": 7,
            "flops": 100, "bytes": 10},
        2: {"tf_op": "jit(f)/transpose(jvp(dl4j.a))/dl4j.b/mul",
            "program_id": 7, "flops": 40, "bytes": 8},
        3: {"tf_op": None, "program_id": 7, "flops": 0, "bytes": 4,
            "category": "copy-done"},
        4: {"tf_op": "jit(g)/dl4j.a/add", "program_id": 9, "flops": 1,
            "bytes": 1},
        5: {"tf_op": "jit(f)/mul", "program_id": 7, "flops": 2, "bytes": 2,
            "category": "loop fusion"},
    }
    ops = [(1, 0.0, 1.0), (2, 1.0, 0.5), (3, 1.5, 0.125), (5, 1.625, 0.125),
           (1, 3.0, 1.0), (2, 4.0, 0.5), (3, 4.5, 0.125), (5, 4.625, 0.125),
           (4, 6.0, 2.0)]           # f twice, then g
    modules = [("jit_f(7)", 0.0, 1.75), ("jit_f(7)", 3.0, 1.75),
               ("jit_g(9)", 6.0, 2.0)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules, "meta": meta}}


def test_reduce_scopes_by_hand():
    r = scope_reduce.reduce_scopes(by_hand())
    f, g = r["programs"]["jit_f"], r["programs"]["jit_g"]
    assert f["executions"] == 2 and g["executions"] == 1
    assert f["scopes"]["a"] == {"device_s": 2.0, "forward_s": 2.0,
                                "backward_s": 0.0, "flops": 200,
                                "bytes": 20, "events": 2}
    assert f["scopes"]["b"]["backward_s"] == 1.0
    assert f["scopes"]["b"]["forward_s"] == 0.0
    # the program's own operation outside any scope, and the compiler's
    assert f["scopes"]["unscoped"]["device_s"] == 0.25
    assert f["scopes"]["compiler"]["device_s"] == 0.25
    assert f["uncovered"] == {"copy-done": 0.25, "loop fusion": 0.25}
    assert g["scopes"] == {"a": {"device_s": 2.0, "forward_s": 2.0,
                                 "backward_s": 0.0, "flops": 1, "bytes": 1,
                                 "events": 1}}
    assert g["uncovered"] == {} and g["uncovered_ops"] == []
    assert sorted(f["uncovered_ops"]) == [["", 0.5]]   # no HLO text by hand
    # the partition: Σ rows = the program's operation time
    for p in (f, g):
        assert sum(s["device_s"] for s in p["scopes"].values()) == \
            pytest.approx(p["op_s"])
    assert f["op_s"] == 3.5
    # a window keeps the operations that start in it and the executions
    # whose middle lies in it
    w = scope_reduce.reduce_scopes(by_hand(), window=(2.5, 5.5))
    assert w["programs"]["jit_f"]["executions"] == 1
    assert w["programs"]["jit_f"]["op_s"] == 1.75
    assert "jit_g" not in w["programs"]


def test_no_operations_is_an_empty_table():
    assert scope_reduce.reduce_scopes({}) == {"programs": {}, "chips": 0}


# -- recorded traces ------------------------------------------------------

def test_wire_reader_agrees_with_profile_data_on_pr24s_trace():
    raw = scope_reduce.read_xplane_scoped(TINY)
    assert list(raw) == ["/device:TPU:0"]
    dev = raw["/device:TPU:0"]
    assert len(dev["ops"]) == 12 and len(dev["modules"]) == 4
    fused = [m for m in dev["meta"].values()
             if m["name"].startswith("%convolution_tanh_fusion")]
    assert fused[0]["tf_op"] == "jit(tiny_step)/dot_general:"
    assert fused[0]["flops"] == 2 * 256 ** 3 + 256 * 256 * 2
    assert fused[0]["bytes"] == 3 * 256 * 256 * 2
    r = scope_reduce.reduce_scopes(raw)
    prog = r["programs"]["jit_tiny_step"]
    # a trace with no scope in it: the program's fusion is `unscoped`,
    # the prefetch copies around it are the compiler's
    assert sorted(prog["scopes"]) == ["compiler", "unscoped"]
    assert prog["scopes"]["unscoped"]["events"] == 4
    assert set(prog["uncovered"]) == {"copy-start", "copy-done",
                                      "convolution fusion"}
    assert prog["uncovered_ops"][0][0].startswith(
        "%convolution_tanh_fusion = bf16[256,256]")
    old = trace_reduce.read_xplane(TINY)
    want = trace_reduce.reduce_events(old["devices"], old["host"])
    assert prog["executions"] == len(want["programs"]["jit_tiny_step"])
    # ProfileData rounds to nanoseconds, the file holds picoseconds
    assert prog["op_s"] == pytest.approx(want["busy_s"], rel=0.01)


@pytest.fixture(scope="module")
def scoped():
    return scope_reduce.reduce_scopes(
        scope_reduce.read_xplane_scoped(SCOPED))


def test_recorded_scoped_trace_names_its_time(scoped):
    """Four executions of record_scoped_trace.py's step: `dl4j.inner`
    (the softmax) nested in `dl4j.outer` (two matmuls and a tanh); the
    loss's mean, the update and a sort outside any scope."""
    prog = scoped["programs"]["jit_scoped_step"]
    assert prog["executions"] == 4
    rows = prog["scopes"]
    assert set(rows) == {"outer", "inner", "unscoped", "compiler"}
    # the sort fuses with nothing and has a path but no scope; the update
    # `w - 0.5 * grad` has none either, but XLA fused it into the matmul
    # that makes the gradient (one operation, one name: `outer`); the
    # prefetch copies have no path at all
    assert prog["uncovered_ops"][0][0].startswith("%sort")
    assert prog["uncovered"]["sort"] == pytest.approx(
        rows["unscoped"]["device_s"], rel=0.01)
    assert prog["uncovered"]["copy-done"] == pytest.approx(
        rows["compiler"]["device_s"], rel=0.01)
    assert not any("subtract" in text for text, _ in prog["uncovered_ops"])
    for name in ("outer", "inner"):
        assert rows[name]["forward_s"] > 0 and rows[name]["backward_s"] > 0
        assert rows[name]["device_s"] == pytest.approx(
            rows[name]["forward_s"] + rows[name]["backward_s"])
    # the matmuls are the outer scope's own: 2 forward + 3 backward
    # 512-cubed products, each execution
    assert rows["outer"]["flops"] >= 4 * 5 * 2 * 512 ** 3
    assert rows["inner"]["flops"] < rows["outer"]["flops"] / 10
    assert rows["compiler"]["backward_s"] == 0.0


def test_recorded_scoped_trace_keeps_the_partition(scoped):
    prog = scoped["programs"]["jit_scoped_step"]
    assert sum(r["device_s"] for r in prog["scopes"].values()) == \
        pytest.approx(prog["op_s"])
    old = trace_reduce.read_xplane(SCOPED)
    want = trace_reduce.reduce_events(old["devices"], old["host"])
    assert prog["op_s"] == pytest.approx(want["busy_s"], rel=0.01)
    assert prog["executions"] == len(want["programs"]["jit_scoped_step"])


# -- the readers ----------------------------------------------------------

def ctx_by_hand():
    return {"trace": {"scopes": scope_reduce.reduce_scopes(by_hand()),
                      "host_spans": {"generation/step": [4, 0.4],
                                     "generation/admit": [4, 0.1],
                                     "generation/prefill_dispatch": [1, 0.06],
                                     "generation/emit": [4, 0.02]}}}


def test_scope_time_ms_by_hand():
    ctx = ctx_by_hand()
    read = lambda prog, scopes: scope_time_ms.read(
        ctx, {"program": prog, "scopes": scopes})
    assert read("jit_f", ["a"]) == pytest.approx(1000.0)       # 2 s / 2
    assert read("jit_f", ["a", "b"]) == pytest.approx(1500.0)
    assert read("jit_f", ["unscoped", "compiler"]) == pytest.approx(250.0)
    assert read("jit_g", ["a"]) == pytest.approx(2000.0)
    # absent, never 0: no such scope, no such program, no table at all
    assert read("jit_f", ["kv_write"]) is None
    assert read("jit_h", ["a"]) is None
    assert scope_time_ms.read({"trace": {}}, {"program": "jit_f",
                                              "scopes": ["a"]}) is None


def test_scope_share_pct_by_hand():
    ctx = ctx_by_hand()
    p = {"program": "jit_f", "scopes": ["unscoped"]}
    assert scope_share_pct.read(ctx, p) == pytest.approx(100 * 0.25 / 3.5)
    # a program that ran with nothing unscoped reads 0, which is a reading
    assert scope_share_pct.read(ctx, dict(p, program="jit_g")) == 0.0
    assert scope_share_pct.read(ctx, dict(p, program="jit_h")) is None
    assert scope_share_pct.read({"trace": {}}, p) is None


def test_host_span_ms_per_by_hand():
    ctx = ctx_by_hand()
    p = {"spans": ["generation/admit", "generation/emit",
                   "generation/reconcile"],
         "minus": ["generation/prefill_dispatch"], "per": "generation/step"}
    assert host_span_ms_per.read(ctx, p) == pytest.approx(
        1e3 * (0.1 + 0.02 - 0.06) / 4)
    assert host_span_ms_per.read(ctx, dict(p, per="generation/idle")) is None
    assert host_span_ms_per.read({"trace": {}}, p) is None


# -- the metric files -----------------------------------------------------

@pytest.mark.parametrize("name", NEW_FILES)
def test_new_metric_file_resolves(name):
    spec = harness.load_json(ROOT, "benchmark/layer_metrics", name + ".json")
    assert spec["name"] == name
    assert os.path.exists(os.path.join(
        ROOT, "benchmark/layer_metrics", spec["reader"] + ".py"))
    assert spec["source"] in ("device_trace", "program_span")
    e2e = {m["name"] for m in BENCH["end_to_end"]} | {
        "itl_p95_ms", "serve_tokens_per_s", "ttft_p95_ms"}
    assert spec["moves"] in e2e
    layers = {m["layer"] for m in BENCH["per_layer"]} | {
        "kernels", "decode scheduler"}
    assert spec["layer"] in layers
    # with the reduced trace as `harness.run_cell` makes it today (no
    # scope table in it) the reader finds nothing and does not raise
    reader = getattr(__import__("benchmark.layer_metrics." + spec["reader"],
                                fromlist=["read"]), "read")
    assert reader({"trace": {"programs": {}, "busy_s": 0, "window_s": 0}},
                  spec["params"]) is None


def test_every_metric_file_names_a_reader_that_is_there():
    for path in glob.glob(os.path.join(ROOT, "benchmark/layer_metrics",
                                       "*.json")):
        spec = harness.load_json(path)
        assert os.path.basename(path) == spec["name"] + ".json"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark/layer_metrics", spec["reader"] + ".py"))


# -- the tool, rehearsed on the CPU ---------------------------------------

def test_scope_table_tool_prints_host_spans(monkeypatch, capsys):
    """`scope_table.py` drives a tiny serving cell end to end with the
    look for a chip skipped: no device plane on the CPU, so no scope and
    no device metric, but the scheduler's spans are on the host plane."""
    from benchmark import scope_table
    monkeypatch.setattr(harness, "require_chip",
                        lambda chips: harness.device_info())
    monkeypatch.setattr(harness, "peak_for", lambda kind: None)
    rc = scope_table.main([
        "--workload", "bertgen-tiny.closed-tiny", "--seed", "3000000019",
        "--seconds", "1.5", "--set", "clients=3",
        "--benchmark", os.path.join(HERE, "rehearsal.json")])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["scopes"] == {} and line["compiles_in_window"] == 0
    for name in ("generation/step", "generation/decode_dispatch",
                 "generation/readback", "generation/emit",
                 "generation/admit", "serving/request"):
        assert line["host_spans"][name][0] > 0
    assert "sched_host_ms_per_step.serve" in line["metrics"]
    assert not any(k.endswith(".train") or k.endswith(".decode")
                   for k in line["metrics"])
