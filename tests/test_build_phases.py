"""Set-up timed from inside the program (PR 37): `counted_jit`'s builds as
`compile/<kind>` spans with jax's `jax/trace`, `jax/lower`, `jax/compile`
phases as their children, the two counters fed beside them
(`dl4j_compile_phase_seconds_total`, `dl4j_jax_cache_requests_total`), the
ring on the wall clock, and the benchmark's two readers of them
(`benchmark/layer_metrics/build_phase_s.py`, `build_cache_hit_pct.py`),
alone and through the harness's CPU rehearsal of the JoyAI cell."""
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common import metrics, tracing
from deeplearning4j_tpu.common.metrics import MetricsRegistry, registry
from deeplearning4j_tpu.runtime.inference import counted_jit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PHASES = ("trace", "lower", "compile")


def _value(name, **labels):
    fam = registry().get(name)
    if fam is None:
        return 0.0
    return sum(c.value() for k, c in fam.children()
               if all(dict(zip(fam.label_names, k))[n] == v
                      for n, v in labels.items()))


def _build_seconds(kind):
    fam = registry().get("dl4j_compile_seconds")
    return sum(c.sum() for (k, _), c in fam.children() if k == kind)


def _nested_step(scale):
    """A step with an inner `jit` traced inside it, and an eager `jit`
    compiled and run at trace time (a constant made while tracing)."""
    @jax.jit
    def inner(x):
        return jnp.sin(x) * scale

    def step(p, x):
        with jax.ensure_compile_time_eval():
            c = jax.jit(lambda a: jnp.cos(a) + scale)(np.arange(3.0))
        return inner(x) + p + c.sum()

    return step


@pytest.fixture
def ring():
    tracing.tracer().clear()
    yield tracing.tracer()
    tracing.tracer().clear()


@pytest.fixture
def jax_persistent_cache(tmp_path):
    """jax's persistent cache in a temporary directory, every compile
    written; the process's own settings are put back afterwards."""
    from jax._src import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        yield str(tmp_path / "jax")
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


# -- spans ------------------------------------------------------------------

def test_build_records_its_phases_as_children(ring):
    kind = "phases_tree"
    out = counted_jit(_nested_step(2.0), f"{kind}:1")(1.0, jnp.ones(4))
    assert np.isfinite(np.asarray(out)).all()
    events = ring.events()
    (build,) = [e for e in events if e["name"] == "compile/" + kind]
    assert build["args"]["cache"]            # the existing label
    sid = build["args"]["span_id"]
    kids = [e for e in events
            if e.get("args", {}).get("parent_span_id") == sid]
    assert {e["name"] for e in kids} == {"jax/trace", "jax/lower",
                                         "jax/compile"}
    for e in kids:
        assert e["args"]["kind"] == kind and e["args"]["fun_name"]
        assert e["args"]["trace_id"] == build["args"]["trace_id"]
        # inside the build, on one clock (1 us of float rounding)
        assert e["ts"] >= build["ts"] - 1
        assert e["ts"] + e["dur"] <= build["ts"] + build["dur"] + 1
    # the eager jit compiled at trace time: its lower and compile sit
    # inside the step's own trace span
    (outer,) = [e for e in kids if e["name"] == "jax/trace"
                and e["args"]["fun_name"] == "step"]
    nested = [e for e in kids if e["name"] in ("jax/lower", "jax/compile")
              and outer["ts"] <= e["ts"] <= outer["ts"] + outer["dur"]]
    assert len(nested) >= 2


def test_phases_are_self_time_and_never_exceed_the_build(ring):
    kind = "phases_sum"
    counted_jit(_nested_step(3.0), f"{kind}:1")(1.0, jnp.ones(4))
    got = {p: _value("dl4j_compile_phase_seconds_total", kind=kind, phase=p)
           for p in PHASES}
    assert all(v > 0 for v in got.values())
    assert sum(got.values()) <= _build_seconds(kind)
    # the ring's spans give the same self seconds: the nested trace of
    # `inner` counted once, the eager jit's lower + compile taken out of
    # the step's trace
    kids = [(e["name"][4:], e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6)
            for e in ring.events()
            if e.get("args", {}).get("kind") == kind]
    want = tracing.phase_self_seconds(kids, -np.inf, np.inf)
    for p in PHASES:
        assert got[p] == pytest.approx(want[p], abs=1e-5)
    traces = [b - a for n, a, b in kids if n == "trace"]
    assert got["trace"] < sum(traces)        # not the sum of nested spans


def test_phase_self_seconds_by_hand():
    spans = [("trace", 0, 10), ("trace", 2, 3), ("lower", 3, 4),
             ("compile", 4, 6), ("trace", 4.5, 5), ("lower", 11, 12),
             ("compile", 20, 30)]
    assert tracing.phase_self_seconds(spans, 0, 12) == {
        "trace": 7.5, "lower": 2.0, "compile": 1.5}
    assert tracing.phase_self_seconds([], 0, 1) == {
        "trace": 0.0, "lower": 0.0, "compile": 0.0}


def test_plain_jit_feeds_no_kind(ring):
    tracing.watch_compiles()
    before = {p: _value("dl4j_compile_phase_seconds_total", phase=p)
              for p in PHASES}
    jax.jit(lambda a: a * 7.0 - 1.0)(jnp.ones(5))
    jax_spans = [e for e in ring.events() if e["name"].startswith("jax/")]
    assert {e["name"] for e in jax_spans} >= {"jax/trace", "jax/lower",
                                              "jax/compile"}
    for e in jax_spans:
        assert "kind" not in e["args"]
        assert "parent_span_id" not in e["args"]
    assert {p: _value("dl4j_compile_phase_seconds_total", phase=p)
            for p in PHASES} == before


# -- cache outcomes ---------------------------------------------------------

def test_cache_miss_then_hit_counted(ring, jax_persistent_cache,
                                     monkeypatch):
    """Through jax's persistent cache, as the cells' donated steps go:
    the program's own executable store is left out."""
    from deeplearning4j_tpu.runtime import compile_cache
    monkeypatch.setattr(compile_cache, "cache", lambda: None)
    kind = "phases_cache"

    def step(p, x):
        return jnp.tanh(x) * p + 0.125

    counted_jit(step, f"{kind}:1")(2.0, jnp.ones(6))
    assert _value("dl4j_jax_cache_requests_total", kind=kind,
                  outcome="miss") >= 1
    jax.clear_caches()                       # the next build reads the disk
    counted_jit(step, f"{kind}:2")(2.0, jnp.ones(6))
    assert _value("dl4j_jax_cache_requests_total", kind=kind,
                  outcome="hit") >= 1
    compiles = [e for e in ring.events() if e["name"] == "jax/compile"
                and e["args"].get("kind") == kind]
    assert {e["args"].get("cache") for e in compiles} >= {"hit", "miss"}


def test_cache_events_count_only_inside_a_build():
    tracing.watch_compiles()
    kind = "phases_events"
    before = _value("dl4j_jax_cache_requests_total")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert _value("dl4j_jax_cache_requests_total") == before
    with tracing.build_span(kind):
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert _value("dl4j_jax_cache_requests_total", kind=kind,
                  outcome="hit") == 1
    assert _value("dl4j_jax_cache_requests_total", kind=kind,
                  outcome="miss") == 2


# -- failure and off states -------------------------------------------------

class _Raising(dict):
    def get(self, *a):
        raise RuntimeError("listener fault")

    def values(self):
        raise RuntimeError("listener fault")


def test_a_raising_listener_never_breaks_a_compile(monkeypatch, ring):
    tracing.watch_compiles()
    monkeypatch.setattr(tracing, "COMPILE_PHASES", _Raising())
    monkeypatch.setattr(tracing, "CACHE_OUTCOMES", _Raising())
    out = counted_jit(lambda p, x: x * p + 2.0, "phases_fault:1")(
        3.0, jnp.ones(3))
    np.testing.assert_allclose(np.asarray(out), 5.0)
    out = jax.jit(lambda a: a - 4.0)(jnp.ones(2))
    np.testing.assert_allclose(np.asarray(out), -3.0)
    # bad arguments straight into the listeners
    tracing._on_compile_phase("/jax/core/compile/jaxpr_trace_duration",
                              "not", None)
    tracing._on_cache_event("/jax/compilation_cache/cache_hits", x=object())
    assert not [e for e in ring.events() if e["name"].startswith("jax/")]


def test_disabled_registry_registers_and_records_nothing(monkeypatch, ring):
    from jax._src import monitoring
    monkeypatch.setattr(tracing, "_WATCHING", False)
    spans = monitoring.get_event_time_span_listeners()
    events = monitoring.get_event_listeners()
    before = _value("dl4j_compile_phase_seconds_total")
    registry().set_enabled(False)
    try:
        out = counted_jit(lambda p, x: x + p, "phases_off:1")(
            1.0, jnp.ones(2))
        jax.jit(lambda a: a * 5.0 + 1.0)(jnp.ones(2))
        assert tracing.build_span("phases_off") is tracing._NULL_SPAN
        assert not tracing.watch_compiles()
    finally:
        registry().set_enabled(True)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert monitoring.get_event_time_span_listeners() == spans
    assert monitoring.get_event_listeners() == events
    assert ring.events() == []
    assert _value("dl4j_compile_phase_seconds_total") == before


# -- one clock --------------------------------------------------------------

def test_ring_is_on_the_wall_clock(ring):
    wall = time.time()
    with ring.span("probe/clock"):
        pass
    t0 = time.perf_counter()
    ring.record("probe/recorded", t0, t0 + 0.001)
    a, b = ring.events()
    assert abs(a["ts"] * 1e-6 - wall) < 0.005
    assert abs(b["ts"] * 1e-6 - time.time()) < 0.005
    fresh = tracing.Tracer(capacity=4)
    with fresh.span("probe/fresh"):
        pass
    assert abs(fresh.events()[0]["ts"] * 1e-6 - time.time()) < 0.005


# -- the readers ------------------------------------------------------------

@pytest.fixture
def fresh_registry(monkeypatch):
    reg = MetricsRegistry(enabled=True)
    monkeypatch.setattr(metrics, "_REGISTRY", reg)
    return reg


def test_readers_from_a_fed_registry(fresh_registry):
    from benchmark.layer_metrics import build_cache_hit_pct, build_phase_s
    ctx = {"end_to_end": {"setup_s": 30.0}}
    for p in ("trace", "lower", "compile", "outside"):
        assert build_phase_s.read(ctx, {"phase": p}) is None
    assert build_cache_hit_pct.read(ctx, {}) is None

    reg = fresh_registry
    secs = reg.histogram("dl4j_compile_seconds", labels=("kind", "cache"))
    secs.labels(kind="a", cache="bypass:donation").observe(9.0)
    secs.labels(kind="b", cache="hit").observe(3.0)
    # a program older than PR 37: builds, but no phase counter
    assert build_phase_s.read(ctx, {"phase": "trace"}) is None
    assert build_phase_s.read(ctx, {"phase": "outside"}) == 18.0
    phases = reg.counter("dl4j_compile_phase_seconds_total",
                         labels=("kind", "phase"))
    for kind, (t, lo, c) in {"a": (4.0, 1.5, 2.0),
                             "b": (0.5, 0.25, 0.0)}.items():
        phases.labels(kind=kind, phase="trace").inc(t)
        phases.labels(kind=kind, phase="lower").inc(lo)
        phases.labels(kind=kind, phase="compile").inc(c)
    assert build_phase_s.read(ctx, {"phase": "trace"}) == 4.5
    assert build_phase_s.read(ctx, {"phase": "lower"}) == 1.75
    assert build_phase_s.read(ctx, {"phase": "compile"}) == 2.0
    cache = reg.counter("dl4j_jax_cache_requests_total",
                        labels=("kind", "outcome"))
    cache.labels(kind="a", outcome="hit").inc(3)
    cache.labels(kind="b", outcome="hit").inc(0)
    assert build_cache_hit_pct.read(ctx, {}) == 100.0
    cache.labels(kind="b", outcome="miss").inc(1)
    assert build_cache_hit_pct.read(ctx, {}) == 75.0


# -- through the harness ----------------------------------------------------

NEW = ("setup_step_trace_s", "setup_step_lower_s", "setup_step_compile_s",
       "setup_outside_step_s", "setup_step_cache_hit_pct")


def test_rehearsal_reports_the_five_build_metrics(monkeypatch,
                                                  fresh_registry,
                                                  jax_persistent_cache):
    """The tiny JoyAI cell through `harness.run_cell` with only the five
    entries: every one a number, the phases inside the builds' seconds
    inside `setup_s`, and no `counted_jit` build between the window's
    close and the readers (the reference builds nothing through it)."""
    from benchmark import harness
    bench = harness.load_json(ROOT, "benchmark/tests/rehearsal_joyai.json")
    tiny = "joyai-tiny.train-tiny-latent"
    real = {m["name"]: m for m in harness.load_benchmark(ROOT)["per_layer"]}
    bench["per_layer"] = [dict(real[n], workloads=[tiny]) for n in NEW]

    closed, seen = [], {}
    driver_for = harness.driver_for

    def counting_driver(kind):
        d = driver_for(kind)

        def measure(*a):
            window = d.measure(*a)
            closed.append(harness.compile_count())
            return window
        return types.SimpleNamespace(setup=d.setup, measure=measure,
                                     release=d.release, check=d.check)

    read_layer_metrics = harness.read_layer_metrics

    def reading(*a, **k):
        seen["compiles"] = harness.compile_count()
        return read_layer_metrics(*a, **k)

    monkeypatch.setattr(harness, "driver_for", counting_driver)
    monkeypatch.setattr(harness, "read_layer_metrics", reading)
    out = harness.run_cell(tiny, 3_700_000_017, 0.5, True,
                           t_start=time.monotonic(), need_chip=False,
                           bench=bench, root=ROOT)
    got = {k: v["value"] for k, v in out["result"]["metrics"].items()}
    assert set(got) == set(NEW)
    assert seen["compiles"] == closed[0]
    built = sum(c.sum() for _, c in
                fresh_registry.get("dl4j_compile_seconds").children())
    steps = sum(got[f"setup_step_{p}_s"] for p in PHASES)
    assert 0 < steps <= built <= out["notes"]["setup_s"]
    assert got["setup_outside_step_s"] >= 0
    assert got["setup_outside_step_s"] == pytest.approx(
        out["notes"]["setup_s"] - built)
    assert 0 <= got["setup_step_cache_hit_pct"] <= 100
    assert out["result"]["correct"] is True
