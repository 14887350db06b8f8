"""The bench sanity gate must reject physically impossible measurements.

A judged headline once read 69,690 samples/s/chip — 2,989% implied MFU,
~30x chip peak — because the runtime answered repeated identical
executes from a cache instead of running them. These tests pin the gate
that keeps such an artifact out of a judged record.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import bench  # noqa: E402  (repo-root module)


def _bert_base_mfu(samples_per_sec, T=128, peak=197e12):
    from deeplearning4j_tpu.models import bert
    fpt = bert.flops_per_token(bert.BertConfig.base())
    return samples_per_sec * T * fpt / peak


DECREASING = np.linspace(10.4, 9.7, 20)


class TestCheckBertSanity:
    def test_rejects_the_r04_artifact(self):
        # the exact invalid judged number: 69,690 samples/s on a v5e
        mfu = _bert_base_mfu(69690.0)
        assert mfu > 10  # ~30x peak — sanity of the test itself
        ok, reason = bench.check_bert_sanity(DECREASING, mfu)
        assert not ok
        assert "impossible" in reason or "ceiling" in reason

    def test_rejects_anything_over_ceiling(self):
        ok, _ = bench.check_bert_sanity(DECREASING, 0.81)
        assert not ok
        ok, _ = bench.check_bert_sanity(DECREASING, bench.BERT_MFU_CEILING
                                        + 1e-6)
        assert not ok

    def test_accepts_credible_measurement(self):
        # r3's trustworthy headline: 1,427 samples/s ~= 60.6% MFU
        mfu = _bert_base_mfu(1427.0)
        assert 0.4 < mfu < bench.BERT_MFU_CEILING
        ok, reason = bench.check_bert_sanity(DECREASING, mfu)
        assert ok, reason

    def test_rejects_flat_loss_trajectory(self):
        # device never stepped: same loss replayed N times
        ok, reason = bench.check_bert_sanity(np.full(20, 10.38), 0.5)
        assert not ok
        assert "mostly flat" in reason

    def test_accepts_single_plateau_step(self):
        # one bitwise-equal adjacent pair is a legitimately plateaued f32
        # step, not a stuck device (the gate requires >= 80% changing)
        l = DECREASING.copy()
        l[7] = l[6]
        ok, reason = bench.check_bert_sanity(l, 0.5)
        assert ok, reason

    def test_rejects_mostly_stuck_trajectory(self):
        l = DECREASING.copy()
        l[10:] = l[10]  # back half frozen: device stopped stepping
        ok, reason = bench.check_bert_sanity(l, 0.5)
        assert not ok
        assert "mostly flat" in reason

    def test_rejects_nonfinite_loss(self):
        l = DECREASING.copy()
        l[3] = np.nan
        ok, reason = bench.check_bert_sanity(l, 0.5)
        assert not ok
        assert "finite" in reason

    def test_rejects_replayed_dispatch(self):
        # two of three dispatches return byte-identical trajectories:
        # a cached execute was served instead of running the scan
        t1 = DECREASING
        t3 = DECREASING - 0.8
        ok, reason = bench.check_bert_sanity(np.stack([t1, t1, t3]), 0.5)
        assert not ok
        assert "replayed" in reason

    def test_accepts_distinct_dispatches(self):
        stack = np.stack([DECREASING, DECREASING - 0.7, DECREASING - 1.4])
        ok, reason = bench.check_bert_sanity(stack, 0.5)
        assert ok, reason


class TestSelectHeadline:
    def test_insane_variant_never_wins(self):
        variants = {
            "flash": {"samples_per_sec": 69690.0, "mfu": 29.6, "sane": False,
                      "reason": "implied MFU 29.6 > ceiling"},
            "xla": {"samples_per_sec": 1427.0, "mfu": 0.606, "sane": True,
                    "reason": "ok"},
        }
        name, rec = bench.select_headline(variants)
        assert name == "xla"
        assert rec["samples_per_sec"] == 1427.0

    def test_all_insane_fails_loudly(self):
        variants = {
            "flash": {"samples_per_sec": 69690.0, "mfu": 29.6, "sane": False,
                      "reason": "implied MFU 29.6 > ceiling"},
        }
        with pytest.raises(RuntimeError, match="refusing to emit"):
            bench.select_headline(variants)

    def test_fastest_sane_wins(self):
        variants = {
            "a": {"samples_per_sec": 1000.0, "sane": True, "reason": "ok"},
            "b": {"samples_per_sec": 1400.0, "sane": True, "reason": "ok"},
        }
        name, _ = bench.select_headline(variants)
        assert name == "b"


class TestNoHiddenFallbacks:
    """bench.py must not guess a device's peak, and a run with a hole in
    it must not exit 0."""

    class _Dev:
        def __init__(self, kind, platform="tpu"):
            self.device_kind, self.platform = kind, platform

    @pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                           ("cpu", 0.0)])
    def test_known_device_kinds(self, kind, peak):
        assert bench._peak_flops(self._Dev(kind)) == peak

    def test_unknown_device_kind_raises(self):
        with pytest.raises(KeyError, match="TPU v9"):
            bench._peak_flops(self._Dev("TPU v9"))

    def test_main_exits_nonzero_when_a_section_raised(self, monkeypatch,
                                                      capsys, tmp_path):
        import json
        monkeypatch.setenv("BENCH_TINY", "1")
        monkeypatch.setenv("BENCH_SKIP_EXTRAS", "1")
        # main() places jax's cache with setdefault: keep the env as found
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

        def fake(jax, jnp, bert, config, batch, B, T, n_steps, kw, fpt,
                 peak):
            if kw.get("use_flash"):
                raise RuntimeError("boom")
            return {"samples_per_sec": 1.0, "mfu": 0.0, "sane": True,
                    "reason": "ok", "variant": kw, "loss_first": 2.0,
                    "loss_last": 1.0, "spread_pct": 0.0}

        monkeypatch.setattr(bench, "_measure_bert_variant", fake)
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code not in (0, None)
        assert "bert_variants.flash" in str(exc.value.code)
        # the record is still printed, with the hole marked
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rec["bert_variants"]["flash"]["reason"].startswith("error:")
        assert rec["bert_variants"]["xla"]["sane"]


def _tm_record(default_sps=100.0, remat_sps=80.0, default_peak=4_000_000,
               accum_peak=1_000_000, default_act=10_000_000,
               remat_act=2_000_000):
    return {
        "default": {"samples_per_sec": default_sps,
                    "peak_bytes": default_peak,
                    "activation_bytes": default_act},
        "remat": {"samples_per_sec": remat_sps,
                  "peak_bytes": default_peak,
                  "activation_bytes": remat_act},
        "remat_accum": {"samples_per_sec": remat_sps,
                        "peak_bytes": accum_peak,
                        "activation_bytes": remat_act},
    }


class TestCheckTrainMemory:
    """Gate logic for the train_memory metric (perf trajectory): remat must
    not cost >30% samples/sec at equal batch, and the accumulation path
    must actually lower peak memory at equal effective batch."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_train_memory(_tm_record())
        assert ok, reason

    def test_rejects_slow_remat(self):
        # 69 < 0.7 * 100: recompute ate more than the one-extra-forward
        # budget — the checkpoint boundaries are wrong
        ok, reason = bench.check_train_memory(_tm_record(remat_sps=69.0))
        assert not ok
        assert "remat samples/sec" in reason
        ok, _ = bench.check_train_memory(_tm_record(remat_sps=71.0))
        assert ok

    def test_rejects_accum_without_memory_win(self):
        ok, reason = bench.check_train_memory(
            _tm_record(accum_peak=4_000_000))
        assert not ok
        assert "saved no memory" in reason

    def test_rejects_remat_without_activation_win(self):
        ok, reason = bench.check_train_memory(
            _tm_record(remat_act=10_000_000))
        assert not ok
        assert "saved no activations" in reason

    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU: the tiny CNN record must pass
        its own gate's analytic legs — deterministically lower XLA peak
        for the accum path and lower stored residuals for remat. The
        samples/sec leg is a ratio of two CPU wall times: evaluated and
        recorded, not judged here."""
        import jax
        import jax.numpy as jnp

        rec = bench.bench_train_memory(jax, jnp, tiny=True)
        assert "gate_ok" in rec and "gate_reason" in rec
        ok, reason = bench.check_train_memory(rec, max_sps_regression=1.0)
        assert ok, reason
        assert rec["remat_accum"]["peak_bytes"] < rec["default"]["peak_bytes"]
        assert (rec["remat"]["activation_bytes"]
                < rec["default"]["activation_bytes"])
        assert rec["effective_batch"] == rec["batch"]


class TestCheckTelemetryOverhead:
    """Gate logic for the telemetry_overhead metric: metrics-on serving
    throughput may cost at most 3% vs metrics-off (the near-zero-cost
    contract of the telemetry subsystem)."""

    def test_accepts_cheap_telemetry(self):
        ok, reason = bench.check_telemetry_overhead(
            {"metrics_on_sps": 990.0, "metrics_off_sps": 1000.0})
        assert ok, reason

    def test_rejects_expensive_telemetry(self):
        ok, reason = bench.check_telemetry_overhead(
            {"metrics_on_sps": 900.0, "metrics_off_sps": 1000.0})
        assert not ok
        assert "near-zero-cost" in reason

    def test_boundary_at_three_percent(self):
        ok, _ = bench.check_telemetry_overhead(
            {"metrics_on_sps": 970.0, "metrics_off_sps": 1000.0})
        assert ok
        ok, _ = bench.check_telemetry_overhead(
            {"metrics_on_sps": 969.0, "metrics_off_sps": 1000.0})
        assert not ok

    def test_custom_budget(self):
        rec = {"metrics_on_sps": 950.0, "metrics_off_sps": 1000.0}
        ok, _ = bench.check_telemetry_overhead(rec, max_overhead=0.10)
        assert ok

    def test_fleet_pass_gated_when_present(self):
        # records without the fleet pass (older artifacts) still gate
        base = {"metrics_on_sps": 990.0, "metrics_off_sps": 1000.0}
        ok, _ = bench.check_telemetry_overhead(dict(base))
        assert ok
        ok, _ = bench.check_telemetry_overhead(
            dict(base, fleet_on_rps=98.0, fleet_off_rps=100.0))
        assert ok
        ok, reason = bench.check_telemetry_overhead(
            dict(base, fleet_on_rps=90.0, fleet_off_rps=100.0))
        assert not ok
        assert "fleet observability plane" in reason

    def test_tiny_live_measurement_structure(self):
        """The metric end-to-end on CPU: record shape + gate evaluation.
        The wall-clock overheads (ratios of two CPU times) are recorded,
        not judged here; the enabled-flag must be restored afterwards."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.common.metrics import registry

        prev = registry().enabled
        rec = bench.bench_telemetry_overhead(jax, jnp, tiny=True)
        assert registry().enabled == prev  # restored
        assert rec["metrics_on_sps"] > 0 and rec["metrics_off_sps"] > 0
        assert "gate_ok" in rec and "gate_reason" in rec
        assert rec["overhead_frac"] == pytest.approx(
            1.0 - rec["metrics_on_sps"] / rec["metrics_off_sps"], abs=1e-3)
        # request-scoped tracing pass (PR 6): measured
        assert rec["metrics_trace_sps"] > 0
        assert "tracing_overhead_frac" in rec
        # fleet observability pass (PR 18): routed path measured with
        # the plane armed vs disarmed
        assert rec["fleet_on_rps"] > 0 and rec["fleet_off_rps"] > 0
        assert "fleet_overhead_frac" in rec


def _so_record(unloaded_p99=10.0, on_p99=20.0, on_completed=50, on_shed=40,
               off_p99=200.0):
    return {
        "unloaded_p99_ms": unloaded_p99,
        "shed_on": {"completed": on_completed, "shed": on_shed,
                    "offered": 120, "p50_ms": on_p99 / 2, "p99_ms": on_p99,
                    "throughput_rps": 100.0},
        "shed_off": {"completed": 120, "shed": 0, "offered": 120,
                     "p50_ms": off_p99 / 2, "p99_ms": off_p99,
                     "throughput_rps": 100.0},
    }


class TestCheckServingOverload:
    """Gate logic for the serving_overload metric: under synthetic
    overload the admission controller must actually shed, and the
    requests it DOES admit must keep a p99 within 3x of the unloaded
    p99 — the bounded-queue contract of load shedding."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_serving_overload(_so_record())
        assert ok, reason

    def test_rejects_unbounded_admitted_p99(self):
        ok, reason = bench.check_serving_overload(_so_record(on_p99=31.0))
        assert not ok
        assert "not bounding" in reason

    def test_boundary_at_three_x(self):
        ok, _ = bench.check_serving_overload(_so_record(on_p99=29.9))
        assert ok
        ok, _ = bench.check_serving_overload(_so_record(on_p99=30.1))
        assert not ok

    def test_rejects_record_without_shedding(self):
        # zero shed means the storm never overloaded the controller: the
        # bounded-p99 claim was not actually tested
        ok, reason = bench.check_serving_overload(_so_record(on_shed=0))
        assert not ok
        assert "never tripped" in reason

    def test_rejects_shed_everything(self):
        ok, reason = bench.check_serving_overload(
            _so_record(on_completed=0))
        assert not ok
        assert "shed everything" in reason

    def test_custom_ratio(self):
        rec = _so_record(on_p99=45.0)
        ok, _ = bench.check_serving_overload(rec, max_p99_ratio=5.0)
        assert ok

    def test_tiny_live_measurement(self):
        """The metric end-to-end on CPU: the storm must actually shed
        (deterministic: 4 threads vs max_concurrent=1 with high_water=1)
        and admitted requests must complete. The 3x wall-clock bound is
        evaluated and recorded; no CPU latency is judged here."""
        import jax
        import jax.numpy as jnp

        rec = bench.bench_serving_overload(jax, jnp, tiny=True)
        assert rec["shed_on"]["completed"] > 0
        assert rec["shed_on"]["shed"] > 0
        assert rec["shed_off"]["shed"] == 0
        assert rec["shed_on"]["completed"] + rec["shed_on"]["shed"] \
            == rec["shed_on"]["offered"]
        assert rec["unloaded_p99_ms"] > 0
        assert "gate_ok" in rec and "gate_reason" in rec
        assert rec["shed_on"]["p99_ms"] > 0 and rec["shed_off"]["p99_ms"] > 0


def _sr_record(ok_rate=0.999, faulted_p99=25.0, fault_free_p99=10.0,
               injected=8, restarts=2, permadeaths=0, survivors=6,
               submitted=6, opened=True, reclosed=True, reclose_s=0.25,
               probe_s=0.2):
    return {
        "threads": 4, "requests_per_phase": 160, "fault_rate": 0.05,
        "fault_free": {"offered": 160, "ok": 160, "quarantined": 0,
                       "failed_other": 0, "ok_rate_of_nonpoison": 1.0,
                       "p50_ms": 2.0, "p99_ms": fault_free_p99},
        "faulted": {"offered": 160, "ok": 155, "quarantined": 2,
                    "failed_other": 0,
                    "ok_rate_of_nonpoison": ok_rate,
                    "p50_ms": 2.5, "p99_ms": faulted_p99,
                    "injected": injected},
        "batcher_crash": {"restarts": restarts, "survivors": survivors,
                          "submitted": submitted,
                          "permadeaths": permadeaths},
        "breaker": {"opened": opened, "reclosed": reclosed,
                    "probe_s": probe_s, "reclose_s": reclose_s,
                    "state": "closed"},
    }


class TestCheckServingResilience:
    """Gate logic for the serving_resilience metric: under 5% injected
    dispatch faults >= 99% of non-quarantined requests must succeed with
    a p99 within 3x of the fault-free run, the supervised batcher must
    restart (and never permadie), and the circuit breaker must open
    under sustained faults and re-close within its probe window."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_serving_resilience(_sr_record())
        assert ok, reason

    def test_rejects_zero_injected_faults(self):
        ok, reason = bench.check_serving_resilience(_sr_record(injected=0))
        assert not ok
        assert "untested" in reason

    def test_rejects_low_success_rate(self):
        ok, reason = bench.check_serving_resilience(
            _sr_record(ok_rate=0.98))
        assert not ok
        assert "innocent" in reason
        ok, _ = bench.check_serving_resilience(_sr_record(ok_rate=0.991))
        assert ok

    def test_rejects_unbounded_faulted_p99(self):
        ok, reason = bench.check_serving_resilience(
            _sr_record(faulted_p99=31.0))
        assert not ok
        assert "stalling" in reason
        ok, _ = bench.check_serving_resilience(_sr_record(faulted_p99=29.9))
        assert ok

    def test_rejects_permadeath(self):
        ok, reason = bench.check_serving_resilience(
            _sr_record(permadeaths=1))
        assert not ok
        assert "permadeath" in reason

    def test_rejects_unexercised_supervisor(self):
        ok, reason = bench.check_serving_resilience(_sr_record(restarts=0))
        assert not ok
        assert "never restarted" in reason

    def test_rejects_lost_queued_work(self):
        ok, reason = bench.check_serving_resilience(
            _sr_record(survivors=5))
        assert not ok
        assert "lost" in reason

    def test_rejects_breaker_that_never_opened(self):
        ok, reason = bench.check_serving_resilience(_sr_record(opened=False))
        assert not ok
        assert "never opened" in reason

    def test_rejects_breaker_that_stayed_open(self):
        ok, reason = bench.check_serving_resilience(
            _sr_record(reclosed=False))
        assert not ok
        assert "re-close" in reason

    def test_rejects_slow_reclose(self):
        ok, reason = bench.check_serving_resilience(
            _sr_record(reclose_s=2.0, probe_s=0.2))
        assert not ok
        assert "probe" in reason

    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU. The deterministic legs ARE
        asserted in CI (faults injected, supervisor restarted, zero
        permadeaths, breaker opened and re-closed); the p99 ratio is
        evaluated and recorded, with wide margin at the tiny sizing."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.common import faults as faults_mod

        rec = bench.bench_serving_resilience(jax, jnp, tiny=True)
        assert not faults_mod.active()  # bench disarmed everything
        assert rec["faulted"]["injected"] > 0
        assert rec["batcher_crash"]["restarts"] >= 1
        assert rec["batcher_crash"]["permadeaths"] == 0
        assert rec["batcher_crash"]["survivors"] == \
            rec["batcher_crash"]["submitted"]
        assert rec["breaker"]["opened"] and rec["breaker"]["reclosed"]
        assert rec["breaker"]["state"] == "closed"
        assert rec["faulted"]["ok_rate_of_nonpoison"] >= 0.99
        assert "gate_ok" in rec and "gate_reason" in rec


def _gd_record(kv_speedup=4.0, cb_speedup=2.0, match=True, compiles=0,
               bytes_ratio=0.35, prefill_speedup=1.7, spec_match=True,
               acceptance=0.8):
    return {
        "kv_cached": {"tokens_per_sec": 400.0},
        "recompute": {"tokens_per_sec": 400.0 / kv_speedup},
        "kv_speedup": kv_speedup,
        "decode_match": match,
        "steady_state_compiles": compiles,
        "continuous": {"tokens_per_sec": 1000.0, "requests": 6,
                       "p50_ttft_ms": 5.0, "p99_ttft_ms": 25.0},
        "serial": {"tokens_per_sec": 1000.0 / cb_speedup},
        "cb_speedup": cb_speedup,
        "paged_kv": {"block_size": 16,
                     "paged_bytes_per_token": 10000.0 * bytes_ratio
                     if bytes_ratio is not None else None,
                     "slab_bytes_per_token": 10000.0,
                     "bytes_ratio": bytes_ratio},
        "batched_prefill": {"prompts": 16, "batched_dispatches": 4,
                            "serial_dispatches": 16,
                            "speedup": prefill_speedup,
                            "p99_ttft_ms": 20.0},
        "speculative": {"k": 3, "decode_match": spec_match,
                        "tokens_per_sec": 600.0,
                        "plain_tokens_per_sec": 400.0,
                        "speedup": 1.5, "acceptance_rate": acceptance,
                        "proposed": 90, "accepted": 72},
    }


class TestCheckGenerativeDecode:
    """Gate logic for the generative_decode metric: the KV cache must buy
    >= 3x tokens/sec over prefix recompute, continuous batching >= 1.5x
    over per-request serving, greedy outputs must be token-identical, and
    the steady state must compile nothing after warmup. The paging PR
    added three more: paged KV must hold <= 0.6x the slab layout's bytes
    per active token, batched prefill must ingest prompts >= 1.3x faster
    than per-prompt dispatch, and the speculative run must be
    token-identical to the engine's own plain run with a measured
    acceptance rate."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_generative_decode(_gd_record())
        assert ok, reason

    def test_rejects_insufficient_kv_speedup(self):
        ok, reason = bench.check_generative_decode(
            _gd_record(kv_speedup=2.5))
        assert not ok
        assert "prefix recompute" in reason

    def test_boundary_at_three_x(self):
        ok, _ = bench.check_generative_decode(_gd_record(kv_speedup=3.01))
        assert ok
        ok, _ = bench.check_generative_decode(_gd_record(kv_speedup=2.99))
        assert not ok

    def test_rejects_insufficient_cb_speedup(self):
        ok, reason = bench.check_generative_decode(
            _gd_record(cb_speedup=1.3))
        assert not ok
        assert "sharing decode steps" in reason
        ok, _ = bench.check_generative_decode(_gd_record(cb_speedup=1.51))
        assert ok

    def test_rejects_token_mismatch(self):
        # a fast decode that decodes something else is not a speedup
        ok, reason = bench.check_generative_decode(_gd_record(match=False))
        assert not ok
        assert "token" in reason

    def test_rejects_steady_state_recompiles(self):
        ok, reason = bench.check_generative_decode(_gd_record(compiles=2))
        assert not ok
        assert "retracing" in reason

    def test_rejects_high_kv_bytes_ratio(self):
        # paged footprint near the slab's means blocks aren't tracking
        # actual sequence length — the whole point of paging
        ok, reason = bench.check_generative_decode(
            _gd_record(bytes_ratio=0.7))
        assert not ok
        assert "bytes per active token" in reason
        ok, _ = bench.check_generative_decode(_gd_record(bytes_ratio=0.59))
        assert ok
        ok, _ = bench.check_generative_decode(_gd_record(bytes_ratio=0.61))
        assert not ok

    def test_rejects_missing_paged_section(self):
        rec = _gd_record()
        del rec["paged_kv"]
        ok, reason = bench.check_generative_decode(rec)
        assert not ok
        assert "paged_kv" in reason
        rec = _gd_record(bytes_ratio=None)
        ok, reason = bench.check_generative_decode(rec)
        assert not ok
        assert "paged_kv" in reason

    def test_rejects_insufficient_prefill_speedup(self):
        ok, reason = bench.check_generative_decode(
            _gd_record(prefill_speedup=1.1))
        assert not ok
        assert "sharing a dispatch" in reason
        ok, _ = bench.check_generative_decode(
            _gd_record(prefill_speedup=1.31))
        assert ok

    def test_rejects_missing_prefill_section(self):
        rec = _gd_record()
        del rec["batched_prefill"]
        ok, reason = bench.check_generative_decode(rec)
        assert not ok
        assert "batched_prefill" in reason

    def test_rejects_speculative_token_mismatch(self):
        # a draft that changes the greedy output is a correctness bug,
        # whatever its speed
        ok, reason = bench.check_generative_decode(
            _gd_record(spec_match=False))
        assert not ok
        assert "non-speculative" in reason

    def test_rejects_missing_acceptance_rate(self):
        # no acceptance rate means the draft never proposed — the spec
        # path wasn't actually exercised
        ok, reason = bench.check_generative_decode(
            _gd_record(acceptance=None))
        assert not ok
        assert "acceptance" in reason

    def test_custom_thresholds(self):
        rec = _gd_record(kv_speedup=2.5, cb_speedup=1.2,
                         bytes_ratio=0.7, prefill_speedup=1.1)
        ok, _ = bench.check_generative_decode(rec, min_kv_speedup=2.0,
                                              min_cb_speedup=1.1,
                                              max_kv_bytes_ratio=0.8,
                                              min_prefill_speedup=1.0)
        assert ok

    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU. The gate's deterministic
        legs ARE asserted: token-identity, the zero-recompile invariant,
        the paged-vs-slab bytes ratio, fewer batched dispatches, the
        speculative run's identity. Its three timed legs (KV, continuous
        batching and batched-prefill speedups, ratios of CPU wall times)
        are evaluated and recorded, not judged here."""
        import jax
        import jax.numpy as jnp

        rec = bench.bench_generative_decode(jax, jnp, tiny=True)
        assert rec["decode_match"]
        assert rec["steady_state_compiles"] == 0
        assert rec["continuous"]["p99_ttft_ms"] > 0
        assert rec["paged_kv"]["bytes_ratio"] < 0.6
        assert rec["batched_prefill"]["batched_dispatches"] < \
            rec["batched_prefill"]["serial_dispatches"]
        assert rec["speculative"]["decode_match"]
        assert rec["speculative"]["acceptance_rate"] is not None
        assert "gate_ok" in rec and "gate_reason" in rec
        ok, reason = bench.check_generative_decode(
            rec, min_kv_speedup=0.0, min_cb_speedup=0.0,
            min_prefill_speedup=0.0)
        assert ok, reason


def _qi_record(speedup=1.8, top1=1.0, bytes_ratio=0.26, rejected=True,
               status=200, served="v1", current="v1"):
    return {
        "top1_agreement": top1,
        "max_abs_err": 0.0003,
        "param_bytes_full": 1000000,
        "param_bytes_quant": int(1000000 * bytes_ratio),
        "bytes_ratio": bytes_ratio,
        "f32_sps": 9000.0,
        "bf16_sps": 4000.0,
        "quantized_sps": 4000.0 * speedup,
        "quant_speedup_vs_bf16": speedup,
        "misscale_rejected": rejected,
        "post_reject_predict_status": status,
        "post_reject_served_version": served,
        "current_version": current,
    }


class TestCheckQuantizedInference:
    """Gate logic for the quantized_inference metric: the int8 twin must
    be >= 1.2x the bf16 baseline and >= 99% top-1-consistent with f32,
    and the mis-scaled-spec drill must end with the gate rejecting the
    deploy and the full-precision version still serving."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_quantized_inference(_qi_record())
        assert ok, reason

    def test_rejects_insufficient_speedup(self):
        ok, reason = bench.check_quantized_inference(
            _qi_record(speedup=1.1))
        assert not ok
        assert "bf16 baseline" in reason

    def test_boundary_at_1_2x(self):
        ok, _ = bench.check_quantized_inference(_qi_record(speedup=1.21))
        assert ok
        ok, _ = bench.check_quantized_inference(_qi_record(speedup=1.19))
        assert not ok

    def test_rejects_low_top1_agreement(self):
        ok, reason = bench.check_quantized_inference(
            _qi_record(top1=0.95))
        assert not ok
        assert "top-1" in reason

    def test_rejects_unshrunk_params(self):
        # a "quantized" twin that is still f32-sized never stored int8
        ok, reason = bench.check_quantized_inference(
            _qi_record(bytes_ratio=1.0))
        assert not ok
        assert "at rest" in reason

    def test_rejects_unguarded_misscale_deploy(self):
        ok, reason = bench.check_quantized_inference(
            _qi_record(rejected=False))
        assert not ok
        assert "gate" in reason

    def test_rejects_disturbed_live_version(self):
        # the aborted swap must leave v1 current and answering
        ok, reason = bench.check_quantized_inference(
            _qi_record(status=503))
        assert not ok
        assert "aborted swap" in reason
        ok, _ = bench.check_quantized_inference(_qi_record(current="v2"))
        assert not ok

    def test_custom_thresholds(self):
        rec = _qi_record(speedup=1.1, top1=0.97)
        ok, _ = bench.check_quantized_inference(rec, min_speedup=1.05,
                                                min_top1=0.95)
        assert ok

    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU. The deterministic legs ARE
        asserted in CI (top-1 agreement on the margin-filtered batch,
        int8-at-rest byte shrink, the mis-scale rejection with v1 still
        answering /predict); the 1.2x throughput gate has wide margin at
        the tiny sizing (measured ~1.8x: XLA:CPU emulates bf16, the twin
        computes in f32 with folded dequant)."""
        import jax
        import jax.numpy as jnp

        rec = bench.bench_quantized_inference(jax, jnp, tiny=True)
        assert rec["top1_agreement"] >= 0.99
        assert rec["bytes_ratio"] < 0.6
        assert rec["misscale_rejected"]
        assert rec["post_reject_predict_status"] == 200
        assert rec["post_reject_served_version"] == "v1"
        assert rec["current_version"] == "v1"
        assert rec["current_precision"] == "float32"
        assert "gate_ok" in rec and "gate_reason" in rec


def _cs_record(cold_ttfi=0.5, warm_ttfi=0.1, warm_hits=4):
    return {
        "cold": {"ttfi_s": cold_ttfi, "warmup_s": 1.0, "cache_hits": 0},
        "warm": {"ttfi_s": warm_ttfi, "warmup_s": 0.3,
                 "cache_hits": warm_hits},
    }


class TestCheckColdStart:
    """Gate logic for the cold_start metric: a warm-cache restart must be
    >= 2x faster to first inference than a cold compile, and the speedup
    must come from real executable-store hits."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_cold_start(_cs_record())
        assert ok, reason

    def test_rejects_insufficient_speedup(self):
        ok, reason = bench.check_cold_start(
            _cs_record(cold_ttfi=0.15, warm_ttfi=0.1))
        assert not ok
        assert "2.0x" in reason or "2x" in reason or "faster" in reason

    def test_boundary_at_two_x(self):
        ok, _ = bench.check_cold_start(
            _cs_record(cold_ttfi=0.21, warm_ttfi=0.1))
        assert ok
        ok, _ = bench.check_cold_start(
            _cs_record(cold_ttfi=0.19, warm_ttfi=0.1))
        assert not ok

    def test_rejects_speedup_without_cache_hits(self):
        # a fast warm phase with zero store hits is measuring leaked
        # in-memory caches, not the persistent store
        ok, reason = bench.check_cold_start(_cs_record(warm_hits=0))
        assert not ok
        assert "no executable-store hits" in reason

    def test_custom_min_speedup(self):
        rec = _cs_record(cold_ttfi=0.15, warm_ttfi=0.1)
        ok, _ = bench.check_cold_start(rec, min_speedup=1.2)
        assert ok

    def test_tiny_live_measurement(self):
        """The full metric end-to-end on CPU: a fresh cache dir, a cold
        phase that stores executables, a warm phase that loads them. The
        warm phase must actually hit the store; the 2x wall-clock gate is
        evaluated and recorded (and asserted by the bench artifact — CI
        only requires the record to be structurally sound and the hits
        real)."""
        import jax
        import jax.numpy as jnp

        rec = bench.bench_cold_start(jax, jnp, tiny=True)
        for phase in ("cold", "warm"):
            assert rec[phase]["ttfi_s"] > 0
            assert rec[phase]["warmup_s"] > 0
            assert rec[phase]["buckets_warmed"] >= 1
        assert rec["cold"]["cache_hits"] == 0
        assert rec["warm"]["cache_hits"] > 0
        assert rec["hit_observations"] > 0
        assert "gate_ok" in rec and "gate_reason" in rec
        assert rec["ttfi_speedup"] == pytest.approx(
            rec["cold"]["ttfi_s"] / rec["warm"]["ttfi_s"], rel=1e-2)


def _ss_record(allclose=True, argmax=1.0, max_err=3e-8, scaleout=2.8,
               hit3=3, failovers=1, nonshed=1.0):
    return {
        "n_devices": 8, "threads": 6, "requests_per_storm": 90,
        "batch_delay_ms": 20.0,
        "parity": {"mesh_shape": {"data": 1, "model": 8},
                   "param_spec": "auto(model)", "allclose": allclose,
                   "argmax_match_rate": argmax, "max_abs_err": max_err},
        "single_replica": {"offered": 90, "ok": 90, "shed": 0,
                           "failed": 0, "throughput_rps": 46.0,
                           "p50_ms": 129.0, "p99_ms": 133.0,
                           "replicas_hit": 1},
        "fleet3": {"offered": 90, "ok": 90, "shed": 0, "failed": 0,
                   "throughput_rps": 46.0 * scaleout, "p50_ms": 45.0,
                   "p99_ms": 53.0, "replicas_hit": hit3},
        "scaleout": scaleout,
        "kill_drill": {"offered": 90, "ok": int(round(88 * nonshed)),
                       "shed": 2, "failed": 90 - 2 - int(round(
                           88 * nonshed)),
                       "throughput_rps": 98.0, "p50_ms": 65.0,
                       "p99_ms": 78.0, "replicas_hit": 3,
                       "failovers": failovers,
                       "nonshed_success_rate": nonshed},
    }


class TestCheckShardedServing:
    """Gate logic for the sharded_serving metric: the mesh-sharded deploy
    must be decision-identical to single-device, the 3-replica router
    must actually spread and buy >= 2x throughput over one replica, and
    killing a replica mid-storm must lose nothing (100% non-shed success
    via one failover retry)."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_sharded_serving(_ss_record())
        assert ok, reason

    def test_rejects_diverging_sharded_logits(self):
        ok, reason = bench.check_sharded_serving(
            _ss_record(allclose=False, max_err=0.3))
        assert not ok
        assert "diverges" in reason

    def test_rejects_changed_decisions(self):
        # logits within tolerance but a flipped argmax is a served
        # wrong answer, whatever the float error
        ok, reason = bench.check_sharded_serving(_ss_record(argmax=0.75))
        assert not ok
        assert "diverges" in reason

    def test_rejects_insufficient_scaleout(self):
        ok, reason = bench.check_sharded_serving(_ss_record(scaleout=1.5))
        assert not ok
        assert "scaling the fleet out" in reason

    def test_boundary_at_two_x(self):
        ok, _ = bench.check_sharded_serving(_ss_record(scaleout=2.01))
        assert ok
        ok, _ = bench.check_sharded_serving(_ss_record(scaleout=1.99))
        assert not ok

    def test_rejects_unspread_storm(self):
        # a ratio measured against a router that piled everything onto
        # one replica proves nothing about scale-out
        ok, reason = bench.check_sharded_serving(_ss_record(hit3=1))
        assert not ok
        assert "never spread" in reason

    def test_rejects_unexercised_kill_drill(self):
        ok, reason = bench.check_sharded_serving(_ss_record(failovers=0))
        assert not ok
        assert "untested" in reason

    def test_rejects_lost_requests_on_failover(self):
        ok, reason = bench.check_sharded_serving(
            _ss_record(nonshed=0.977))
        assert not ok
        assert "losing requests" in reason

    def test_custom_min_scaleout(self):
        ok, _ = bench.check_sharded_serving(_ss_record(scaleout=1.6),
                                            min_scaleout=1.5)
        assert ok

    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU. The deterministic legs ARE
        asserted in CI: sharded-vs-single-device parity, the router
        spreading over all 3 replicas, and the kill drill's zero lost
        requests with a recorded failover. The 2x throughput leg (a
        ratio of two CPU wall times) is evaluated and recorded, not
        judged here."""
        import jax
        import jax.numpy as jnp

        rec = bench.bench_sharded_serving(jax, jnp, tiny=True)
        assert rec["parity"]["allclose"]
        assert rec["parity"]["argmax_match_rate"] == 1.0
        assert rec["fleet3"]["replicas_hit"] == 3
        assert rec["kill_drill"]["failovers"] >= 1
        assert rec["kill_drill"]["nonshed_success_rate"] == 1.0
        assert rec["kill_drill"]["failed"] == 0
        assert "gate_ok" in rec and "gate_reason" in rec
        ok, reason = bench.check_sharded_serving(rec, min_scaleout=0.0)
        assert ok, reason


def _fr_record(baseline_p99=80.0, faulted_p99=160.0, failed=0,
               baseline_failed=0, injected=30, extra=30, launched=10,
               ejections=1, readmissions=1, ratio=0.5, burst=10.0):
    offered = 90
    return {
        "threads": 6, "requests_per_storm": offered,
        "batch_delay_ms": 20.0, "fault_rate": 0.2,
        "outlier_delay_ms": 200.0,
        "budget": {"ratio": ratio, "burst": burst},
        "baseline": {"offered": offered, "ok": offered - baseline_failed,
                     "shed": 0, "failed": baseline_failed,
                     "throughput_rps": 80.0, "p50_ms": 60.0,
                     "p99_ms": baseline_p99, "replicas_hit": 3},
        "faulted": {"offered": offered, "ok": offered - failed,
                    "shed": 0, "failed": failed, "throughput_rps": 60.0,
                    "p50_ms": 70.0, "p99_ms": faulted_p99,
                    "replicas_hit": 3, "injected": injected,
                    "attempts": offered + extra,
                    "extra_dispatches": extra,
                    "hedges": {"launched": launched, "won": 5,
                               "suppressed": 1},
                    "budget_denials": 1},
        "p99_ratio": round(faulted_p99 / baseline_p99, 3),
        "outlier": {"url": "http://127.0.0.1:9999",
                    "ejections": ejections,
                    "readmissions": readmissions},
    }


class TestCheckFleetResilience:
    """Gate logic for the fleet_resilience metric: under a 20% injected
    dispatch-fault rate plus one 10x-latency outlier, the router must
    lose zero non-shed requests, hold p99 <= 3x the fault-free storm,
    keep hedge+retry overhead inside the token budget, and eject then
    probe-re-admit the outlier."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_fleet_resilience(_fr_record())
        assert ok, reason

    def test_rejects_zero_injected_faults(self):
        ok, reason = bench.check_fleet_resilience(_fr_record(injected=0))
        assert not ok
        assert "untested" in reason

    def test_rejects_dirty_baseline(self):
        # a fault-free storm that drops requests invalidates the p99
        # yardstick (and means the fleet is broken without faults)
        ok, reason = bench.check_fleet_resilience(
            _fr_record(baseline_failed=1))
        assert not ok
        assert "yardstick" in reason

    def test_rejects_lost_requests(self):
        ok, reason = bench.check_fleet_resilience(_fr_record(failed=1))
        assert not ok
        assert "dropping traffic" in reason

    def test_rejects_unbounded_p99_and_boundary(self):
        ok, reason = bench.check_fleet_resilience(
            _fr_record(faulted_p99=241.0))
        assert not ok
        assert "tail" in reason
        ok, _ = bench.check_fleet_resilience(_fr_record(faulted_p99=239.0))
        assert ok

    def test_rejects_overbudget_dispatch_and_boundary(self):
        # allowance = 0.5 * 90 offered + 10 burst = 55
        ok, reason = bench.check_fleet_resilience(_fr_record(extra=56))
        assert not ok
        assert "unbounded" in reason
        ok, _ = bench.check_fleet_resilience(_fr_record(extra=55))
        assert ok

    def test_rejects_storm_that_never_hedged(self):
        ok, reason = bench.check_fleet_resilience(_fr_record(launched=0))
        assert not ok
        assert "hedging path is untested" in reason

    def test_rejects_unejected_outlier(self):
        ok, reason = bench.check_fleet_resilience(_fr_record(ejections=0))
        assert not ok
        assert "never ejected" in reason

    def test_rejects_permanent_ejection(self):
        ok, reason = bench.check_fleet_resilience(
            _fr_record(readmissions=0))
        assert not ok
        assert "permanent" in reason

    def test_custom_max_ratio(self):
        rec = _fr_record(faulted_p99=320.0)
        ok, _ = bench.check_fleet_resilience(rec, max_p99_ratio=5.0)
        assert ok

    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU. The deterministic legs ARE
        asserted in CI: faults fired, zero lost requests in both storms,
        hedges launched, the outlier ejected and probe-re-admitted, and
        dispatch overhead inside the configured budget. The 3x p99
        ratio (two CPU latencies) is evaluated and recorded, not judged
        here."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.common import faults as faults_mod

        rec = bench.bench_fleet_resilience(jax, jnp, tiny=True)
        assert not faults_mod.active()  # bench disarmed everything
        assert rec["faulted"]["injected"] > 0
        assert rec["baseline"]["failed"] == 0
        assert rec["faulted"]["failed"] == 0
        assert rec["faulted"]["hedges"]["launched"] >= 1
        allowance = (rec["budget"]["ratio"] * rec["faulted"]["offered"]
                     + rec["budget"]["burst"])
        assert rec["faulted"]["extra_dispatches"] <= allowance
        assert rec["outlier"]["ejections"] >= 1
        assert rec["outlier"]["readmissions"] >= 1
        assert "gate_ok" in rec and "gate_reason" in rec
        ok, reason = bench.check_fleet_resilience(
            rec, max_p99_ratio=float("inf"))
        assert ok, reason


def _op_record(storm_ok=40, status=200, echoed="ab" * 16,
               kinds=("hedge", "primary"), subtree=(
                   "inference/dispatch", "inference/ride",
                   "serving/admission", "serving/predict",
                   "serving/request"),
               checked=4, missing=0, max_diff=0.0, rows=3,
               consistent=True):
    return {
        "replicas": 3,
        "storm_requests": 40,
        "storm_ok": storm_ok,
        "percentile_parity": {
            "series_checked": checked,
            "series_missing": missing,
            "max_abs_diff": max_diff,
        },
        "signals": {
            "replica_rows": rows,
            "fleet_ready": rows,
            "rollup_consistent": consistent,
        },
        "stitched": {
            "status": status,
            "trace_id": "ab" * 16,
            "echoed_trace_id": echoed,
            "attempt_kinds": sorted(kinds),
            "outcomes": ["abandoned", "ok"],
            "replicas_stitched": 2,
            "winner_subtree": sorted(subtree),
        },
    }


class TestCheckObservabilityPlane:
    """Gate logic for the observability_plane metric: a hedged predict
    through the real HTTP front door must yield ONE stitched trace
    (both attempt spans + the winner's server-side subtree), fleet
    percentiles must be bucket-exact vs the pooled per-replica data,
    and /fleet/signals must list every replica with a self-consistent
    rollup."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_observability_plane(_op_record())
        assert ok, reason

    def test_rejects_lossy_storm(self):
        ok, reason = bench.check_observability_plane(
            _op_record(storm_ok=39))
        assert not ok
        assert "unhealthy" in reason

    def test_rejects_failed_hedged_predict(self):
        ok, reason = bench.check_observability_plane(
            _op_record(status=503))
        assert not ok
        assert "503" in reason

    def test_rejects_dropped_trace_context(self):
        ok, reason = bench.check_observability_plane(
            _op_record(echoed="cd" * 16))
        assert not ok
        assert "trace context was dropped" in reason

    def test_rejects_missing_attempt_span(self):
        ok, reason = bench.check_observability_plane(
            _op_record(kinds=("primary",)))
        assert not ok
        assert "hedge" in reason
        ok, reason = bench.check_observability_plane(
            _op_record(kinds=("hedge", "retry")))
        assert not ok

    def test_rejects_unstitched_winner_subtree(self):
        ok, reason = bench.check_observability_plane(
            _op_record(subtree=("serving/request", "serving/admission")))
        assert not ok
        assert "inference/dispatch" in reason

    def test_rejects_empty_parity_check(self):
        ok, reason = bench.check_observability_plane(
            _op_record(checked=0))
        assert not ok
        assert "no histogram series" in reason

    def test_rejects_missing_merged_series(self):
        ok, reason = bench.check_observability_plane(
            _op_record(missing=1))
        assert not ok
        assert "missing from the fleet" in reason

    def test_rejects_inexact_percentiles(self):
        # ANY drift fails: the merge is bucket addition, not estimation
        ok, reason = bench.check_observability_plane(
            _op_record(max_diff=1e-9))
        assert not ok
        assert "not exact" in reason

    def test_rejects_incomplete_signals_membership(self):
        ok, reason = bench.check_observability_plane(_op_record(rows=2))
        assert not ok
        assert "expected 3" in reason

    def test_rejects_inconsistent_rollup(self):
        ok, reason = bench.check_observability_plane(
            _op_record(consistent=False))
        assert not ok
        assert "rollup" in reason

    @pytest.mark.slow
    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU over real HTTP: storm,
        percentile parity, signals rollup, and the forced-hedge
        stitched trace are all deterministic legs — the gate is
        asserted, not just recorded. Slow-marked like the other fleet
        acceptance drills: the same measurement gates `python bench.py`
        via main(), and the gate logic itself is pinned by the
        fabricated-record tests above."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.common import faults as faults_mod
        from deeplearning4j_tpu.common.metrics import registry

        prev = registry().enabled
        rec = bench.bench_observability_plane(jax, jnp, tiny=True)
        assert registry().enabled == prev  # restored
        assert not faults_mod.active()     # hedge fault disarmed
        assert rec["storm_ok"] == rec["storm_requests"]
        assert rec["percentile_parity"]["series_checked"] >= 1
        assert rec["percentile_parity"]["max_abs_diff"] == 0.0
        assert rec["signals"]["replica_rows"] == rec["replicas"]
        st = rec["stitched"]
        assert st["echoed_trace_id"] == st["trace_id"]
        assert {"hedge", "primary"} <= set(st["attempt_kinds"])
        assert rec["gate_ok"], rec["gate_reason"]


class TestScannedStepEndToEnd:
    def test_tiny_scan_chain_produces_sane_record(self):
        """The full measurement path on CPU: scanned step, median-of-5,
        gate evaluation — the losses must actually move."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models import bert

        config = bert.BertConfig.tiny()
        B, T = 4, 16
        rng = np.random.RandomState(0)
        batch = {
            "input_ids": jnp.asarray(
                rng.randint(0, config.vocab_size, (B, T)), jnp.int32),
            "labels": jnp.asarray(
                np.where(rng.rand(B, T) < 0.15,
                         rng.randint(0, config.vocab_size, (B, T)), -100),
                jnp.int32),
            "attention_mask": jnp.ones((B, T), jnp.int32),
        }
        fpt = bert.flops_per_token(config)
        rec = bench._measure_bert_variant(
            jax, jnp, bert, config, batch, B, T, 4, {"remat": False},
            fpt, peak=0.0)
        assert rec["sane"], rec["reason"]
        assert rec["loss_last"] < rec["loss_first"]
        assert rec["samples_per_sec"] > 0


def _sa_record(lint_seconds=2.5, findings=0, inversions=0,
               on_sps=990.0, off_sps=1000.0):
    return {
        "lint_seconds": lint_seconds,
        "lint_modules": 168,
        "lint_findings": findings,
        "lint_baselined": 11,
        "lock_off_sps": off_sps,
        "lock_on_sps": on_sps,
        "lock_overhead_frac": round(1.0 - on_sps / off_sps, 4),
        "lock_inversions": inversions,
        "request_count": 32,
    }


class TestCheckStaticAnalysis:
    """Gate logic for the static_analysis metric: the dl4jlint pass must
    fit the CI budget (< 30 s) and come back green, and the DL105
    runtime lock-order tracker must cost < 3% serving throughput when
    armed (and record zero inversions on the healthy serving path)."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_static_analysis(_sa_record())
        assert ok, reason

    def test_rejects_slow_lint(self):
        ok, reason = bench.check_static_analysis(
            _sa_record(lint_seconds=31.0))
        assert not ok
        assert "budget" in reason

    def test_rejects_unbaselined_findings(self):
        ok, reason = bench.check_static_analysis(_sa_record(findings=2))
        assert not ok
        assert "lint-green" in reason

    def test_rejects_recorded_inversions(self):
        ok, reason = bench.check_static_analysis(_sa_record(inversions=1))
        assert not ok
        assert "inversion" in reason

    def test_rejects_expensive_tracker(self):
        ok, reason = bench.check_static_analysis(
            _sa_record(on_sps=960.0, off_sps=1000.0))
        assert not ok
        assert "near-zero-cost" in reason

    def test_boundary_at_three_percent(self):
        ok, _ = bench.check_static_analysis(
            _sa_record(on_sps=970.1, off_sps=1000.0))
        assert ok
        ok, _ = bench.check_static_analysis(
            _sa_record(on_sps=969.0, off_sps=1000.0))
        assert not ok

    def test_custom_budgets(self):
        ok, _ = bench.check_static_analysis(
            _sa_record(lint_seconds=31.0), max_seconds=60.0)
        assert ok
        ok, _ = bench.check_static_analysis(
            _sa_record(on_sps=960.0), max_overhead=0.05)
        assert ok

    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU: the lint pass runs over
        the real package (green) and the tracker on/off serving
        measurement records no inversions. The lint's seconds and the 3%
        overhead leg are CPU times: evaluated and recorded; the
        deterministic legs are hard asserts."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.common import locks

        before = locks.lock_check_enabled()
        rec = bench.bench_static_analysis(jax, jnp, tiny=True)
        assert rec["lint_findings"] == 0
        assert rec["lint_modules"] > 150
        assert rec["lint_seconds"] > 0
        assert rec["lock_inversions"] == 0
        assert rec["lock_off_sps"] > 0 and rec["lock_on_sps"] > 0
        assert "gate_ok" in rec and "gate_reason" in rec
        # the bench restored the tracker to the suite's state
        assert locks.lock_check_enabled() == before


def _fcs_record(remote_entries=4, live=0, hits=4, buckets=4,
                cold_ttr=0.12, warm_ttr=0.1):
    return {
        "remote_entries": remote_entries, "remote_bytes": 4096,
        "seed": {"ttr_s": 0.9, "buckets_warmed": buckets,
                 "live_compiles": buckets, "hit_compiles": 0,
                 "store_hits": 0},
        "warm_restart": {"ttr_s": warm_ttr, "buckets_warmed": buckets,
                         "live_compiles": 0, "hit_compiles": buckets,
                         "store_hits": buckets},
        "cold_join": {"ttr_s": cold_ttr, "buckets_warmed": buckets,
                      "live_compiles": live, "hit_compiles": hits,
                      "store_hits": hits},
        "ttr_ratio": round(cold_ttr / warm_ttr, 3),
    }


class TestCheckFleetColdStart:
    """Gate logic for the fleet_cold_start metric: a second replica with
    an empty local cache must warm entirely from the shared artifact
    store — zero live compiles — in <= 1.2x a fully-warm local
    restart's time-to-ready."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_fleet_cold_start(_fcs_record())
        assert ok, reason

    def test_rejects_empty_shared_store(self):
        # nothing published by the seed phase -> the cold join would be
        # measuring local recompiles, not the store
        ok, reason = bench.check_fleet_cold_start(
            _fcs_record(remote_entries=0))
        assert not ok
        assert "shared store" in reason

    def test_rejects_live_compiles_on_cold_join(self):
        ok, reason = bench.check_fleet_cold_start(
            _fcs_record(live=1, hits=3))
        assert not ok
        assert "live" in reason

    def test_rejects_partial_store_coverage(self):
        # a full ladder warmed but fewer store hits than buckets means
        # part of it came from somewhere other than the shared store
        ok, reason = bench.check_fleet_cold_start(
            _fcs_record(hits=2, buckets=4))
        assert not ok
        assert "somewhere other than" in reason

    def test_rejects_slow_join_and_boundary(self):
        ok, reason = bench.check_fleet_cold_start(
            _fcs_record(cold_ttr=0.15, warm_ttr=0.1))
        assert not ok
        assert "1.2" in reason
        ok, _ = bench.check_fleet_cold_start(
            _fcs_record(cold_ttr=0.119, warm_ttr=0.1))
        assert ok

    def test_custom_max_ratio(self):
        rec = _fcs_record(cold_ttr=0.15, warm_ttr=0.1)
        ok, _ = bench.check_fleet_cold_start(rec, max_ratio=2.0)
        assert ok

    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU against a real shared
        filesystem store. The deterministic legs are hard asserts (seed
        publishes, joiner records zero live compiles with every bucket a
        store hit); the 1.2x wall-clock ratio has wide margin on CPU
        since local and remote tiers are the same filesystem."""
        import jax
        import jax.numpy as jnp

        rec = bench.bench_fleet_cold_start(jax, jnp, tiny=True)
        for phase in ("seed", "warm_restart", "cold_join"):
            assert rec[phase]["ttr_s"] > 0
            assert rec[phase]["buckets_warmed"] >= 1
        assert rec["remote_entries"] > 0
        assert rec["seed"]["live_compiles"] > 0
        assert rec["cold_join"]["live_compiles"] == 0
        assert rec["cold_join"]["store_hits"] >= \
            rec["cold_join"]["buckets_warmed"]
        assert rec["ttr_ratio"] == pytest.approx(
            rec["cold_join"]["ttr_s"] / rec["warm_restart"]["ttr_s"],
            rel=1e-2)
        assert "gate_ok" in rec and "gate_reason" in rec


def _pr_record(match=True, reused=1120, expected=1120, cold_rows=1416,
               warm_rows=296, hits=5, requests=6, sess_match=True,
               ratio=10.2):
    return {
        "storm": {"decode_match": match, "requests": requests,
                  "reused_rows": reused, "expected_reused_rows": expected,
                  "prefill_rows": warm_rows,
                  "prefill_rows_cold": cold_rows,
                  "prefix_hits": hits},
        "session": {"decode_match": sess_match, "ttft_ratio": ratio,
                    "warm_ttft_s": 0.01, "cold_ttft_s": 0.01 * ratio},
    }


class TestCheckPrefixReuse:
    """Gate logic for the prefix_reuse metric: the radix cache must be
    invisible to the decoded function (token identity both phases), the
    storm must reuse EXACTLY the block-aligned common prefix per
    follower with the computed-row gap to prove single prefill, every
    follower must hit, and warm turn-2 TTFT must beat the cold
    full-history prefill by >= 5x."""

    def test_accepts_good_record(self):
        ok, reason = bench.check_prefix_reuse(_pr_record())
        assert ok, reason

    def test_rejects_storm_token_mismatch(self):
        ok, reason = bench.check_prefix_reuse(_pr_record(match=False))
        assert not ok
        assert "changed the decoded function" in reason

    def test_rejects_wrong_reused_rows(self):
        # a follower that re-prefilled its prefix (reused < expected) or
        # attached beyond the block-aligned run (reused > expected)
        ok, reason = bench.check_prefix_reuse(_pr_record(reused=1100))
        assert not ok
        assert "block-aligned common prefix" in reason
        ok, _ = bench.check_prefix_reuse(_pr_record(reused=1140))
        assert not ok

    def test_rejects_computed_row_gap_mismatch(self):
        # reused counter says 1120 but the engine actually computed the
        # same rows as the cold run: the "reuse" never skipped work
        ok, reason = bench.check_prefix_reuse(
            _pr_record(warm_rows=1416))
        assert not ok
        assert "prefilled exactly once" in reason

    def test_rejects_missed_followers(self):
        ok, reason = bench.check_prefix_reuse(_pr_record(hits=4))
        assert not ok
        assert "hit the cache" in reason

    def test_rejects_session_token_mismatch(self):
        ok, reason = bench.check_prefix_reuse(
            _pr_record(sess_match=False))
        assert not ok
        assert "decodes differently" in reason

    def test_rejects_insufficient_ttft_ratio_and_boundary(self):
        ok, reason = bench.check_prefix_reuse(_pr_record(ratio=4.9))
        assert not ok
        assert "5.0" in reason or "5x" in reason
        ok, _ = bench.check_prefix_reuse(_pr_record(ratio=5.01))
        assert ok

    def test_custom_min_ratio(self):
        ok, _ = bench.check_prefix_reuse(_pr_record(ratio=3.0),
                                         min_ratio=2.5)
        assert ok

    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU. The deterministic legs ARE
        asserted in CI: token identity in both phases, exact reused-row
        accounting (the storm prefills the common prefix once — the
        cold/warm computed-row gap equals the reused rows), and every
        follower hitting. The 5x TTFT leg (a ratio of two CPU times) is
        evaluated and recorded, not judged here."""
        import jax
        import jax.numpy as jnp

        rec = bench.bench_prefix_reuse(jax, jnp, tiny=True)
        assert rec["storm"]["decode_match"]
        assert rec["storm"]["reused_rows"] == \
            rec["storm"]["expected_reused_rows"]
        assert (rec["storm"]["prefill_rows_cold"]
                - rec["storm"]["prefill_rows"]) == \
            rec["storm"]["reused_rows"]
        assert rec["storm"]["prefix_hits"] == rec["storm"]["requests"] - 1
        assert rec["session"]["decode_match"]
        assert rec["session"]["ttft_ratio"] > 0
        assert "gate_ok" in rec and "gate_reason" in rec
        ok, reason = bench.check_prefix_reuse(rec, min_ratio=0.0)
        assert ok, reason


def _pd_record(identical=True, g_paged=1, g_flash=0, k_paged=0, k_flash=1,
               g_compiles=0, k_compiles=0, fused_dispatch=1, fused_err=2e-6,
               top1=1.0, platform="cpu", interpret=True, speedup=1.4):
    return {
        "platform": platform,
        "interpret": interpret,
        "gather": {"path": "paged", "tokens_per_sec": 100.0,
                   "steady_state_compiles": g_compiles,
                   "dispatch_paged": g_paged,
                   "dispatch_paged_flash": g_flash},
        "kernel": {"path": "paged_flash",
                   "tokens_per_sec": 100.0 * speedup,
                   "steady_state_compiles": k_compiles,
                   "dispatch_paged": k_paged,
                   "dispatch_paged_flash": k_flash},
        "token_identical": identical,
        "speedup_vs_gather": speedup,
        "fused_dequant": {"k": 512, "n": 512, "max_abs_err": fused_err,
                          "top1_agreement": top1,
                          "dispatch_fused": fused_dispatch},
    }


class TestCheckPallasDecode:
    """Gate logic for the pallas_decode metric: token-identical greedy
    streams between the gather and paged-flash phases, dispatch counters
    proving which path compiled each phase, zero steady-state recompiles,
    the fused dequant-matmul within the quant deploy-gate thresholds, and
    (accelerators only) the kernel actually beating the gather."""

    def test_accepts_good_cpu_record(self):
        ok, reason = bench.check_pallas_decode(_pd_record())
        assert ok, reason

    def test_rejects_token_divergence(self):
        ok, reason = bench.check_pallas_decode(_pd_record(identical=False))
        assert not ok
        assert "drop-in" in reason

    def test_rejects_gather_phase_served_by_kernel(self):
        # the "gather baseline" that secretly compiled the kernel
        ok, reason = bench.check_pallas_decode(
            _pd_record(g_paged=1, g_flash=1))
        assert not ok
        assert "gather" in reason
        ok, _ = bench.check_pallas_decode(_pd_record(g_paged=0))
        assert not ok

    def test_rejects_kernel_phase_served_by_gather(self):
        # a kernel phase that silently fell back measures nothing
        ok, reason = bench.check_pallas_decode(
            _pd_record(k_flash=0, k_paged=1))
        assert not ok
        assert "paged-flash" in reason

    def test_rejects_steady_state_recompiles(self):
        ok, reason = bench.check_pallas_decode(_pd_record(k_compiles=2))
        assert not ok
        assert "recompiled" in reason
        ok, _ = bench.check_pallas_decode(_pd_record(g_compiles=1))
        assert not ok

    def test_rejects_fused_leg_that_never_fused(self):
        ok, reason = bench.check_pallas_decode(
            _pd_record(fused_dispatch=0))
        assert not ok
        assert "fallback against itself" in reason

    def test_rejects_fused_divergence_and_top1(self):
        ok, reason = bench.check_pallas_decode(_pd_record(fused_err=0.3))
        assert not ok
        assert "diverges" in reason
        ok, reason = bench.check_pallas_decode(_pd_record(top1=0.9))
        assert not ok
        assert "top-1" in reason

    def test_accelerator_speed_gate_and_boundary(self):
        # on hardware the kernel must pay for itself; CPU (interpret
        # mode) skips the speed leg but must say so
        ok, reason = bench.check_pallas_decode(
            _pd_record(platform="tpu", interpret=False, speedup=1.01))
        assert not ok
        assert "paying for itself" in reason
        ok, _ = bench.check_pallas_decode(
            _pd_record(platform="tpu", interpret=False, speedup=1.06))
        assert ok
        ok, _ = bench.check_pallas_decode(
            _pd_record(speedup=0.5))  # cpu: speed leg skipped
        assert ok
        ok, reason = bench.check_pallas_decode(_pd_record(interpret=False))
        assert not ok
        assert "interpret" in reason

    def test_custom_thresholds(self):
        rec = _pd_record(platform="tpu", interpret=False, speedup=1.02)
        ok, _ = bench.check_pallas_decode(rec, min_speedup=1.01)
        assert ok

    def test_tiny_live_measurement_passes_gate(self):
        """The full metric end-to-end on CPU: the gather phase runs the
        XLA block-table gather, the kernel phase the same greedy loop
        through the interpret-mode Pallas kernel. The deterministic legs
        ARE asserted in CI (token identity, dispatch-counter proof of
        which path compiled each phase, zero steady-state recompiles,
        fused-dequant parity); the throughput leg is informational on
        CPU."""
        import jax
        import jax.numpy as jnp

        rec = bench.bench_pallas_decode(jax, jnp, tiny=True)
        assert rec["token_identical"]
        assert rec["interpret"]
        assert rec["gather"]["dispatch_paged"] >= 1
        assert rec["gather"]["dispatch_paged_flash"] == 0
        assert rec["kernel"]["dispatch_paged_flash"] >= 1
        assert rec["kernel"]["dispatch_paged"] == 0
        assert rec["gather"]["steady_state_compiles"] == 0
        assert rec["kernel"]["steady_state_compiles"] == 0
        assert rec["fused_dequant"]["max_abs_err"] <= 0.25
        assert rec["fused_dequant"]["dispatch_fused"] >= 1
        assert rec["gate_ok"], rec["gate_reason"]
