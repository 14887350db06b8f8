"""Benchmark suite: BASELINE.md configs on one TPU chip.

Headline (the ONE JSON line's metric): BERT-base MLM training samples/sec/
chip + MFU (BASELINE config 3; north-star acceptance 35% MFU → vs_baseline
1.0). Extra keys cover the other single-chip BASELINE configs:
  - resnet50_imgs_per_sec (config 2, zoo ResNet-50 ComputationGraph)
  - lenet_imgs_per_sec    (config 1, LeNet-MNIST MultiLayerNetwork)
  - word2vec_words_per_sec(config 4, SGNS skip-gram round throughput)
  - flash_attn_speedup    (Pallas flash attention vs XLA attention)
  - inference_serving     (mixed-batch-size stream: bucketed
                           InferenceEngine vs naive exact-shape jit —
                           throughput, p50/p99 latency, compile counts)
  - telemetry_overhead    (bucketed serving throughput with the metrics
                           registry + spans on vs off; gated <3%)
  - cold_start            (time-to-first-inference + warmup wall-clock
                           for a restarted server, cold vs warm
                           persistent executable cache; gated >= 2x)
  - serving_overload      (admission control under synthetic overload:
                           admitted-request p99 + shed counts with the
                           shedder on vs off; gated: shedding keeps
                           admitted p99 within 3x of unloaded p99)
  - generative_decode     (autoregressive serving: tokens/sec + p99 TTFT
                           under mixed prompt lengths, KV-cached vs
                           full-recompute decode and continuous vs
                           per-request batching; gated: KV >= 3x,
                           continuous >= 1.5x, token-identical greedy,
                           zero steady-state recompiles)
  - serving_resilience    (self-healing under deterministic fault
                           injection: 5% dispatch faults + batcher
                           crashes; gated: >= 99% of non-poison requests
                           succeed, admitted p99 <= 3x fault-free, zero
                           engine-thread permadeaths, and the circuit
                           breaker re-closes within its probe window
                           after injection stops)
  - static_analysis       (dl4jlint full-package pass wall-clock — the
                           tier-1 gate must fit CI, < 30 s — plus the
                           DL105 lock-order tracker's serving-throughput
                           overhead, on vs off; gated < 3%)
  - sharded_serving       (sharded serving fleet: mesh-sharded deploy
                           parity vs single-device + FleetRouter
                           scale-out over 3 replicas; gated: identical
                           argmax, 3-replica throughput >= 2x one
                           replica, and a mid-storm replica kill keeps
                           non-shed success at 100% via one failover
                           retry)
Config 5 (multi-chip scaling) needs >1 chip; the driver's multichip dryrun
covers correctness, scaling numbers await real multi-chip hardware.

The reference publishes no numbers ("published": {}), so vs_baseline
reports progress against the 35%-MFU bar.
"""
import json
import os
import sys
import time

import numpy as np


#: Per-chip bf16 peak FLOP/s, keyed by ``jax.Device.device_kind`` (source:
#: Google Cloud TPU documentation, the system-architecture page of each
#: generation; "TPU v5 lite" is what a v5e chip reports). The CPU entry is
#: for BENCH_TINY rehearsals: no peak, so no MFU is ever derived off-chip.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v6 lite": 918e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "cpu": 0.0,
}


def _peak_flops(dev) -> float:
    """Per-chip bf16 peak FLOP/s of ``dev``. A device that is not in the
    table is an error, not a default: an MFU against a guessed peak is
    worse than none."""
    kind = getattr(dev, "device_kind", "")
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {kind!r} "
            f"(platform {dev.platform!r}); add it to PEAK_BF16_FLOPS "
            "with its source")
    return PEAK_BF16_FLOPS[kind]


# Hard ceiling on believable MFU for the headline: nothing this code can
# do runs the chip past ~80% of bf16 peak; any measurement above it is an
# artifact (a runtime that answers a repeated execute from a cache once
# produced a 2,989% "MFU"), never a speedup.
BERT_MFU_CEILING = 0.8


def check_bert_sanity(losses, mfu, max_mfu=BERT_MFU_CEILING):
    """(ok, reason): hard gates a BERT measurement must pass to be judged.

    - implied MFU must be physically possible (<= max_mfu of chip peak)
    - every timed dispatch's loss trajectory must be finite and actually
      moving: not all losses equal, and >= 80% of adjacent steps changing.
      (A single bitwise-repeated adjacent pair is legitimate for a
      plateaued f32 step; a flat or mostly-flat trajectory means the
      device never actually stepped — stale replay or a dead train step.)
    - no two dispatches may return identical trajectories: a repeated
      execute answered from a cache returns the previous dispatch's
      arrays verbatim, with a near-zero wall time that would otherwise
      poison the median

    ``losses``: one trajectory [n_steps] or a stack of per-dispatch
    trajectories [n_runs, n_steps].
    """
    if mfu > max_mfu:
        return False, (f"implied MFU {mfu:.4f} > ceiling {max_mfu}: "
                       "physically impossible, measurement artifact "
                       "(replayed execute?)")
    arr = np.asarray(losses, np.float64)
    trajs = arr[None, :] if arr.ndim == 1 else arr
    for i, l in enumerate(trajs):
        if l.size and not np.all(np.isfinite(l)):
            return False, (f"non-finite loss in chained-step trajectory "
                           f"(dispatch {i})")
        if l.size >= 2:
            diffs = np.diff(l)
            changed = int(np.count_nonzero(diffs))
            if changed == 0 or changed < 0.8 * diffs.size:
                return False, ("loss trajectory mostly flat across chained "
                               f"steps (dispatch {i}: {changed}/{diffs.size}"
                               " steps changed): training did not actually "
                               "advance")
    for i in range(len(trajs)):
        for j in range(i + 1, len(trajs)):
            if trajs[i].size and np.array_equal(trajs[i], trajs[j]):
                return False, (f"dispatches {i} and {j} returned identical "
                               "loss trajectories: replayed from cache, "
                               "not re-executed")
    return True, "ok"


def select_headline(variants):
    """Best *sane* variant wins the headline; no sane variant -> fail
    loudly rather than emit an unfalsifiable record."""
    sane = {k: v for k, v in variants.items() if v["sane"]}
    if not sane:
        raise RuntimeError(
            "no BERT variant passed the sanity gates; refusing to emit a "
            "judged record from insane measurements: "
            + "; ".join(f"{k}: {v['reason']}" for k, v in variants.items()))
    name = max(sane, key=lambda k: sane[k]["samples_per_sec"])
    return name, sane[name]


def _measure_bert_variant(jax, jnp, bert, config, batch, B, T, n_steps,
                          kw, fpt, peak):
    """Median-of-5 scan-chained timing for one train-step variant, with
    one remeasure retry if the sanity gate rejects the first attempt."""
    params = bert.init_params(jax.random.key(0), config)
    opt = bert.init_opt_state(params)
    step = bert.make_scanned_train_step(config, n_steps, mesh=None,
                                        learning_rate=1e-4, **kw)
    params, opt, losses = step(params, opt, batch, 0)  # compile + warm
    jax.block_until_ready(losses)
    it = n_steps
    for attempt in range(2):
        runs, trajs = [], []
        n_runs = 5  # median over 5: one hiccup cannot shift it
        for _ in range(n_runs):
            t0 = time.perf_counter()
            params, opt, losses = step(params, opt, batch, it)
            jax.block_until_ready(losses)
            runs.append(time.perf_counter() - t0)
            trajs.append(np.asarray(losses, np.float64))
            it += n_steps
        runs.sort()
        dt = runs[n_runs // 2]
        sps = n_steps * B / dt
        mfu = sps * T * fpt / peak if peak else 0.0
        ok, reason = check_bert_sanity(np.stack(trajs), mfu)
        if ok or attempt == 1:
            del params, opt
            return {
                "samples_per_sec": sps, "mfu": mfu, "sane": ok,
                "reason": reason, "variant": kw,
                "loss_first": float(trajs[0][0]),
                "loss_last": float(trajs[-1][-1]),
                "spread_pct": round(100.0 * (runs[-1] - runs[0]) / dt, 2),
            }


def bench_bert(jax, jnp, tiny, peak):
    from deeplearning4j_tpu.models import bert

    if tiny:
        config = bert.BertConfig.tiny()
        B, T = 8, 32
    else:
        config = bert.BertConfig.base()
        # B=128 without remat fits single-chip HBM and maximizes MXU
        # occupancy (measured: 59% MFU vs 40% at B=32+remat)
        B, T = 128, 128
    n_steps = 5 if tiny else 20

    rng = np.random.RandomState(0)
    batch = {
        "input_ids": jnp.asarray(rng.randint(0, config.vocab_size, (B, T)),
                                 jnp.int32),
        "labels": jnp.asarray(
            np.where(rng.rand(B, T) < 0.15,
                     rng.randint(0, config.vocab_size, (B, T)), -100),
            jnp.int32),
        "attention_mask": jnp.ones((B, T), jnp.int32),
    }

    fpt = bert.flops_per_token(config)
    variants = {}
    for name, kw in (("xla", {"remat": False}),
                     ("flash", {"remat": False, "use_flash": True})):
        try:
            variants[name] = _measure_bert_variant(
                jax, jnp, bert, config, batch, B, T, n_steps, kw, fpt, peak)
        except Exception as e:
            variants[name] = {"sane": False, "samples_per_sec": 0.0,
                              "mfu": 0.0, "variant": kw,
                              "reason": f"error: {type(e).__name__}: {e}"}
    return {"B": B, "T": T, "config": config, "n_chained": n_steps,
            "flops_per_token": fpt, "variants": variants}


def _zoo_batches(rng, n, B, in_shape, num_classes):
    """Device-resident DataSets: re-staging the raw batches host->device
    inside the timed fit() would swamp the measurement for small
    models."""
    import jax.numpy as _jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    out = []
    for _ in range(n):
        x = rng.randn(B, *in_shape).astype(np.float32)
        y = np.zeros((B, num_classes), np.float32)
        y[np.arange(B), rng.randint(0, num_classes, B)] = 1.0
        out.append(DataSet(_jnp.asarray(x), _jnp.asarray(y)))
    return out


def _fit_throughput(jax, net, batches, B, epochs):
    """samples/sec through the layer-API scanned fit fast path."""
    net.fit(batches, num_epochs=1)  # compile + warm
    t0 = time.perf_counter()
    net.fit(batches, num_epochs=epochs)
    # fit syncs score_value at the end, so the clock covers all device work
    dt = time.perf_counter() - t0
    return epochs * len(batches) * B / dt


# Training FLOPs/image at 224x224, 1000 classes: 3x forward (bwd ~= 2x fwd),
# forward = 2 x MACs (the peak-FLOPs table counts an FMA as 2, so the
# numerator must too). MACs are the canonical per-architecture counts
# (torchvision/fvcore-verified): ResNet-50 4.089 GMAC, VGG16 15.47 GMAC.
VISION_TRAIN_FLOPS_PER_IMG = {
    "resnet50": 3 * 2 * 4.089e9,
    "vgg16": 3 * 2 * 15.47e9,
}


def bench_resnet50(jax, jnp, tiny):
    """Layer-API ResNet-50 training throughput (BASELINE config 2).

    bf16 body + scanned fit: one dispatch per epoch over device-resident
    batches, matching how the reference's PerformanceListener samples
    steady-state fit() throughput."""
    from deeplearning4j_tpu.zoo import ResNet50

    num_classes = 10 if tiny else 1000
    B = 4 if tiny else 128  # measured: B=128 2265 img/s vs B=64 2042 vs B=32/f32 221
    side = 64 if tiny else 224
    net = ResNet50(num_classes=num_classes, input_shape=(3, side, side),
                   dtype="bfloat16").init_model()
    batches = _zoo_batches(np.random.RandomState(0), 2 if tiny else 4, B,
                           (3, side, side), num_classes)
    return _fit_throughput(jax, net, batches, B, epochs=2 if tiny else 6)


def bench_vgg16(jax, jnp, tiny):
    """Layer-API VGG16 training throughput (BASELINE config 2, second
    model)."""
    from deeplearning4j_tpu.zoo import VGG16

    num_classes = 10 if tiny else 1000
    B = 4 if tiny else 64  # VGG16 activations are fatter than ResNet's
    side = 32 if tiny else 224
    net = VGG16(num_classes=num_classes, input_shape=(3, side, side),
                dtype="bfloat16").init_model()
    batches = _zoo_batches(np.random.RandomState(0), 2 if tiny else 4, B,
                           (3, side, side), num_classes)
    return _fit_throughput(jax, net, batches, B, epochs=2 if tiny else 6)


def bench_seq2seq(jax, jnp, tiny):
    """Seq2Seq LSTM teacher-forcing training samples/sec (BASELINE config 4,
    second metric — reference deeplearning4j-nlp Seq2Seq LSTM)."""
    from deeplearning4j_tpu.models import seq2seq

    c = (seq2seq.Seq2SeqConfig.tiny() if tiny
         else seq2seq.Seq2SeqConfig(vocab_size=8000, embed_dim=256,
                                    hidden=512))
    B, S = (8, 8) if tiny else (128, 32)
    rng = np.random.RandomState(0)
    src = jnp.asarray(rng.randint(2, c.vocab_size, (B, S)), jnp.int32)
    tgt = jnp.asarray(rng.randint(2, c.vocab_size, (B, S)), jnp.int32)
    batch = {"src": src,
             "tgt_in": jnp.concatenate(
                 [jnp.full((B, 1), c.bos_token, jnp.int32), tgt[:, :-1]], 1),
             "tgt_out": tgt}
    params = seq2seq.init_params(jax.random.key(0), c)
    opt = seq2seq.init_opt_state(params)
    step = seq2seq.make_train_step(c, learning_rate=1e-3)
    params, opt, loss = step(params, opt, batch, 0)
    jax.block_until_ready(loss)
    iters = 3 if tiny else 30
    t0 = time.perf_counter()
    for i in range(1, iters + 1):
        params, opt, loss = step(params, opt, batch, i)
    jax.block_until_ready(loss)
    return iters * B / (time.perf_counter() - t0)


def bench_lenet(jax, jnp, tiny):
    from deeplearning4j_tpu.zoo import LeNet

    net = LeNet(num_classes=10, input_shape=(1, 28, 28),
                dtype="bfloat16").init_model()
    B = 128
    # LeNet steps are microseconds; few big scanned epochs (not many small
    # ones) so remote-dispatch round-trips don't dominate the measurement
    batches = _zoo_batches(np.random.RandomState(0), 2 if tiny else 32, B,
                           (1, 28, 28), 10)
    return _fit_throughput(jax, net, batches, B, epochs=2 if tiny else 10)


def bench_word2vec(jax, jnp, tiny):
    """SGNS skip-gram round throughput (words/sec) via the nlp op."""
    from deeplearning4j_tpu.ops.registry import exec_op
    import jax as _jax

    vocab, dim = (1000, 64) if tiny else (30000, 128)
    B, K = 1024, 5
    rng = np.random.RandomState(0)
    syn0 = jnp.asarray(rng.randn(vocab, dim).astype(np.float32) * 0.1)
    syn1 = jnp.asarray(rng.randn(vocab, dim).astype(np.float32) * 0.1)
    target = jnp.asarray(rng.randint(0, vocab, B), jnp.int32)
    context = jnp.asarray(rng.randint(0, vocab, B), jnp.int32)
    neg = jnp.asarray(rng.randint(0, vocab, (B, K)), jnp.int32)

    from deeplearning4j_tpu.ops import nlp_ops
    raw = (nlp_ops.skipgram.__wrapped__
           if hasattr(nlp_ops.skipgram, "__wrapped__")
           else nlp_ops.skipgram)
    iters = 5 if tiny else 200

    # one dispatch for the whole chain: skipgram rounds are ~100us, so
    # per-call timing measures dispatch round-trips, not the op (same
    # pattern as bench_flash_attention)
    @_jax.jit
    def many(s0, s1):
        def body(carry, _):
            s0, s1 = carry
            s0, s1, loss = raw(s0, s1, target, context, neg)
            return (s0, s1), loss
        (s0, s1), losses = _jax.lax.scan(body, (s0, s1), None, length=iters)
        return s0, s1, losses[-1]

    s0, s1, loss = many(syn0, syn1)
    _jax.block_until_ready(loss)
    t0 = time.perf_counter()
    s0, s1, loss = many(syn0, syn1)
    _jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    return iters * B / dt


def _saved_residual_bytes(jax, net, data, labels):
    """Bytes of forward residuals the backward pass keeps alive (via
    jax.ad_checkpoint.saved_residuals, abstract eval only — no FLOPs): the
    activation footprint that remat exists to shrink. On CPU the XLA
    buffer-assignment peak can be pinned by conv-backward scratch that remat
    cannot touch, so this is the honest cross-backend remat metric."""
    # jax 0.9.0 exports only print_saved_residuals publicly
    from jax._src.ad_checkpoint import saved_residuals

    trainable = net._trainable(net._params)
    states = net._states(net._params)
    key = jax.random.key(0)

    def loss_of(tr):
        if hasattr(net, "_loss_with_bn"):  # MultiLayerNetwork
            return net._loss_with_bn(tr, states, data, labels, key)[0]
        params = net._merge_states(tr, states)  # ComputationGraph
        return net._compute_loss(params, data, labels, key)

    total = 0
    for res, _src in saved_residuals(loss_of, trainable):
        if hasattr(res, "shape") and hasattr(res, "dtype"):
            total += int(np.prod(res.shape or (1,))) * res.dtype.itemsize
    return total


def _train_step_peak_bytes(jax, net, x, y):
    """Peak device memory of ONE compiled train step, from XLA's own
    compiled-program memory analysis (temp + arguments + output) — exact,
    deterministic, and available on CPU; `memory_stats()` peaks are
    monotonic per-process so they can't compare variants within one run.
    Params are deep-copied because the step donates its inputs."""
    import jax.numpy as jnp

    def copy(t):
        return jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True), t)

    trainable = copy(net._trainable(net._params))
    states = copy(net._states(net._params))
    ustate = copy(net._updater_state)
    step = jax.jit(net._train_step_fn(), donate_argnums=net._DONATE)
    lowered = step.lower(trainable, states, ustate,
                         jnp.asarray(0, jnp.int32), x, y, jax.random.key(0))
    m = lowered.compile().memory_analysis()
    if m is None:
        raise RuntimeError("memory_analysis unsupported on this backend")
    return int(m.temp_size_in_bytes + m.argument_size_in_bytes
               + m.output_size_in_bytes)


def bench_train_memory(jax, jnp, tiny, accum=4):
    """Memory-scaled-training metric: peak train-step memory + samples/sec
    for the memory levers on vs off, at EQUAL effective batch size:

      - default:     remat="none",  grad_accum=1
      - remat:       remat="layer", grad_accum=1   (activation remat only)
      - remat_accum: remat="layer", grad_accum=4   (remat + micro-batching)

    Non-tiny runs the BASELINE ResNet-50 at 224px (the 0.28-MFU
    under-batched config this PR targets); tiny runs a compact CNN so the
    CI gate (tests/test_bench_gate.py) stays cheap. `hbm_peak_bytes` is
    additionally reported on backends with memory_stats()."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    if tiny:
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.conf.config import (
            InputType, NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        # activation-dominated regime (like ResNet-50 at 224): all-conv +
        # global pooling, so the memory levers' effect is visible at CI scale
        B, in_shape, num_classes, epochs = 16, (1, 32, 32), 10, 2

        def build():
            # deep enough that stored residuals (not one conv backward's
            # scratch) set the peak, and gelu so each layer keeps a
            # pre-activation the remat path gets to drop — the ResNet-50
            # memory shape at CI scale
            b = NeuralNetConfiguration.builder().seed(0).list()
            b.layer(L.ConvolutionLayer(n_in=1, n_out=8, kernel_size=(3, 3),
                                       activation="gelu"))
            for _ in range(5):
                b.layer(L.ConvolutionLayer(n_in=8, n_out=8,
                                           kernel_size=(3, 3),
                                           activation="gelu"))
            conf = (b.layer(L.GlobalPoolingLayer())
                    .layer(L.OutputLayer(n_in=8, n_out=num_classes))
                    .set_input_type(InputType.convolutional(32, 32, 1))
                    .build())
            return MultiLayerNetwork(conf).init()
    else:
        from deeplearning4j_tpu.zoo import ResNet50

        B, in_shape, num_classes, epochs = 128, (3, 224, 224), 1000, 3

        def build():
            return ResNet50(num_classes=num_classes,
                            input_shape=in_shape,
                            dtype="bfloat16").init_model()

    rng = np.random.RandomState(0)
    batches = _zoo_batches(rng, 2, B, in_shape, num_classes)

    variants = {"default": ("none", 1), "remat": ("layer", 1),
                "remat_accum": ("layer", accum)}
    out = {"batch": B, "effective_batch": B, "grad_accum": accum,
           "model": "resnet50" if not tiny else "tiny_cnn"}
    for name, (remat, k) in variants.items():
        net = build()
        net.conf.remat = remat
        net.conf.grad_accum = k
        data, labels = net._stage_batch(batches[0])
        peak = _train_step_peak_bytes(jax, net, data, labels)
        act = _saved_residual_bytes(jax, net, data, labels)
        sps = _fit_throughput(jax, net, batches, B, epochs=epochs)
        rec = {"peak_bytes": peak, "activation_bytes": act,
               "samples_per_sec": round(sps, 2)}
        stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
        if stats and "peak_bytes_in_use" in stats:
            rec["hbm_peak_bytes"] = int(stats["peak_bytes_in_use"])
        out[name] = rec
        del net
    out["remat_sps_ratio"] = round(
        out["remat"]["samples_per_sec"]
        / max(out["default"]["samples_per_sec"], 1e-9), 3)
    out["remat_activation_ratio"] = round(
        out["remat"]["activation_bytes"]
        / max(out["default"]["activation_bytes"], 1), 3)
    out["accum_peak_ratio"] = round(
        out["remat_accum"]["peak_bytes"]
        / max(out["default"]["peak_bytes"], 1), 3)
    ok, reason = check_train_memory(out)
    out["gate_ok"], out["gate_reason"] = ok, reason
    return out


def check_train_memory(rec, max_sps_regression=0.30):
    """(ok, reason): gates a train_memory record must pass.

    - remat must not regress samples/sec by more than `max_sps_regression`
      at equal batch size (rematerialization recomputes at most one extra
      forward, bounded by ~1/3 of step FLOPs — a bigger slowdown means the
      checkpoint boundaries are wrong)
    - remat must shrink the stored-residual (activation) footprint at equal
      batch — a remat that saves as much as it stores is a no-op
    - the accumulation path must report LOWER peak memory than full-batch
      at equal effective batch size (the whole point of the lever)
    """
    d = rec["default"]
    floor = (1.0 - max_sps_regression) * d["samples_per_sec"]
    if rec["remat"]["samples_per_sec"] < floor:
        return False, (
            f"remat samples/sec {rec['remat']['samples_per_sec']:.2f} < "
            f"{floor:.2f} ({(1 - max_sps_regression) * 100:.0f}% of default "
            f"{d['samples_per_sec']:.2f}): recompute cost exceeds the remat "
            "budget")
    if rec["remat"]["activation_bytes"] >= d["activation_bytes"]:
        return False, (
            f"remat stored residuals {rec['remat']['activation_bytes']} >= "
            f"default {d['activation_bytes']}: checkpointing saved no "
            "activations")
    if rec["remat_accum"]["peak_bytes"] >= d["peak_bytes"]:
        return False, (
            f"accum path peak {rec['remat_accum']['peak_bytes']} >= "
            f"full-batch peak {d['peak_bytes']} at equal effective batch: "
            "micro-batching saved no memory")
    return True, "ok"


def bench_inference_serving(jax, jnp, tiny):
    """Mixed-batch-size serving (north-star "heavy traffic" scenario):
    a request stream with K distinct batch sizes served (a) naively —
    every odd shape jits an exact executable inside the timed window, the
    pre-bucketing behavior — and (b) through the bucketed InferenceEngine
    after warmup(). Reports throughput, p50/p99 request latency, and the
    XLA compile count each policy pays (new compile counter)."""
    from deeplearning4j_tpu.common.environment import environment
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.runtime.inference import InferenceEngine

    n_in, hidden, n_out = (16, 32, 4) if tiny else (256, 1024, 64)
    max_batch = 8 if tiny else 32
    sizes = ([1, 3, 7, 5, 2, 6, 4, 8] if tiny
             else [1, 3, 7, 17, 5, 29, 2, 11, 23, 4, 31, 9])
    n_requests = len(sizes) * (2 if tiny else 8)

    def build():
        conf = (NeuralNetConfiguration.builder().seed(0).list()
                .layer(DenseLayer(n_in=n_in, n_out=hidden,
                                  activation="relu"))
                .layer(DenseLayer(n_in=hidden, n_out=hidden,
                                  activation="relu"))
                .layer(OutputLayer(n_in=hidden, n_out=n_out))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.RandomState(0)
    reqs = [jnp.asarray(rng.randn(sizes[i % len(sizes)], n_in)
                        .astype(np.float32)) for i in range(n_requests)]
    total_rows = sum(int(r.shape[0]) for r in reqs)

    env = environment()
    prev_bucketing = env.inference_bucketing()
    results = {}
    try:
        for mode in ("naive", "bucketed"):
            env.set_inference_bucketing(mode == "bucketed")
            env.reset_compile_count()
            net = build()
            if mode == "bucketed":
                eng = InferenceEngine(net, max_batch=max_batch)
                eng.warmup(reqs[0])
                run = eng.infer
            else:
                run = net.output
            lat = []
            t_all = time.perf_counter()
            for r in reqs:
                t0 = time.perf_counter()
                jax.block_until_ready(run(r).jax())
                lat.append(time.perf_counter() - t0)
            dt = time.perf_counter() - t_all
            results[mode] = {
                "throughput_sps": round(total_rows / dt, 2),
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
                "compiles": env.compile_count(),
            }
    finally:
        env.set_inference_bucketing(prev_bucketing)
        env.reset_compile_count()
    results["request_count"] = n_requests
    results["distinct_batch_sizes"] = len(set(sizes))
    results["max_batch"] = max_batch
    results["throughput_speedup"] = round(
        results["bucketed"]["throughput_sps"]
        / max(results["naive"]["throughput_sps"], 1e-9), 3)
    return results


def bench_telemetry_overhead(jax, jnp, tiny):
    """Cost of the telemetry subsystem on the serving hot path: bucketed
    InferenceEngine throughput over a mixed-size request stream with the
    metrics registry + spans enabled vs disabled (DL4J_TPU_METRICS),
    plus a third pass with a per-request trace context bound — the
    serving front end's request-scoped tracing (traceparent in,
    span-tree out) — to price the contextvar/span-id machinery.
    A fourth, fleet-level pass routes the same predict through a live
    2-replica FleetRouter (background polling + aggregator scraping on)
    with the whole observability plane armed vs off: attempt spans,
    traceparent forwarding, metrics aggregation and the replica-side
    decomposition must all ride inside the same near-zero-cost
    contract. `overhead_frac` and `fleet_overhead_frac` must both stay
    under the `check_telemetry_overhead` gate's 3%;
    `tracing_overhead_frac` is reported alongside them."""
    from deeplearning4j_tpu.common.environment import environment
    from deeplearning4j_tpu.common.tracing import (TraceContext,
                                                   new_trace_id, tracer,
                                                   use_context)
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.runtime.inference import InferenceEngine

    n_in, hidden, n_out = (16, 32, 4) if tiny else (256, 1024, 64)
    max_batch = 8 if tiny else 32
    sizes = [1, 3, 7, 5, 2, 6, 4, 8] if tiny \
        else [1, 3, 7, 17, 5, 29, 2, 11, 23, 4, 31, 9]
    n_requests = len(sizes) * (4 if tiny else 16)

    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_in=hidden, n_out=n_out))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    reqs = [jnp.asarray(rng.randn(sizes[i % len(sizes)], n_in)
                        .astype(np.float32)) for i in range(n_requests)]
    total_rows = sum(int(r.shape[0]) for r in reqs)

    reg = environment().metrics()
    prev_enabled = reg.enabled
    out = {"request_count": n_requests, "max_batch": max_batch}
    try:
        for mode in ("off", "on", "trace"):
            reg.set_enabled(mode != "off")
            eng = InferenceEngine(net, max_batch=max_batch)
            eng.warmup(reqs[0])
            runs = []
            for _ in range(5):
                t0 = time.perf_counter()
                if mode == "trace":
                    # one fresh trace context per request, like the HTTP
                    # front end binds from traceparent
                    for r in reqs:
                        with use_context(TraceContext(new_trace_id())):
                            jax.block_until_ready(eng.infer(r).jax())
                else:
                    for r in reqs:
                        jax.block_until_ready(eng.infer(r).jax())
                runs.append(time.perf_counter() - t0)
            runs.sort()
            out[f"metrics_{mode}_sps"] = round(
                total_rows / runs[len(runs) // 2], 2)
    finally:
        reg.set_enabled(prev_enabled)
        tracer().clear()
    out["overhead_frac"] = round(
        1.0 - out["metrics_on_sps"] / max(out["metrics_off_sps"], 1e-9), 4)
    out["tracing_overhead_frac"] = round(
        1.0 - out["metrics_trace_sps"] / max(out["metrics_off_sps"], 1e-9),
        4)

    # -- fleet pass: the observability plane armed vs off ----------------
    # two in-process replicas behind one router with background polling;
    # toggling the shared registry arms/disarms attempt spans, the
    # aggregator's scrape targets and the replicas' own instrumentation
    # at once — the routed request rate must not notice.
    from deeplearning4j_tpu.serving import ModelRegistry, ModelServer
    from deeplearning4j_tpu.serving.fleet import FleetRouter

    n_fleet_reqs = 40 if tiny else 120
    body = json.dumps(
        {"inputs": np.asarray(reqs[0]).tolist()}).encode()
    hdrs = [("Content-Type", "application/json")]
    members, router = [], None
    try:
        for _ in range(2):
            sreg = ModelRegistry(manifest_dir=None)
            sreg.deploy("bench", "v1", net, example=reqs[0],
                        max_batch=max_batch)
            srv = ModelServer(sreg, max_concurrent=4)
            members.append((sreg, srv, f"http://127.0.0.1:{srv.start()}"))
        router = FleetRouter([m[2] for m in members], poll_s=0.2,
                             timeout_s=30)
        router.poll_once()
        router.start_polling()

        def drive():
            for _ in range(n_fleet_reqs):
                router.route("POST", "/v1/models/bench/predict", body,
                             headers=hdrs, model="bench", timeout_s=30)

        drive()  # warm: ladder compiled, hedge samples, one poll cycle
        for mode in ("off", "on"):
            reg.set_enabled(mode == "on")
            runs = []
            for _ in range(5):
                t0 = time.perf_counter()
                drive()
                runs.append(time.perf_counter() - t0)
            runs.sort()
            out[f"fleet_{mode}_rps"] = round(
                n_fleet_reqs / runs[len(runs) // 2], 2)
    finally:
        reg.set_enabled(prev_enabled)
        tracer().clear()
        if router is not None:
            router.stop_polling()
        for sreg, srv, _ in members:
            try:
                srv.stop()
            except Exception:
                pass
            try:
                sreg.drain_all(save_manifests=False)
            except Exception:
                pass
    out["fleet_overhead_frac"] = round(
        1.0 - out["fleet_on_rps"] / max(out["fleet_off_rps"], 1e-9), 4)
    ok, reason = check_telemetry_overhead(out)
    out["gate_ok"], out["gate_reason"] = ok, reason
    return out


def bench_cold_start(jax, jnp, tiny):
    """Cold-start serving latency (the AOT compile pipeline's headline):
    time-to-first-inference and full-ladder warmup wall-clock for a
    freshly built server, cold vs warm persistent executable cache
    (DL4J_TPU_CACHE_DIR). A "restart" is simulated with fresh
    network/engine objects plus jax.clear_caches() — only the disk store
    survives between the phases, exactly like a process restart. The gate
    requires the warm restart's time-to-first-inference to be >= 2x
    faster than the cold one."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.common.environment import environment
    from deeplearning4j_tpu.common.metrics import registry
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.runtime import compile_cache
    from deeplearning4j_tpu.runtime.inference import InferenceEngine

    # deep enough that XLA compile time (what the cache removes), not
    # tracing (what it cannot), dominates the cold path
    n_in, hidden, n_out, depth = (16, 64, 4, 8) if tiny \
        else (256, 1024, 64, 12)
    max_batch = 8 if tiny else 32

    def build():
        b = NeuralNetConfiguration.builder().seed(0).list()
        b.layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
        for _ in range(depth - 2):
            b.layer(DenseLayer(n_in=hidden, n_out=hidden,
                               activation="relu"))
        conf = b.layer(OutputLayer(n_in=hidden, n_out=n_out)).build()
        return MultiLayerNetwork(conf).init()

    rng = np.random.RandomState(0)
    x = rng.randn(3, n_in).astype(np.float32)

    env = environment()
    from deeplearning4j_tpu.common.environment import SystemProperties
    prev_override = env.property_override(SystemProperties.CACHE_DIR)
    tmp = tempfile.mkdtemp(prefix="dl4j-cold-start-")
    rec = {"max_batch": max_batch, "model_depth": depth}
    try:
        env.set_cache_dir(tmp)
        compile_cache.reset_cache()
        for phase in ("cold", "warm"):
            jax.clear_caches()
            cc = compile_cache.cache()
            h0 = cc.stats["hits"] if cc else 0
            net = build()
            eng = InferenceEngine(net, max_batch=max_batch)
            t0 = time.perf_counter()
            jax.block_until_ready(eng.infer(jnp.asarray(x)).jax())
            ttfi = time.perf_counter() - t0
            t0 = time.perf_counter()
            warmed = eng.warmup(jnp.asarray(x))
            warmup_s = time.perf_counter() - t0
            rec[phase] = {
                "ttfi_s": round(ttfi, 4),
                "warmup_s": round(warmup_s, 4),
                "buckets_warmed": len(warmed),
                "cache_hits": (cc.stats["hits"] - h0) if cc else 0,
            }
    finally:
        if prev_override is None:
            env.clear_property(SystemProperties.CACHE_DIR)
        else:
            env.set_property(SystemProperties.CACHE_DIR, prev_override)
        compile_cache.reset_cache()
        shutil.rmtree(tmp, ignore_errors=True)
    rec["ttfi_speedup"] = round(
        rec["cold"]["ttfi_s"] / max(rec["warm"]["ttfi_s"], 1e-9), 3)
    rec["warmup_speedup"] = round(
        rec["cold"]["warmup_s"] / max(rec["warm"]["warmup_s"], 1e-9), 3)
    # the acceptance surface: /metrics must show hit-labeled compile events
    fam = registry().get("dl4j_compile_seconds")
    rec["hit_observations"] = sum(
        child.count() for key, child in (fam.children() if fam else [])
        if len(key) == 2 and key[1] == "hit")
    ok, reason = check_cold_start(rec)
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def check_cold_start(rec, min_speedup=2.0):
    """(ok, reason): gates a cold_start record must pass.

    - the warm phase must have actually loaded executables from the
      persistent store (cache_hits > 0) — a "speedup" without hits is
      measuring something else (e.g. leaked in-memory caches);
    - warm-cache time-to-first-inference must be >= `min_speedup` (2x)
      faster than the cold compile path — the acceptance bar of the AOT
      pipeline."""
    warm, cold = rec["warm"], rec["cold"]
    if warm.get("cache_hits", 0) <= 0:
        return False, ("warm phase recorded no executable-store hits: the "
                       "restart did not load from the persistent cache")
    speedup = cold["ttfi_s"] / max(warm["ttfi_s"], 1e-9)
    if speedup < min_speedup:
        return False, (
            f"warm-cache time-to-first-inference {warm['ttfi_s']:.4f}s is "
            f"only {speedup:.2f}x faster than cold {cold['ttfi_s']:.4f}s "
            f"(gate: >= {min_speedup}x): the executable cache is not "
            "removing the XLA compile from the restart path")
    return True, "ok"


def bench_serving_overload(jax, jnp, tiny):
    """Admission control under synthetic overload (the serving
    subsystem's headline): client threads hammer one deployed model far
    past its dispatch concurrency. With shedding ON the controller
    refuses arrivals past the high-water mark (429 + retry-after at the
    HTTP layer) so the admitted requests keep a bounded queue — their p99
    must stay within 3x of the unloaded p99 (check_serving_overload).
    With shedding OFF every arrival queues and the p99 grows with the
    backlog; the ratio between the two runs is the record's evidence that
    admission control, not luck, bounds the tail."""
    import sys
    import threading

    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.serving import (AdmissionController,
                                            ModelRegistry, ShedError)

    # sized so one dispatch is a few ms even on CPU: the 3x-of-unloaded
    # p99 gate must be judged against model service time, not against OS
    # scheduler jitter (which dominates sub-ms dispatches)
    n_in, hidden, n_out, depth, B = ((128, 1024, 8, 6, 32) if tiny
                                     else (256, 2048, 64, 8, 64))
    n_threads = 4 if tiny else 16
    per_thread = 30 if tiny else 60

    b = NeuralNetConfiguration.builder().seed(0).list()
    b.layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
    for _ in range(depth - 2):
        b.layer(DenseLayer(n_in=hidden, n_out=hidden, activation="relu"))
    conf = b.layer(OutputLayer(n_in=hidden, n_out=n_out)).build()
    net = MultiLayerNetwork(conf).init()
    registry = ModelRegistry(manifest_dir=None, retain=0)
    x = jnp.asarray(np.random.RandomState(0).randn(B, n_in)
                    .astype(np.float32))
    # max_delay_ms=0: this storm measures admission, so the coalesce
    # window would only add a constant to every latency
    registry.deploy("bench", "v1", net, example=x, max_batch=B,
                    max_delay_ms=0.0)
    # the p99 under GIL-contended client threads is dominated by the
    # interpreter's 5ms switch interval unless it is turned down — a real
    # serving process tunes this for the same reason
    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)

    def unloaded_floor():
        # unloaded p99: one client, no contention — the latency floor the
        # shedder is judged against (enough samples that the p99 actually
        # samples the dispatch tail, or the 3x gate judges against noise)
        lat = []
        for _ in range(100 if tiny else 200):
            t0 = time.perf_counter()
            jax.block_until_ready(registry.predict("bench", x).jax())
            lat.append(time.perf_counter() - t0)
        return float(np.percentile(lat, 99))

    def storm(shed: bool):
        big = 1 << 20  # effectively unbounded
        ctrl = AdmissionController(
            "bench", max_concurrent=1,
            queue_depth=2 if shed else big,
            high_water=1 if shed else big,
            default_timeout_s=None)
        admitted, shed_n, lock = [], [0], threading.Lock()

        def client():
            for _ in range(per_thread):
                t0 = time.perf_counter()
                try:
                    ctrl.run(lambda: jax.block_until_ready(
                        registry.predict("bench", x).jax()))
                except ShedError:
                    with lock:
                        shed_n[0] += 1
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    admitted.append(dt)

        threads = [threading.Thread(target=client)
                   for _ in range(n_threads)]
        t_all = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_all
        return {
            "completed": len(admitted),
            "shed": shed_n[0],
            "offered": n_threads * per_thread,
            "p50_ms": round(float(np.percentile(admitted, 50)) * 1e3, 3)
            if admitted else None,
            "p99_ms": round(float(np.percentile(admitted, 99)) * 1e3, 3)
            if admitted else None,
            "throughput_rps": round(len(admitted) / wall, 2),
        }

    try:
        # one remeasure retry, same as the BERT variants: a single
        # scheduler hiccup in the p99 tail must not fail the artifact
        for attempt in range(2):
            rec = {"unloaded_p99_ms": round(unloaded_floor() * 1e3, 3),
                   "threads": n_threads,
                   "shed_on": storm(True), "shed_off": storm(False)}
            ok, reason = check_serving_overload(rec)
            if ok or attempt == 1:
                break
    finally:
        sys.setswitchinterval(prev_switch)
        registry.drain_all(save_manifests=False)
    if rec["shed_on"]["p99_ms"] and rec["shed_off"]["p99_ms"]:
        rec["p99_ratio_off_over_on"] = round(
            rec["shed_off"]["p99_ms"] / rec["shed_on"]["p99_ms"], 3)
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def bench_generative_decode(jax, jnp, tiny):
    """Generative serving fast path (the KV-cache + continuous-batching
    headline): a tiny decoder-only causal LM decoded three ways.

    1. **KV-cached** — DecodeEngine: one jitted prefill per prompt bucket
       fills a preallocated slot cache, then one jitted single-token step
       per generated token (O(max_ctx) work/token).
    2. **Full recompute** — the pre-PR decode: every token re-runs the
       whole causal forward over the padded context (O(T²) total), one
       fixed-shape executable so the comparison isolates compute, not
       retracing.
    3. **Continuous vs per-request batching** — R concurrent requests
       with mixed prompt/generation lengths through the same engine:
       submitted together (requests join/leave the running decode batch
       per token) vs strictly one at a time. p99 TTFT is reported from
       the concurrent run.

    4. **Paged vs slab KV footprint** — the same mixed short/long
       workload through a paged engine (small blocks) and a slab-layout
       engine (block_size == max_ctx: one block per slot, the pre-paging
       reservation policy), sampling reserved KV rows per committed token
       at every emitted token. Reported as bytes-per-active-token and
       the paged/slab ratio.
    5. **Batched prefill** — a burst of mixed-length prompts ingested
       with same-bucket prompts coalesced into one prefill dispatch
       (prefill_batch=4) vs one dispatch per prompt (prefill_batch=1):
       prompt throughput, speedup, and batched p99 TTFT.
    6. **Speculative decoding** — a 1-layer weight-shared draft proposes
       k tokens per step, the target verifies them in one pass: greedy
       output must be token-identical to the engine's own
       non-speculative run; tokens/sec and draft acceptance rate are
       reported.

    The greedy KV-cached continuation must be token-identical to the
    recompute reference, and the steady-state run must record ZERO new
    compiles after warmup (one prefill executable per (bucket, batch
    rung) + one decode executable) — both gated by
    ``check_generative_decode`` alongside the >= 3x KV and >= 1.5x
    continuous-batching speedups, the <= 0.6x paged-vs-slab
    bytes-per-active-token ratio, the >= 1.3x batched-prefill prompt
    throughput, and speculative token-identity.
    """
    import dataclasses

    from deeplearning4j_tpu.common.environment import environment
    from deeplearning4j_tpu.models import causal_lm
    from deeplearning4j_tpu.runtime.generation import DecodeEngine

    if tiny:
        cfg = causal_lm.CausalLMConfig(
            vocab_size=128, hidden_size=128, num_layers=2, num_heads=4,
            intermediate_size=256, max_position_embeddings=256,
            dtype=jnp.float32)
        max_ctx, slots, gen_tokens = 256, 4, 32
        buckets = [16, 64]
        prompts = [4, 24, 8, 40, 12, 32]
        gens = [24, 8, 16, 12, 20, 8]
        kv_block = 16
        mix_lens = [16, 128, 16, 16, 128, 16, 16, 128]
        mix_gens = [16, 24, 12, 16, 16, 12, 16, 24]
        burst_lens = [14, 60, 9, 44, 16, 52, 12, 30,
                      7, 61, 15, 40, 11, 58, 13, 33]
        spec_k, spec_tokens = 3, 32
    else:
        cfg = causal_lm.CausalLMConfig(
            vocab_size=8192, hidden_size=512, num_layers=6, num_heads=8,
            intermediate_size=2048, max_position_embeddings=1024,
            dtype=jnp.bfloat16)
        max_ctx, slots, gen_tokens = 512, 8, 128
        buckets = [64, 256, 512]
        prompts = [16, 200, 48, 320, 64, 128, 24, 256]
        gens = [96, 32, 64, 48, 80, 24, 112, 40]
        kv_block = 32
        mix_lens = [32, 256, 32, 32, 256, 32, 32, 256]
        mix_gens = [32, 48, 24, 32, 32, 24, 32, 48]
        burst_lens = [30, 120, 20, 90, 34, 100, 26, 60,
                      16, 122, 32, 80, 24, 116, 28, 70]
        spec_k, spec_tokens = 3, 64
    model = causal_lm.CausalLM(cfg, seed=0)
    env = environment()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, 16).astype(np.int32)

    # -- full-recompute reference: one fixed-shape causal forward per
    # token over the padded context (greedy)
    fwd = jax.jit(lambda p, ids: causal_lm.forward(p, ids, cfg))
    ctx_pad = np.zeros((1, max_ctx), np.int32)
    ctx_pad[0, :prompt.size] = prompt
    jax.block_until_ready(fwd(model.params, jnp.asarray(ctx_pad)))  # warm

    def recompute_decode():
        ids = ctx_pad.copy()
        n = int(prompt.size)
        toks = []
        for _ in range(gen_tokens):
            logits = fwd(model.params, jnp.asarray(ids))
            tok = int(jnp.argmax(logits[0, n - 1]))
            toks.append(tok)
            if n < max_ctx:
                ids[0, n] = tok
            n += 1
        return toks

    engine = DecodeEngine(model, slots=slots, max_ctx=max_ctx,
                          prompt_buckets=buckets, kv_block_size=kv_block)
    engine.warmup()

    def kv_decode():
        res = engine.generate(prompt, max_tokens=gen_tokens,
                              eos_token=None).result()
        return res["tokens"]

    def timed(fn, runs=3):
        best_tokens, times = None, []
        for _ in range(runs):
            t0 = time.perf_counter()
            best_tokens = fn()
            times.append(time.perf_counter() - t0)
        times.sort()
        return best_tokens, times[len(times) // 2]

    rec = {"slots": slots, "max_ctx": max_ctx, "gen_tokens": gen_tokens,
           "prompt_buckets": list(engine.ladder)}
    for attempt in range(2):
        kv_toks, kv_dt = timed(kv_decode)
        rc_toks, rc_dt = timed(recompute_decode)
        rec["kv_cached"] = {"tokens_per_sec": round(gen_tokens / kv_dt, 2)}
        rec["recompute"] = {"tokens_per_sec": round(gen_tokens / rc_dt, 2)}
        rec["kv_speedup"] = round(rc_dt / kv_dt, 3)
        rec["decode_match"] = kv_toks == rc_toks

        # -- continuous vs per-request batching over mixed lengths
        reqs = [(rng.randint(0, cfg.vocab_size, p).astype(np.int32), g)
                for p, g in zip(prompts, gens)]
        total = sum(g for _, g in reqs)

        env.reset_compile_count()
        t0 = time.perf_counter()
        futs = [engine.generate(p, max_tokens=g, eos_token=None)
                for p, g in reqs]
        results = [f.result() for f in futs]
        cont_dt = time.perf_counter() - t0
        rec["steady_state_compiles"] = env.compile_count()
        ttfts = [r["ttft_s"] for r in results if r["ttft_s"] is not None]
        rec["continuous"] = {
            "tokens_per_sec": round(total / cont_dt, 2),
            "requests": len(reqs),
            "p50_ttft_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 3),
            "p99_ttft_ms": round(float(np.percentile(ttfts, 99)) * 1e3, 3),
        }

        t0 = time.perf_counter()
        for p, g in reqs:
            engine.generate(p, max_tokens=g, eos_token=None).result()
        serial_dt = time.perf_counter() - t0
        rec["serial"] = {"tokens_per_sec": round(total / serial_dt, 2)}
        rec["cb_speedup"] = round(serial_dt / cont_dt, 3)

        # -- paged vs slab KV bytes-per-active-token: the same mixed
        # short/long workload through a small-block pool and a
        # slab-layout pool (block_size == max_ctx reserves a sequence's
        # whole context window up front — the pre-paging policy). Reserved
        # rows and committed tokens are sampled from the on_token
        # callback, which the decode loop thread calls synchronously, so
        # the host-side tables are race-free to read.
        c = cfg
        row_bytes = (2 * c.num_layers * c.num_heads * c.head_dim
                     * np.dtype(c.dtype).itemsize)
        mixed = [(rng.randint(0, c.vocab_size, l).astype(np.int32), g)
                 for l, g in zip(mix_lens, mix_gens)]

        def kv_bytes_per_token(block_size):
            eng = DecodeEngine(model, slots=slots, max_ctx=max_ctx,
                               prompt_buckets=sorted(set(mix_lens)),
                               kv_block_size=block_size)
            eng.warmup()
            acc = {"rows": 0, "tokens": 0, "samples": 0}

            def cb(_tok):
                acc["rows"] += int(eng._nblocks.sum()) * eng.block_size
                acc["tokens"] += int(eng._lengths.sum())
                acc["samples"] += 1

            futs = [eng.generate(p, max_tokens=g, eos_token=None,
                                 on_token=cb) for p, g in mixed]
            for f in futs:
                f.result()
            eng.close(10.0)
            return (acc["rows"] / max(acc["tokens"], 1)) * row_bytes

        paged_bpt = kv_bytes_per_token(kv_block)
        slab_bpt = kv_bytes_per_token(max_ctx)
        rec["paged_kv"] = {
            "block_size": kv_block,
            "paged_bytes_per_token": round(paged_bpt, 1),
            "slab_bytes_per_token": round(slab_bpt, 1),
            "bytes_ratio": round(paged_bpt / slab_bpt, 4),
        }

        # -- batched prefill: burst of mixed-length prompts, coalesced
        # same-bucket prefill dispatches vs one dispatch per prompt
        # (max_tokens=1 isolates prompt ingest)
        burst = [rng.randint(0, c.vocab_size, l).astype(np.int32)
                 for l in burst_lens]

        def prefill_burst(batch, runs=3):
            # median of `runs` bursts — a single burst is a handful of
            # milliseconds on the tiny sizing and one scheduler hiccup
            # can swamp the dispatch-coalescing win being measured
            eng = DecodeEngine(model, slots=slots * 2, max_ctx=max_ctx,
                               prompt_buckets=buckets,
                               kv_block_size=kv_block,
                               prefill_batch=batch)
            eng.warmup()
            times, dispatches, ttfts = [], 0, []
            for i in range(runs):
                d0 = eng.stats()["prefill_dispatches"]
                t0 = time.perf_counter()
                futs = [eng.generate(p, max_tokens=1, eos_token=None)
                        for p in burst]
                results = [f.result() for f in futs]
                times.append(time.perf_counter() - t0)
                if i == 0:
                    dispatches = (eng.stats()["prefill_dispatches"]
                                  - d0)
                    ttfts = [r["ttft_s"] for r in results
                             if r["ttft_s"] is not None]
            eng.close(10.0)
            times.sort()
            dt = times[len(times) // 2]
            return len(burst) / dt, dispatches, ttfts

        batched_thr, batched_disp, batched_ttfts = prefill_burst(4)
        serial_thr, serial_disp, _ = prefill_burst(1)
        rec["batched_prefill"] = {
            "prompts": len(burst),
            "batched_prompts_per_sec": round(batched_thr, 2),
            "serial_prompts_per_sec": round(serial_thr, 2),
            "batched_dispatches": batched_disp,
            "serial_dispatches": serial_disp,
            "speedup": round(batched_thr / serial_thr, 3),
            "p99_ttft_ms": round(
                float(np.percentile(batched_ttfts, 99)) * 1e3, 3),
        }

        # -- speculative decoding: 1-layer weight-shared draft proposes
        # spec_k tokens per step; greedy output must match the engine's
        # own non-speculative run token for token
        dcfg = dataclasses.replace(cfg, num_layers=1)
        draft = causal_lm.CausalLM(dcfg, params={
            "embeddings": model.params["embeddings"],
            "layers": model.params["layers"][:1]})
        spec_reqs = [(rng.randint(0, c.vocab_size, l).astype(np.int32),
                      spec_tokens) for l in prompts[:4]]

        def spec_run(draft_model, k):
            eng = DecodeEngine(model, slots=4, max_ctx=max_ctx,
                               prompt_buckets=buckets,
                               kv_block_size=kv_block,
                               draft_model=draft_model, spec_k=k)
            eng.warmup()
            t0 = time.perf_counter()
            futs = [eng.generate(p, max_tokens=g, eos_token=None)
                    for p, g in spec_reqs]
            toks = [f.result()["tokens"] for f in futs]
            dt = time.perf_counter() - t0
            st = eng.stats()
            eng.close(10.0)
            total_toks = sum(len(t) for t in toks)
            return toks, total_toks / dt, st

        plain_toks, plain_thr, _ = spec_run(None, 0)
        spec_toks, spec_thr, spec_stats = spec_run(draft, spec_k)
        rec["speculative"] = {
            "k": spec_k,
            "decode_match": spec_toks == plain_toks,
            "tokens_per_sec": round(spec_thr, 2),
            "plain_tokens_per_sec": round(plain_thr, 2),
            "speedup": round(spec_thr / plain_thr, 3),
            "acceptance_rate": spec_stats.get("spec_acceptance"),
            "proposed": spec_stats.get("spec_proposed"),
            "accepted": spec_stats.get("spec_accepted"),
        }

        ok, reason = check_generative_decode(rec)
        if ok or attempt == 1:
            break
    engine.close(10.0)
    env.reset_compile_count()
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def check_generative_decode(rec, min_kv_speedup=3.0, min_cb_speedup=1.5,
                            max_kv_bytes_ratio=0.6,
                            min_prefill_speedup=1.3):
    """(ok, reason): gates a generative_decode record must pass.

    - the KV-cached greedy continuation must be token-identical to the
      full-recompute reference (a fast decode that decodes something
      else is not a speedup);
    - the steady state must have recorded ZERO new compiles after warmup
      (one prefill per (bucket, batch rung) + one decode executable is
      the entire executable set — per-token retracing is the failure
      mode this architecture exists to kill);
    - KV-cached decode must be >= ``min_kv_speedup`` (3x) tokens/sec over
      recomputing the whole prefix each token;
    - continuous batching must yield >= ``min_cb_speedup`` (1.5x)
      aggregate tokens/sec over serving the same mixed-length requests
      one at a time;
    - paged KV must reserve <= ``max_kv_bytes_ratio`` (0.6x) of the slab
      layout's bytes-per-active-token on the mixed short/long workload
      (blocks proportional to actual sequence length, not max_ctx);
    - batched prefill must ingest prompts >= ``min_prefill_speedup``
      (1.3x) faster than one dispatch per prompt;
    - speculative greedy output must be token-identical to the engine's
      own non-speculative run, with a measured acceptance rate reported
      (speculation that changes tokens is a correctness bug, whatever
      its speed)."""
    if not rec.get("decode_match"):
        return False, ("KV-cached greedy tokens differ from the "
                       "full-recompute reference: the cached decode is "
                       "not computing the same function")
    if rec.get("steady_state_compiles", -1) != 0:
        return False, (
            f"steady-state decode recorded "
            f"{rec.get('steady_state_compiles')} compiles after warmup "
            "(expected 0): the decode path is retracing")
    if rec["kv_speedup"] < min_kv_speedup:
        return False, (
            f"KV-cached decode only {rec['kv_speedup']:.2f}x the "
            f"full-recompute path (gate: >= {min_kv_speedup}x): the cache "
            "is not removing the prefix recompute")
    if rec["cb_speedup"] < min_cb_speedup:
        return False, (
            f"continuous batching only {rec['cb_speedup']:.2f}x "
            f"per-request serving (gate: >= {min_cb_speedup}x): requests "
            "are not actually sharing decode steps")
    paged = rec.get("paged_kv") or {}
    ratio = paged.get("bytes_ratio")
    if ratio is None:
        return False, ("record has no paged_kv.bytes_ratio: the paged-"
                       "vs-slab footprint comparison did not run")
    if ratio > max_kv_bytes_ratio:
        return False, (
            f"paged KV holds {ratio:.2f}x the slab layout's bytes per "
            f"active token (gate: <= {max_kv_bytes_ratio}x): blocks are "
            "not tracking actual sequence length")
    bp = rec.get("batched_prefill") or {}
    if bp.get("speedup") is None:
        return False, ("record has no batched_prefill.speedup: the "
                       "prompt-ingest comparison did not run")
    if bp["speedup"] < min_prefill_speedup:
        return False, (
            f"batched prefill only {bp['speedup']:.2f}x per-prompt "
            f"dispatch (gate: >= {min_prefill_speedup}x): same-bucket "
            "prompts are not sharing a dispatch")
    spec = rec.get("speculative") or {}
    if not spec.get("decode_match"):
        return False, (
            "speculative greedy tokens differ from the engine's own "
            "non-speculative run: accepted-prefix verification is "
            "broken")
    if spec.get("acceptance_rate") is None:
        return False, ("speculative run reported no acceptance rate: "
                       "the draft never proposed (spec path not "
                       "exercised)")
    return True, "ok"


def bench_prefix_reuse(jax, jnp, tiny):
    """Prefix-aware KV reuse (the radix-cache headline): the same tiny
    causal LM serving two chat-shaped workloads with the prefix cache
    on vs off.

    1. **Shared-system-prompt storm** — N requests sharing one long
       system prompt, each with a distinct short user tail. The first
       request prefills the full prompt; every follower must attach the
       cached common blocks and prefill only its tail, so the common
       prefix is prefilled exactly once fleet-wide. The engine's
       dispatch counters prove it: ``prefill_rows`` (rows actually
       computed) drops by exactly ``prefix_reused_rows`` (rows attached
       from cache) relative to the cache-off engine.
    2. **Multi-turn session replay** — turn 1 generates a reply; turn 2
       re-sends the whole history plus a new user message. Warm (cache
       on, same engine) the prefill covers only the new tail and lands
       in a small prompt bucket; cold (cache off) it recomputes the
       whole history in the big bucket. Reported as the cold/warm TTFT
       ratio (gate: >= 5x).

    Greedy output must be token-identical between the cached and
    uncached engines in every phase — reuse that changes tokens is a
    correctness bug, whatever its speed. Gated by
    ``check_prefix_reuse``.
    """
    from deeplearning4j_tpu.models import causal_lm
    from deeplearning4j_tpu.runtime.generation import DecodeEngine

    if tiny:
        # 4 layers, not the usual tiny 2: the cold full-history prefill
        # must dwarf the warm tail's fixed dispatch overhead for the 5x
        # TTFT gate to measure compute skipped, not scheduler noise
        cfg = causal_lm.CausalLMConfig(
            vocab_size=128, hidden_size=128, num_layers=4, num_heads=4,
            intermediate_size=256, max_position_embeddings=512,
            dtype=jnp.float32)
        max_ctx, bs = 512, 16
        buckets = [16, 32, 512]
        common_len, tail_len, storm_n, storm_gen = 224, 12, 6, 8
        turn1_len, turn1_gen, turn2_extra, ttft_runs = 352, 16, 14, 5
    else:
        cfg = causal_lm.CausalLMConfig(
            vocab_size=8192, hidden_size=512, num_layers=6, num_heads=8,
            intermediate_size=2048, max_position_embeddings=2048,
            dtype=jnp.bfloat16)
        max_ctx, bs = 2048, 32
        buckets = [32, 64, 2048]
        common_len, tail_len, storm_n, storm_gen = 1024, 24, 8, 16
        turn1_len, turn1_gen, turn2_extra, ttft_runs = 1500, 32, 28, 5
    model = causal_lm.CausalLM(cfg, seed=0)
    rng = np.random.RandomState(7)
    blocks = 4 * (max_ctx // bs)   # roomy pool: no eviction noise

    def engine(cache):
        eng = DecodeEngine(model, slots=4, max_ctx=max_ctx,
                           prompt_buckets=buckets, kv_block_size=bs,
                           kv_blocks=blocks, prefill_batch=1,
                           prefix_cache=cache)
        eng.warmup()
        return eng

    rec = {"block_size": bs, "prompt_buckets": buckets}
    for attempt in range(2):
        # -- phase 1: shared-system-prompt storm --------------------------
        common = rng.randint(0, cfg.vocab_size, common_len).astype(np.int32)
        tails = [rng.randint(0, cfg.vocab_size, tail_len).astype(np.int32)
                 for _ in range(storm_n)]
        prompts = [np.concatenate([common, t]) for t in tails]

        def storm(eng):
            # leader first so followers find its blocks published, then
            # the rest of the storm concurrently
            first = eng.generate(prompts[0], max_tokens=storm_gen,
                                 eos_token=None).result()
            futs = [eng.generate(p, max_tokens=storm_gen, eos_token=None)
                    for p in prompts[1:]]
            return [first["tokens"]] + [f.result()["tokens"] for f in futs]

        warm_eng = engine(True)
        warm_toks = storm(warm_eng)
        ws = warm_eng.stats()
        warm_eng.close(10.0)
        cold_eng = engine(False)
        cold_toks = storm(cold_eng)
        cs = cold_eng.stats()
        cold_eng.close(10.0)
        # every follower reuses exactly the block-aligned common run
        expected_reused = (storm_n - 1) * (common_len // bs) * bs
        rec["storm"] = {
            "requests": storm_n,
            "common_tokens": common_len,
            "prefill_rows": ws["prefill_rows"],
            "prefill_rows_cold": cs["prefill_rows"],
            "reused_rows": ws["prefix_reused_rows"],
            "expected_reused_rows": expected_reused,
            "prefix_hits": ws["prefix_hits"],
            "decode_match": warm_toks == cold_toks,
        }

        # -- phase 2: multi-turn session replay ---------------------------
        base = rng.randint(0, cfg.vocab_size, turn1_len).astype(np.int32)
        extra = rng.randint(0, cfg.vocab_size,
                            turn2_extra).astype(np.int32)

        def session(eng):
            # turn 1 populates (or not) the cache; turn 2 re-sends the
            # whole history + a new user message, several times for a
            # stable TTFT median (cache-off never re-learns, cache-on
            # re-attaches every repeat)
            t1 = eng.generate(base, max_tokens=turn1_gen,
                              eos_token=None).result()
            turn2 = np.concatenate(
                [base, np.asarray(t1["tokens"], np.int32), extra])
            ttfts, toks = [], None
            for _ in range(ttft_runs):
                r = eng.generate(turn2, max_tokens=storm_gen,
                                 eos_token=None).result()
                ttfts.append(r["ttft_s"])
                toks = r["tokens"]
            return t1["tokens"], toks, float(np.median(ttfts))

        warm_eng = engine(True)
        w1, w2, warm_ttft = session(warm_eng)
        ws2 = warm_eng.stats()
        warm_eng.close(10.0)
        cold_eng = engine(False)
        c1, c2, cold_ttft = session(cold_eng)
        cold_eng.close(10.0)
        rec["session"] = {
            "turn2_tokens": int(turn1_len + turn1_gen + turn2_extra),
            "cold_ttft_ms": round(cold_ttft * 1e3, 3),
            "warm_ttft_ms": round(warm_ttft * 1e3, 3),
            "ttft_ratio": round(cold_ttft / max(warm_ttft, 1e-9), 3),
            "warm_reused_rows": ws2["prefix_reused_rows"],
            "decode_match": (w1, w2) == (c1, c2),
        }

        ok, reason = check_prefix_reuse(rec)
        if ok or attempt == 1:
            break
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def check_prefix_reuse(rec, min_ratio=5.0):
    """(ok, reason): gates a prefix_reuse record must pass.

    - cached greedy output must be token-identical to the cache-off
      engine in both phases (reuse must not change the function);
    - the storm must reuse exactly the block-aligned common prefix for
      every follower — ``reused_rows == (N-1) * aligned(common)`` —
      and the computed-row counter must drop by the same amount vs the
      cold engine, proving the common prefix was prefilled once;
    - every storm follower must be a cache hit;
    - the warm turn-2 TTFT must be >= ``min_ratio`` (5x) faster than
      the cold engine's full-history prefill."""
    storm = rec.get("storm") or {}
    if not storm.get("decode_match"):
        return False, ("storm greedy tokens differ between cached and "
                       "uncached engines: prefix reuse changed the "
                       "decoded function")
    expected = storm.get("expected_reused_rows")
    if storm.get("reused_rows") != expected:
        return False, (
            f"storm reused {storm.get('reused_rows')} rows, expected "
            f"exactly {expected}: followers are not attaching the "
            "block-aligned common prefix")
    if storm.get("prefill_rows_cold", 0) - storm.get("prefill_rows", 0) \
            != expected:
        return False, (
            f"storm computed {storm.get('prefill_rows')} rows vs "
            f"{storm.get('prefill_rows_cold')} cold — the gap must be "
            f"exactly the {expected} reused rows: the common prefix was "
            "not prefilled exactly once")
    if storm.get("prefix_hits") != storm.get("requests", 0) - 1:
        return False, (
            f"{storm.get('prefix_hits')} storm followers hit the cache, "
            f"expected {storm.get('requests', 0) - 1}")
    sess = rec.get("session") or {}
    if not sess.get("decode_match"):
        return False, ("session replay tokens differ between cached and "
                       "uncached engines: re-attached turn history "
                       "decodes differently")
    ratio = sess.get("ttft_ratio", 0.0)
    if ratio < min_ratio:
        return False, (
            f"warm turn-2 TTFT only {ratio:.2f}x the cold full-history "
            f"prefill (gate: >= {min_ratio}x): the tail-only prefill is "
            "not skipping the cached history")
    return True, "ok"


def bench_quantized_inference(jax, jnp, tiny):
    """Post-training quantization for serving (quant/): an MLP served
    three ways — f32 reference, bf16 (the pre-PR mixed-precision serving
    default), and the int8 weight-quantized twin from
    ``quant.transforms.quantize_model`` — plus the full deploy-gate drill
    over HTTP.

    Measures, all gated by ``check_quantized_inference``:

    1. **throughput** — quantized twin vs the bf16 baseline over repeated
       ``output()`` dispatches of one warm executable (>= 1.2x; on CPU the
       twin computes in f32 — XLA:CPU emulates bf16 arithmetic — with the
       int8 dequant folded into the matmuls);
    2. **agreement** — top-1 vs the f32 reference on the calibration
       batch (>= 99%); the batch is margin-filtered (top-2 logit margin)
       the way an operator would pick decisive calibration traffic;
    3. **the divergence gate end-to-end** — a full-precision v1 deploys
       behind a live ``ModelServer``, then a deploy of a deliberately
       mis-scaled ``QuantSpec`` twin must be REJECTED by the gate with v1
       still answering ``POST /predict`` (200) and listed current in
       ``GET /v1/models`` with its precision metadata.
    """
    import copy
    import json as _json
    import urllib.request

    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.quant import (QuantSpec,
                                          QuantizationRejectedError,
                                          param_bytes_of, quantize_model)
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.serving.server import ModelServer

    n_in, hidden, n_out = (256, 1024, 16) if tiny else (512, 2048, 64)
    n_hidden_layers = 4
    B = 32 if tiny else 128
    reps = 30 if tiny else 60

    def build():
        b = NeuralNetConfiguration.builder().seed(0).list()
        b.layer(DenseLayer(n_in=n_in, n_out=hidden, activation="gelu"))
        for _ in range(n_hidden_layers - 1):
            b.layer(DenseLayer(n_in=hidden, n_out=hidden,
                               activation="gelu"))
        b.layer(OutputLayer(n_in=hidden, n_out=n_out))
        return MultiLayerNetwork(b.build()).init()

    full = build()

    # bf16 baseline: same params, conf compute dtype flipped (the serving
    # default on accelerators; XLA:CPU emulates it, which is the point of
    # comparison — quantized twins compute in f32 there)
    bf16 = type(full)(copy.copy(full.conf))
    bf16.conf.dtype = "bfloat16"
    bf16._params = full._params
    bf16._updater_state = None
    bf16._initialized = True

    quant = quantize_model(full)

    # margin-filtered calibration batch: of 4x candidates keep the B whose
    # f32 top-2 logit margin is largest (decisive traffic, so top-1
    # agreement measures quantization error, not coin flips)
    rng = np.random.RandomState(0)
    cands = rng.randn(4 * B, n_in).astype(np.float32)
    ref_logits = np.asarray(full.output(cands).jax())
    part = np.partition(ref_logits, -2, axis=-1)
    margin = part[:, -1] - part[:, -2]
    batch = cands[np.argsort(margin)[-B:]]
    ref = np.asarray(full.output(batch).jax())
    q_out = np.asarray(quant.output(batch).jax())
    rec = {
        "batch": B, "n_in": n_in, "hidden": hidden,
        "layers": n_hidden_layers + 1,
        "top1_agreement": round(float(
            (ref.argmax(-1) == q_out.argmax(-1)).mean()), 4),
        "max_abs_err": round(float(np.abs(ref - q_out).max()), 6),
        "param_bytes_full": param_bytes_of(full),
        "param_bytes_quant": param_bytes_of(quant),
    }
    rec["bytes_ratio"] = round(
        rec["param_bytes_quant"] / max(rec["param_bytes_full"], 1), 4)

    xb = jnp.asarray(batch)

    def sps(net):
        jax.block_until_ready(net.output(xb).jax())  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = net.output(xb)
        jax.block_until_ready(out.jax())
        return B * reps / (time.perf_counter() - t0)

    for attempt in range(2):
        rec["f32_sps"] = round(sps(full), 2)
        rec["bf16_sps"] = round(sps(bf16), 2)
        rec["quantized_sps"] = round(sps(quant), 2)
        rec["quant_speedup_vs_bf16"] = round(
            rec["quantized_sps"] / max(rec["bf16_sps"], 1e-9), 3)
        if rec["quant_speedup_vs_bf16"] >= 1.2 or attempt == 1:
            break

    # -- the gate drill, end to end over HTTP
    reg = ModelRegistry(manifest_dir=None)
    server = ModelServer(reg)
    port = server.start()
    base = f"http://127.0.0.1:{port}"
    try:
        reg.deploy("quantbench", "v1", build(), example=batch)
        try:
            reg.deploy("quantbench", "v2", build(), example=batch,
                       quantize=QuantSpec(scale_overrides={"": 64.0}))
            rec["misscale_rejected"] = False
        except QuantizationRejectedError as e:
            rec["misscale_rejected"] = True
            rec["misscale_reason"] = str(e)[:160]
        body = _json.dumps({"inputs": batch[:4].tolist()}).encode()
        req = urllib.request.Request(
            base + "/v1/models/quantbench/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            doc = _json.loads(resp.read())
            rec["post_reject_predict_status"] = resp.status
            rec["post_reject_served_version"] = doc.get("version")
        with urllib.request.urlopen(base + "/v1/models",
                                    timeout=30) as resp:
            models = _json.loads(resp.read())["models"]["quantbench"]
            rec["current_version"] = models["current"]
            rec["current_precision"] = models["versions"][0]["precision"]
    finally:
        server.stop()
        reg.drain_all(5.0)

    ok, reason = check_quantized_inference(rec)
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def check_quantized_inference(rec, min_speedup=1.2, min_top1=0.99):
    """(ok, reason): gates a quantized_inference record must pass.

    - quantized serving throughput >= ``min_speedup`` (1.2x) the bf16
      baseline — quantization must buy speed, not just bytes;
    - top-1 agreement with the f32 reference >= ``min_top1`` (99%) on the
      calibration batch — and the quantized twin must be materially
      smaller at rest (int8 + scales < 60% of f32 bytes);
    - the deliberately mis-scaled QuantSpec must have been REJECTED by
      the divergence gate, with the full-precision v1 still current AND
      still answering ``/predict`` (200) afterward — the fail-closed
      cutover contract."""
    if not rec.get("misscale_rejected"):
        return False, ("the deliberately mis-scaled QuantSpec deployed "
                       "without the divergence gate rejecting it: the "
                       "gate is not guarding cutover")
    if rec.get("post_reject_predict_status") != 200 \
            or rec.get("post_reject_served_version") != "v1" \
            or rec.get("current_version") != "v1":
        return False, (
            f"after the rejected quantized deploy, /predict returned "
            f"{rec.get('post_reject_predict_status')} from version "
            f"{rec.get('post_reject_served_version')!r} (current: "
            f"{rec.get('current_version')!r}; expected 200 from v1): the "
            "aborted swap disturbed the live version")
    if rec["top1_agreement"] < min_top1:
        return False, (
            f"top-1 agreement {rec['top1_agreement']:.4f} vs the f32 "
            f"reference (gate: >= {min_top1}): int8 weight error is "
            "flipping predictions on decisive inputs")
    if rec["bytes_ratio"] >= 0.6:
        return False, (
            f"quantized params are {rec['bytes_ratio']:.2f}x the f32 "
            "bytes (gate: < 0.6): weights are not int8 at rest")
    if rec["quant_speedup_vs_bf16"] < min_speedup:
        return False, (
            f"quantized throughput only {rec['quant_speedup_vs_bf16']:.2f}"
            f"x the bf16 baseline (gate: >= {min_speedup}x): the "
            "quantized twin is not faster to serve")
    return True, "ok"


def bench_pallas_decode(jax, jnp, tiny):
    """Paged decode read path: the Pallas paged-flash kernel
    (``kernels.paged_flash_decode`` — block tables walked in-kernel via
    scalar prefetch, KV blocks streamed HBM→VMEM with online-softmax
    accumulation) vs the XLA block-table gather it replaces, plus the
    fused int8 dequant-matmul parity proof.

    Two phases run the SAME greedy decode loop over one jitted
    ``paged_decode`` step: "gather" pins ``DL4J_TPU_PAGED_KERNEL=off``,
    "kernel" forces it on (interpret mode on CPU, the compiled kernel
    on accelerators — never "auto", which keeps every head_dim <= 64
    model in this repo on the gather and would measure the gather
    twice). Each phase records tokens/sec, its
    ``dl4j_kernel_dispatch_total{kernel=paged_decode,path=}`` deltas
    (proving which path actually served the executable), and the
    steady-state compile count (must be zero — the path decision is
    trace-time, so a warm loop never retraces). The greedy token streams
    of both phases must be identical. Gated by ``check_pallas_decode``.
    """
    from deeplearning4j_tpu.common.environment import environment
    from deeplearning4j_tpu.models.causal_lm import CausalLM
    from deeplearning4j_tpu.quant.transforms import (dequant_matmul,
                                                     quantize_tensor)
    from deeplearning4j_tpu.runtime.inference import counted_jit

    env = environment()
    platform = jax.devices()[0].platform
    S, Bs, MB = (4, 16, 4) if tiny else (8, 16, 16)
    steps = 12 if tiny else 48
    model = CausalLM(seed=0)
    N = S * MB + 1  # block 0 stays scratch
    rng = np.random.RandomState(0)
    base = model.init_paged_kv_cache(N, Bs)
    pool_shape = base["k"].shape
    # a pre-warmed pool (random committed K/V) so the read path dominates
    cache0 = {
        "k": jnp.asarray(rng.randn(*pool_shape).astype(np.float32) * 0.3,
                         base["k"].dtype),
        "v": jnp.asarray(rng.randn(*pool_shape).astype(np.float32) * 0.3,
                         base["v"].dtype),
    }
    tables = jnp.asarray(np.arange(1, 1 + S * MB).reshape(S, MB), np.int32)
    max_len = MB * Bs - steps - 1
    lengths0 = jnp.asarray(rng.randint(1, max_len, S), np.int32)

    fam_help = ("Hand-written-kernel vs fallback path decisions per "
                "kernel family, evaluated at trace time")
    fam = env.metrics().counter("dl4j_kernel_dispatch_total", fam_help,
                                labels=("kernel", "path"))

    def run_phase(mode):
        env.set_paged_kernel(mode)
        try:
            before = {p: fam.labels(kernel="paged_decode", path=p).value()
                      for p in ("paged", "paged_flash")}
            step = counted_jit(
                lambda cache, toks, ln: model.paged_decode(
                    model.params, cache, tables, toks, ln),
                f"bench_pallas_decode:{mode}")
            toks = jnp.ones((S, 1), jnp.int32)
            cache_i, ln_i = cache0, lengths0
            cache_i, lg = step(cache_i, toks, ln_i)  # compile + warm
            jax.block_until_ready(lg)
            cache_i, ln_i = cache0, lengths0
            ids = []
            compiles0 = env.compile_count()
            t0 = time.perf_counter()
            for _ in range(steps):
                cache_i, lg = step(cache_i, toks, ln_i)
                nxt = lg[:, -1].argmax(-1).astype(jnp.int32)
                ids.append(np.asarray(nxt))  # host sync: the decode loop
                toks = nxt[:, None]
                ln_i = ln_i + 1
            dt = time.perf_counter() - t0
            return {
                "path": "paged" if mode == "off" else "paged_flash",
                "tokens_per_sec": round(S * steps / dt, 2),
                "steady_state_compiles": env.compile_count() - compiles0,
                "dispatch_paged": int(
                    fam.labels(kernel="paged_decode", path="paged").value()
                    - before["paged"]),
                "dispatch_paged_flash": int(
                    fam.labels(kernel="paged_decode",
                               path="paged_flash").value()
                    - before["paged_flash"]),
            }, [int(t) for row in ids for t in row]
        finally:
            env.clear_property("paged_kernel")

    gather, tok_g = run_phase("off")
    kernel, tok_k = run_phase("on")
    rec = {
        "platform": platform, "slots": S, "block_size": Bs,
        "max_blocks_per_slot": MB, "steps": steps,
        "interpret": platform == "cpu",
        "gather": gather, "kernel": kernel,
        "token_identical": tok_g == tok_k,
        "speedup_vs_gather": round(
            kernel["tokens_per_sec"] / max(gather["tokens_per_sec"], 1e-9),
            3),
    }

    # fused int8 dequant-matmul parity: forced-on Pallas kernel vs the
    # XLA cast-then-dot fallback on the same quantized weight
    K, Nw = (256, 256) if tiny else (512, 512)
    w = quantize_tensor(jnp.asarray(
        rng.randn(K, Nw).astype(np.float32) * 0.05))
    x = jnp.asarray(rng.randn(32, K).astype(np.float32))
    before_f = fam.labels(kernel="dequant_matmul", path="fused").value()
    env.set_fused_dequant("off")
    ref = np.asarray(dequant_matmul(x, w))
    env.set_fused_dequant("on")
    try:
        fused = np.asarray(jax.jit(lambda a: dequant_matmul(a, w))(x))
    finally:
        env.clear_property("fused_dequant")
    rec["fused_dequant"] = {
        "k": K, "n": Nw,
        "max_abs_err": round(float(np.abs(fused - ref).max()), 6),
        "top1_agreement": round(float(
            (ref.argmax(-1) == fused.argmax(-1)).mean()), 4),
        "dispatch_fused": int(
            fam.labels(kernel="dequant_matmul", path="fused").value()
            - before_f),
    }

    ok, reason = check_pallas_decode(rec)
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def check_pallas_decode(rec, min_speedup=1.05, max_divergence=0.25,
                        min_top1=0.99):
    """(ok, reason): gates a pallas_decode record must pass.

    - greedy token streams identical between the gather and paged-flash
      phases — the kernel is a drop-in numeric replacement;
    - the dispatch counters prove which path served each phase: the
      gather phase compiled exactly zero paged_flash executables and at
      least one paged one, the kernel phase the reverse;
    - zero steady-state recompiles in both timed loops (the path
      decision is trace-time; a warm decode loop never retraces);
    - fused dequant-matmul: dispatched through the fused path, within
      ``max_divergence`` of the XLA contraction and >= ``min_top1``
      top-1 agreement (the existing quant deploy-gate thresholds);
    - on accelerators the kernel phase must beat the gather phase by
      ``min_speedup``; on CPU the kernel runs interpret mode (parity
      coverage, not a perf claim), so the speed leg is skipped and the
      record must say so via ``interpret``."""
    if not rec.get("token_identical"):
        return False, ("greedy token streams diverged between the gather "
                       "and paged-flash phases: the kernel is not a "
                       "drop-in replacement for the gather read")
    g, k = rec["gather"], rec["kernel"]
    if g["dispatch_paged"] < 1 or g["dispatch_paged_flash"] != 0:
        return False, (
            f"gather phase dispatch counters (paged={g['dispatch_paged']}, "
            f"paged_flash={g['dispatch_paged_flash']}) don't prove the "
            "gather path served it")
    if k["dispatch_paged_flash"] < 1 or k["dispatch_paged"] != 0:
        return False, (
            f"kernel phase dispatch counters (paged={k['dispatch_paged']}, "
            f"paged_flash={k['dispatch_paged_flash']}) don't prove the "
            "paged-flash kernel served it")
    for name, ph in (("gather", g), ("kernel", k)):
        if ph["steady_state_compiles"] != 0:
            return False, (
                f"{name} phase recompiled {ph['steady_state_compiles']} "
                "time(s) during the warm decode loop (gate: 0): the path "
                "decision is leaking into steady state")
    fd = rec.get("fused_dequant") or {}
    if fd.get("dispatch_fused", 0) < 1:
        return False, ("fused dequant-matmul never dispatched through the "
                       "Pallas path: the parity leg measured the fallback "
                       "against itself")
    if fd.get("max_abs_err", float("inf")) > max_divergence:
        return False, (
            f"fused dequant-matmul diverges {fd.get('max_abs_err')} from "
            f"the XLA contraction (gate: <= {max_divergence}, the quant "
            "deploy-gate threshold)")
    if fd.get("top1_agreement", 0.0) < min_top1:
        return False, (
            f"fused dequant-matmul top-1 agreement "
            f"{fd.get('top1_agreement')} vs the XLA contraction (gate: >= "
            f"{min_top1})")
    if rec.get("platform") != "cpu":
        if rec["speedup_vs_gather"] < min_speedup:
            return False, (
                f"paged-flash kernel only {rec['speedup_vs_gather']:.2f}x "
                f"the gather path (gate: >= {min_speedup}x on "
                "accelerators): the kernel is not paying for itself")
    elif not rec.get("interpret"):
        return False, ("CPU record without interpret=True: the kernel "
                       "phase did not exercise the interpreted Pallas "
                       "path, so the parity claim is empty")
    return True, "ok"


def bench_serving_resilience(jax, jnp, tiny):
    """Self-healing serving under deterministic fault injection (the
    resilience subsystem's headline). Four phases over one deployed
    model:

    1. **fault-free** — client threads through ``registry.predict`` (the
       breaker-accounted micro-batcher path); p99 is the baseline.
    2. **5% dispatch faults** — ``engine.dispatch`` armed at rate 0.05.
       A failed coalesced dispatch re-dispatches its riders individually
       once, so requests only fail when BOTH their group and their
       isolated retry draw a fault (quarantined). The gate: >= 99% of
       non-quarantined requests succeed and the admitted p99 stays
       within 3x of the fault-free run — injected faults must degrade
       the tail, not the service.
    3. **batcher crashes** — ``engine.batcher`` armed; the supervised
       worker restarts with backoff and every queued request survives.
       Zero permadeaths (worker_dead) allowed.
    4. **breaker** — rate-1.0 faults until the version's breaker opens
       (fail-fast BreakerOpenError), then injection stops and the
       half-open probe must re-close the breaker within its probe
       window.
    """
    import threading

    from deeplearning4j_tpu.common import faults
    from deeplearning4j_tpu.common.metrics import registry as mreg
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.serving import (BreakerOpenError, ModelRegistry,
                                            PoisonRequestError)

    n_in, hidden, n_out, B = ((64, 256, 8, 16) if tiny
                              else (128, 1024, 32, 32))
    n_threads = 4 if tiny else 8
    per_thread = 25 if tiny else 80
    probe_s = 0.2

    b = NeuralNetConfiguration.builder().seed(0).list()
    b.layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
    conf = b.layer(OutputLayer(n_in=hidden, n_out=n_out)).build()
    net = MultiLayerNetwork(conf).init()
    registry = ModelRegistry(manifest_dir=None, retain=0,
                             breaker_threshold=5, breaker_probe_s=probe_s)
    x = jnp.asarray(np.random.RandomState(0).randn(B, n_in)
                    .astype(np.float32))
    registry.deploy("bench", "v1", net, example=x, max_batch=B,
                    max_delay_ms=0.5)
    engine = registry.get("bench").engine

    def storm():
        ok, quarantined, failed, lat = [0], [0], [0], []
        lock = threading.Lock()

        def client(seed):
            xs = jnp.asarray(np.random.RandomState(seed)
                             .randn(2, n_in).astype(np.float32))
            for _ in range(per_thread):
                t0 = time.perf_counter()
                try:
                    jax.block_until_ready(
                        registry.predict("bench", xs).jax())
                except PoisonRequestError:
                    with lock:
                        quarantined[0] += 1
                    continue
                except Exception:
                    with lock:
                        failed[0] += 1
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    ok[0] += 1
                    lat.append(dt)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        offered = n_threads * per_thread
        eligible = max(offered - quarantined[0], 1)
        return {"offered": offered, "ok": ok[0],
                "quarantined": quarantined[0], "failed_other": failed[0],
                "ok_rate_of_nonpoison": round(ok[0] / eligible, 5),
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3)
                if lat else None,
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3)
                if lat else None}

    def injected_count():
        fam = mreg().get("dl4j_faults_injected_total")
        if fam is None:
            return 0.0
        return sum(c.value() for _, c in fam.children())

    restart_fam = mreg().counter(
        "dl4j_engine_restarts_total",
        "Supervised engine worker-thread restarts after a crash",
        labels=("engine",)).labels(engine="inference")

    try:
        rec = {"threads": n_threads,
               "requests_per_phase": n_threads * per_thread,
               "fault_rate": 0.05}
        rec["fault_free"] = storm()

        # phase 2: 5% deterministic dispatch faults
        faults.clear()
        rule = faults.inject("engine.dispatch", rate=0.05, seed=11)
        before_inj = injected_count()
        rec["faulted"] = storm()
        faults.remove(rule)
        rec["faulted"]["injected"] = int(injected_count() - before_inj)

        # phase 3: batcher thread crashes under traffic
        r0 = restart_fam.value()
        with faults.injected("engine.batcher", rate=1.0, times=3):
            futs = [engine.submit(x) for _ in range(6)]
            crash_survivors = sum(
                1 for f in futs if f.result(timeout=60) is not None)
        rec["batcher_crash"] = {
            "restarts": int(restart_fam.value() - r0),
            "survivors": crash_survivors, "submitted": len(futs),
            "permadeaths": int(bool(engine.worker_dead))}

        # phase 4: open the breaker, stop injecting, time the re-close
        rule = faults.inject("engine.dispatch", rate=1.0, seed=3)
        opened = False
        for _ in range(32):
            try:
                registry.predict("bench", x)
            except BreakerOpenError:
                opened = True
                break
            except Exception:
                continue
        faults.remove(rule)
        t_open = time.perf_counter()
        reclosed = False
        while time.perf_counter() - t_open < probe_s * 10:
            try:
                registry.predict("bench", x)
                reclosed = True
                break
            except BreakerOpenError:
                time.sleep(probe_s / 10)
            except Exception:
                time.sleep(probe_s / 10)
        rec["breaker"] = {
            "opened": opened, "reclosed": reclosed,
            "probe_s": probe_s,
            "reclose_s": round(time.perf_counter() - t_open, 3),
            "state": registry.breaker_for("bench", "v1").state}
    finally:
        faults.clear()
        registry.drain_all(save_manifests=False)
    ok, reason = check_serving_resilience(rec)
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def check_serving_resilience(rec, min_ok_rate=0.99, max_p99_ratio=3.0):
    """(ok, reason): gates a serving_resilience record must pass.

    - faults must actually have been injected (a resilience record
      measured against zero faults proves nothing);
    - >= ``min_ok_rate`` (99%) of non-quarantined requests succeed under
      5% dispatch faults — isolated retry absorbs the fault for a poison
      request's innocent riders, and transient faults for everyone;
    - the faulted-run admitted p99 stays within ``max_p99_ratio`` (3x)
      of the fault-free p99 — recovery must not stall the service;
    - zero engine-thread permadeaths, and the supervised batcher must
      have actually restarted (the crash phase exercised it);
    - the circuit breaker must have opened under sustained faults AND
      re-closed once injection stopped, within its probe window (x3
      slack for scheduling)."""
    f = rec["faulted"]
    if not f.get("injected"):
        return False, ("no faults were injected in the faulted phase: "
                       "the resilience claim is untested")
    if f["ok_rate_of_nonpoison"] < min_ok_rate:
        return False, (
            f"only {f['ok_rate_of_nonpoison']:.4f} of non-quarantined "
            f"requests succeeded under injected faults "
            f"(gate: >= {min_ok_rate}): recovery is losing innocent "
            "requests")
    if f["p99_ms"] and rec["fault_free"]["p99_ms"]:
        limit = max_p99_ratio * rec["fault_free"]["p99_ms"]
        if f["p99_ms"] > limit:
            return False, (
                f"faulted-run p99 {f['p99_ms']:.3f}ms > {limit:.3f}ms "
                f"({max_p99_ratio}x fault-free "
                f"{rec['fault_free']['p99_ms']:.3f}ms): recovery is "
                "stalling the admitted tail")
    bc = rec["batcher_crash"]
    if bc["permadeaths"] != 0:
        return False, (f"{bc['permadeaths']} engine-thread permadeath(s): "
                       "the supervisor gave up under the crash budget")
    if bc["restarts"] < 1:
        return False, ("the batcher never restarted: the crash phase did "
                       "not exercise the supervisor")
    if bc["survivors"] != bc["submitted"]:
        return False, (
            f"only {bc['survivors']}/{bc['submitted']} requests survived "
            "the batcher crash: queued work is being lost on restart")
    br = rec["breaker"]
    if not br["opened"]:
        return False, ("the breaker never opened under rate-1.0 faults: "
                       "consecutive dispatch failures are not tripping it")
    if not br["reclosed"]:
        return False, ("the breaker did not re-close after injection "
                       "stopped: the half-open probe path is broken")
    if br["reclose_s"] > br["probe_s"] * 3 + 0.5:
        return False, (
            f"breaker took {br['reclose_s']:.3f}s to re-close (probe "
            f"window {br['probe_s']}s): probes are not firing on time")
    return True, "ok"


def check_serving_overload(rec, max_p99_ratio=3.0):
    """(ok, reason): gates a serving_overload record must pass.

    - with shedding on, admitted requests must exist AND the shedder must
      actually have engaged under the synthetic overload (zero shed means
      the storm never overloaded the controller — the record proves
      nothing);
    - the admitted requests' p99 must stay within ``max_p99_ratio`` (3x)
      of the unloaded p99: shedding exists precisely so the clients that
      ARE admitted never sit behind an unbounded queue."""
    on = rec["shed_on"]
    if not on.get("completed"):
        return False, ("no admitted request completed under overload "
                       "with shedding on: the controller shed everything")
    if on.get("shed", 0) <= 0:
        return False, ("overload never tripped the shedder (0 shed): the "
                       "storm did not overload the controller, so the "
                       "bounded-p99 claim is untested")
    limit = max_p99_ratio * rec["unloaded_p99_ms"]
    if on["p99_ms"] > limit:
        return False, (
            f"admitted-request p99 {on['p99_ms']:.3f}ms > {limit:.3f}ms "
            f"({max_p99_ratio}x unloaded {rec['unloaded_p99_ms']:.3f}ms): "
            "shedding is not bounding the admitted queue")
    return True, "ok"


def check_telemetry_overhead(rec, max_overhead=0.03):
    """(ok, reason): metrics-on serving throughput may cost at most
    `max_overhead` (3%) vs metrics-off — the near-zero-cost contract of
    the telemetry subsystem. A bigger gap means instrumentation leaked
    onto the per-dispatch path (allocation, locking, or a host sync).
    When the record carries the fleet pass (`fleet_on_rps`), the same
    gate applies to the whole observability plane armed vs off: attempt
    spans + aggregator scraping + decomposition on the routed path."""
    on, off = rec["metrics_on_sps"], rec["metrics_off_sps"]
    floor = (1.0 - max_overhead) * off
    if on < floor:
        return False, (
            f"metrics-on throughput {on:.2f} < {floor:.2f} "
            f"({(1 - max_overhead) * 100:.0f}% of metrics-off {off:.2f}): "
            "telemetry is not near-zero-cost on the serving path")
    f_on = rec.get("fleet_on_rps")
    if f_on is not None:
        f_off = rec["fleet_off_rps"]
        f_floor = (1.0 - max_overhead) * f_off
        if f_on < f_floor:
            return False, (
                f"observability-armed fleet throughput {f_on:.2f} < "
                f"{f_floor:.2f} ({(1 - max_overhead) * 100:.0f}% of "
                f"disarmed {f_off:.2f}): the fleet observability plane "
                "is taxing the routed serving path")
    return True, "ok"


def bench_static_analysis(jax, jnp, tiny):
    """The dl4jlint pass + DL105 lock-tracker cost (PR 9's headline).

    Two budgets, both CI-facing:

    1. **lint wall-clock** — the full-package static pass (DL101-DL105
       over every module) runs inside tier-1, so it must stay under 30 s
       on CPU CI — and it must come back green (0 unbaselined findings).
    2. **lock-tracker overhead** — the serving stack's locks are
       ``common.locks.OrderedLock``; with ``DL4J_TPU_LOCK_CHECK`` off
       the wrapper must be invisible on the serving path. Measured as
       engine+admission serving throughput (the same submit()-driven
       path the serving_overload storm hammers, minus the deliberate
       overload so the ratio isolates lock cost, not queueing) with the
       tracker off vs on; the *off* case is the production default and
       the on/off gap is gated < 3%, matching the telemetry convention.
    """
    from deeplearning4j_tpu import analysis
    from deeplearning4j_tpu.common import locks
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.runtime.inference import InferenceEngine
    from deeplearning4j_tpu.serving import AdmissionController

    # 1. the lint pass itself
    t0 = time.perf_counter()
    res = analysis.run_analysis()
    lint_s = time.perf_counter() - t0

    # 2. tracker on/off serving throughput
    n_in, hidden, n_out = (16, 32, 4) if tiny else (128, 512, 16)
    max_batch = 8 if tiny else 32
    sizes = [1, 3, 7, 5, 2, 6, 4, 8]
    n_requests = len(sizes) * (12 if tiny else 16)

    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_in=hidden, n_out=n_out))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    reqs = [jnp.asarray(rng.randn(sizes[i % len(sizes)], n_in)
                        .astype(np.float32)) for i in range(n_requests)]
    total_rows = sum(int(r.shape[0]) for r in reqs)

    prev = locks.lock_check_enabled()
    sps = {}
    try:
        # ONE engine + warmup serves both modes (the lock mode is a
        # module global, not engine state); off/on passes interleave so
        # both modes see identical cache/scheduler conditions and the
        # ratio isolates tracker cost
        locks.clear_violations()
        eng = InferenceEngine(net, max_batch=max_batch)
        eng.warmup(reqs[0])
        ctrl = AdmissionController("bench-lint", default_timeout_s=None)
        runs = {"off": [], "on": []}
        for _ in range(4 if tiny else 5):
            for mode in ("off", "on"):
                locks.set_lock_check(mode == "on")
                t0 = time.perf_counter()
                for r in reqs:
                    with ctrl.admit():
                        jax.block_until_ready(
                            eng.submit(r).result().jax())
                runs[mode].append(time.perf_counter() - t0)
        eng.close(5.0)
        for mode, times in runs.items():
            # best-of (the timeit convention): scheduler hiccups only
            # ever ADD time, and a 3% ratio gate cannot absorb them
            sps[mode] = total_rows / min(times)
        inversions = len(locks.violations())
    finally:
        locks.set_lock_check(prev)
        locks.clear_violations()

    rec = {
        "lint_seconds": round(lint_s, 3),
        "lint_modules": res.modules,
        "lint_findings": len(res.findings),
        "lint_baselined": len(res.baselined),
        "lock_off_sps": round(sps["off"], 2),
        "lock_on_sps": round(sps["on"], 2),
        "lock_overhead_frac": round(1.0 - sps["on"] / max(sps["off"], 1e-9),
                                    4),
        "lock_inversions": inversions,
        "request_count": n_requests,
    }
    ok, reason = check_static_analysis(rec)
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def check_static_analysis(rec, max_seconds=30.0, max_overhead=0.03):
    """(ok, reason): gates a static_analysis record must pass.

    - the full-package lint must finish inside the CI budget
      (``max_seconds``, 30 s on CPU) — a slow linter gets skipped, and a
      skipped linter guards nothing;
    - it must come back green: 0 unbaselined findings (the repo state
      tier-1 enforces);
    - the DL105 runtime lock tracker must be free when off: serving
      throughput with the tracker ON may cost at most ``max_overhead``
      (3%) vs off — and the tracked run itself must record no
      lock-order inversions."""
    if rec["lint_seconds"] > max_seconds:
        return False, (
            f"lint pass took {rec['lint_seconds']:.1f}s > {max_seconds}s "
            "CI budget: the tier-1 analysis gate would dominate the suite")
    if rec.get("lint_findings", 0):
        return False, (
            f"{rec['lint_findings']} unbaselined finding(s): the repo is "
            "not lint-green (fix or baseline-with-justification)")
    if rec.get("lock_inversions", 0):
        return False, (
            f"{rec['lock_inversions']} lock-order inversion(s) recorded "
            "on the serving path under the tracker")
    on, off = rec["lock_on_sps"], rec["lock_off_sps"]
    floor = (1.0 - max_overhead) * off
    if on < floor:
        return False, (
            f"tracker-on throughput {on:.2f} < {floor:.2f} "
            f"({(1 - max_overhead) * 100:.0f}% of tracker-off {off:.2f}): "
            "the lock-order tracker is not near-zero-cost")
    return True, "ok"


def bench_sharded_serving(jax, jnp, tiny):
    """Sharded serving fleet (serving/fleet): scale-up parity plus
    scale-out routing. Three legs over the same toy MLP:

    1. **mesh parity** — the model deployed sharded over the full
       ``serving_mesh()`` (params partitioned over the ``model`` axis)
       must answer ``predict`` with logits matching the single-device
       deploy to float tolerance and with identical argmax.
       Cross-device contractions reorder the reduction, so bitwise
       identity holds only on a 1x1 mesh (pinned in
       tests/test_fleet.py); the serving contract gated here is
       decision-identity.
    2. **scale-out** — a 6-thread client storm through a FleetRouter
       over 3 in-process ModelServer replicas (each admission-limited
       to ``max_concurrent=1``) vs the same storm over one replica.
       Per-request service time is dominated by the micro-batcher's
       coalescing window — a wait that burns no host CPU, standing in
       for per-replica device time on a single-core CI box — so the
       ratio measures the ROUTER's least-loaded spreading, not host
       parallelism. Gate: >= 2x.
    3. **replica-kill drill** — the same storm with one replica's HTTP
       server stopped a quarter of the way in. The router must take
       the dead replica out of rotation (one failover retry on a
       different replica) with every non-shed request still
       succeeding. Gate: 100% non-shed success and at least one
       recorded failover.
    """
    import threading

    from deeplearning4j_tpu.common.mesh import mesh_shape, serving_mesh
    from deeplearning4j_tpu.common.metrics import registry as mreg
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.serving import ModelRegistry, ModelServer
    from deeplearning4j_tpu.serving.fleet import FleetRouter, NoReplicaError

    n_in, hidden, n_out, B = 32, 64, 8, 4
    n_threads = 6
    per_thread = 15 if tiny else 40
    delay_ms = 20.0  # the no-CPU service-time floor per solo dispatch

    def _mlp(seed=0):
        b = NeuralNetConfiguration.builder().seed(seed).list()
        b.layer(DenseLayer(n_in=n_in, n_out=hidden, activation="tanh"))
        conf = b.layer(OutputLayer(n_in=hidden, n_out=n_out)).build()
        return MultiLayerNetwork(conf).init()

    x = np.random.RandomState(0).randn(B, n_in).astype(np.float32)
    rec = {"n_devices": jax.device_count(), "threads": n_threads,
           "requests_per_storm": n_threads * per_thread,
           "batch_delay_ms": delay_ms}

    # -- leg 1: mesh-sharded deploy parity vs single-device ---------------
    mesh = serving_mesh()
    regp = ModelRegistry(manifest_dir=None)
    try:
        regp.deploy("plain", "v1", _mlp(), example=x, warm=True)
        ref = np.asarray(regp.predict("plain", x).jax())
        mv = regp.deploy("sharded", "v1", _mlp(), example=x, warm=True,
                         mesh=mesh)
        out = np.asarray(regp.predict("sharded", x).jax())
        rec["parity"] = {
            "mesh_shape": mesh_shape(mesh),
            "param_spec": mv.describe().get("param_spec"),
            "allclose": bool(np.allclose(ref, out, rtol=1e-5, atol=1e-6)),
            "argmax_match_rate": float(
                (ref.argmax(-1) == out.argmax(-1)).mean()),
            "max_abs_err": float(np.abs(ref - out).max()),
        }
    finally:
        regp.drain_all(save_manifests=False)

    # -- legs 2+3: the replica fleet --------------------------------------
    body = json.dumps({"inputs": x.tolist()}).encode()

    def storm(router, kill_at=None, kill_fn=None):
        ok, shed, failed = [0], [0], [0]
        lat, hit = [], set()
        lock = threading.Lock()
        done = [0]

        def client():
            for _ in range(per_thread):
                t0 = time.perf_counter()
                try:
                    status, _, _, url = router.route(
                        "POST", "/v1/models/bench/predict", body,
                        headers=[("Content-Type", "application/json")],
                        model="bench", timeout_s=30)
                except NoReplicaError:
                    with lock:
                        failed[0] += 1
                        done[0] += 1
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    done[0] += 1
                    if status == 200:
                        ok[0] += 1
                        lat.append(dt)
                        hit.add(url)
                    elif status == 429:
                        shed[0] += 1
                    else:
                        failed[0] += 1

        threads = [threading.Thread(target=client)
                   for _ in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        if kill_fn is not None:
            while True:
                with lock:
                    if done[0] >= kill_at:
                        break
                time.sleep(0.005)
            kill_fn()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return {"offered": n_threads * per_thread, "ok": ok[0],
                "shed": shed[0], "failed": failed[0],
                "throughput_rps": round(ok[0] / wall, 2),
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2)
                if lat else None,
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2)
                if lat else None,
                "replicas_hit": len(hit)}

    def failovers():
        fam = mreg().get("dl4j_router_dispatch_total")
        if fam is None:
            return 0.0
        i = fam.label_names.index("outcome")
        return sum(c.value() for key, c in fam.children()
                   if key[i] == "failover")

    members, urls = [], []
    try:
        for i in range(3):
            reg = ModelRegistry(manifest_dir=None)
            reg.deploy("bench", "v1", _mlp(), example=x, max_batch=8,
                       max_delay_ms=delay_ms)
            srv = ModelServer(reg, max_concurrent=1, queue_depth=64,
                              high_water=64)
            port = srv.start()
            members.append((reg, srv))
            urls.append(f"http://127.0.0.1:{port}")

        single = FleetRouter(urls[:1], poll_s=3600, retries=1,
                             timeout_s=30)
        single.poll_once()
        rec["single_replica"] = storm(single)

        fleet = FleetRouter(urls, poll_s=3600, retries=1, timeout_s=30)
        fleet.poll_once()
        rec["fleet3"] = storm(fleet)
        rec["scaleout"] = round(
            rec["fleet3"]["throughput_rps"]
            / max(rec["single_replica"]["throughput_rps"], 1e-9), 3)

        # leg 3: stop the replica the router would pick next, a quarter
        # of the way through the storm
        pre = failovers()
        victim = fleet._candidates("bench")[0]
        idx = next(i for i, (_, s) in enumerate(members)
                   if f":{s.port}" in victim.url)
        kill = storm(fleet, kill_at=(n_threads * per_thread) // 4,
                     kill_fn=lambda: members[idx][1].stop())
        kill["failovers"] = int(failovers() - pre)
        kill["nonshed_success_rate"] = round(
            kill["ok"] / max(kill["offered"] - kill["shed"], 1), 5)
        rec["kill_drill"] = kill
    finally:
        for reg, srv in members:
            try:
                srv.stop()
            except Exception:
                pass
            try:
                reg.drain_all(save_manifests=False)
            except Exception:
                pass
    ok, reason = check_sharded_serving(rec)
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def check_sharded_serving(rec, min_scaleout=2.0):
    """(ok, reason): gates a sharded_serving record must pass.

    - the mesh-sharded deploy must serve the same model: logits within
      float tolerance of the single-device deploy and every argmax
      identical (cross-device reduction order forbids bitwise identity
      on a >1-device mesh; decisions may never change);
    - the 3-replica storm must actually have spread (>= 2 replicas hit)
      — a ratio measured against a router that never fanned out proves
      nothing;
    - 3-replica throughput must be >= ``min_scaleout`` (2x) the single
      replica's;
    - the replica-kill drill must have recorded at least one failover
      (the dead replica was really in rotation) and lost nothing: 100%
      of non-shed requests succeed via the retry."""
    p = rec["parity"]
    if not p["allclose"] or p["argmax_match_rate"] < 1.0:
        return False, (
            f"sharded predict diverges from single-device: "
            f"allclose={p['allclose']}, argmax match "
            f"{p['argmax_match_rate']:.4f}, max |err| "
            f"{p['max_abs_err']:.2e} — the mesh deploy is not serving "
            "the same model")
    if rec["fleet3"]["replicas_hit"] < 2:
        return False, (
            f"the 3-replica storm landed on "
            f"{rec['fleet3']['replicas_hit']} replica(s): the router "
            "never spread the load, so the scale-out ratio is untested")
    if rec["scaleout"] < min_scaleout:
        return False, (
            f"3-replica throughput "
            f"{rec['fleet3']['throughput_rps']:.2f} rps is only "
            f"{rec['scaleout']:.2f}x the single replica's "
            f"{rec['single_replica']['throughput_rps']:.2f} (gate: >= "
            f"{min_scaleout}x): adding replicas is not scaling the "
            "fleet out")
    k = rec["kill_drill"]
    if k["failovers"] < 1:
        return False, (
            "the kill drill recorded no failovers: the dead replica was "
            "never routed to, so the recovery claim is untested")
    if k["nonshed_success_rate"] < 1.0:
        return False, (
            f"only {k['nonshed_success_rate']:.4f} of non-shed requests "
            "succeeded through the replica kill (gate: 100%): failover "
            "is losing requests")
    return True, "ok"


def bench_fleet_resilience(jax, jnp, tiny):
    """Tail-tolerant fleet under storm (serving/fleet): hedged requests,
    retry budget, outlier ejection, probe re-admission. Three phases
    over a 3-replica fleet of admission-limited ModelServers, all
    through one FleetRouter with background polling on:

    1. **baseline** — a fault-free 6-thread client storm. Sets the p99
       yardstick and warms the router's per-model latency samples so
       hedging is armed for phase 2.
    2. **faulted storm** — the same storm with ``fleet.dispatch``
       faults injected router-side: a 20% connection-error rate on the
       two healthy replicas, plus a fixed 10x-service-time connect
       delay on ONE replica (the outlier — its OWN ``/readyz`` and
       ``/metrics.json`` stay perfectly healthy, so only dispatch-
       outcome ejection can catch it). The router must hedge around
       the outlier, eject it on latency z-score, fail over around the
       connection errors within the retry budget, and lose zero
       non-shed requests while holding p99 <= 3x the baseline.
    3. **re-admission** — faults cleared; single requests driven until
       the ejected outlier's backoff expires and one probe request
       re-admits it.

    Gates (check_fleet_resilience): faults actually fired; zero lost
    requests in both storms; p99 ratio <= 3x; total dispatch attempts
    bounded by offered + budget allowance (hedges and retries both
    draw tokens); at least one hedge launched; the outlier ejected at
    least once and probe-re-admitted."""
    import threading

    from deeplearning4j_tpu.common import faults
    from deeplearning4j_tpu.common.metrics import registry as mreg
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.serving import ModelRegistry, ModelServer
    from deeplearning4j_tpu.serving.fleet import FleetRouter, NoReplicaError

    n_in, hidden, n_out, B = 32, 64, 8, 4
    n_threads = 6
    per_thread = 15 if tiny else 40
    delay_ms = 20.0              # no-CPU service-time floor per dispatch
    fault_rate = 0.2             # connect-error rate on healthy replicas
    outlier_delay_s = 10.0 * delay_ms / 1e3  # the 10x-latency outlier
    budget_ratio, budget_burst = 0.5, 10.0

    def _mlp(seed=0):
        b = NeuralNetConfiguration.builder().seed(seed).list()
        b.layer(DenseLayer(n_in=n_in, n_out=hidden, activation="tanh"))
        conf = b.layer(OutputLayer(n_in=hidden, n_out=n_out)).build()
        return MultiLayerNetwork(conf).init()

    x = np.random.RandomState(0).randn(B, n_in).astype(np.float32)
    body = json.dumps({"inputs": x.tolist()}).encode()
    rec = {"threads": n_threads, "requests_per_storm": n_threads * per_thread,
           "batch_delay_ms": delay_ms, "fault_rate": fault_rate,
           "outlier_delay_ms": round(outlier_delay_s * 1e3, 1),
           "budget": {"ratio": budget_ratio, "burst": budget_burst}}

    def counter(name, **want):
        fam = mreg().get(name)
        if fam is None:
            return 0.0
        idx = {k: fam.label_names.index(k) for k in want}
        return sum(c.value() for key, c in fam.children()
                   if all(key[i] == v for v, i
                          in zip(want.values(), idx.values())))

    def attempts_total():
        # every dispatch outcome except no_replica is one real HTTP
        # attempt (ok|failover|failed|passthrough|abandoned), so this
        # delta is the hedge+retry overhead denominator
        fam = mreg().get("dl4j_router_dispatch_total")
        if fam is None:
            return 0.0
        i = fam.label_names.index("outcome")
        return sum(c.value() for key, c in fam.children()
                   if key[i] != "no_replica")

    def storm(router):
        ok, shed, failed = [0], [0], [0]
        lat, hit = [], set()
        lock = threading.Lock()

        def client():
            for _ in range(per_thread):
                t0 = time.perf_counter()
                try:
                    status, _, _, url = router.route(
                        "POST", "/v1/models/bench/predict", body,
                        headers=[("Content-Type", "application/json")],
                        model="bench", timeout_s=30)
                except NoReplicaError:
                    with lock:
                        failed[0] += 1
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    if status == 200:
                        ok[0] += 1
                        lat.append(dt)
                        hit.add(url)
                    elif status == 429:
                        shed[0] += 1
                    else:
                        failed[0] += 1

        threads = [threading.Thread(target=client)
                   for _ in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return {"offered": n_threads * per_thread, "ok": ok[0],
                "shed": shed[0], "failed": failed[0],
                "throughput_rps": round(ok[0] / wall, 2),
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2)
                if lat else None,
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2)
                if lat else None,
                "replicas_hit": len(hit)}

    members, urls = [], []
    router = None
    try:
        for i in range(3):
            reg = ModelRegistry(manifest_dir=None)
            reg.deploy("bench", "v1", _mlp(), example=x, max_batch=8,
                       max_delay_ms=delay_ms)
            srv = ModelServer(reg, max_concurrent=1, queue_depth=64,
                              high_water=64)
            port = srv.start()
            members.append((reg, srv))
            urls.append(f"http://127.0.0.1:{port}")

        # enough failover headroom that a 20% connect-fault rate can't
        # exhaust distinct+second-chance attempts; fast poll so faulted
        # replicas come back into rotation between errors; short
        # ejection backoff so phase 3 probes inside the bench budget
        router = FleetRouter(urls, poll_s=0.25, retries=4, timeout_s=30,
                             retry_budget=budget_ratio,
                             retry_burst=budget_burst,
                             hedge_pctl=95, hedge_min_samples=8,
                             eject_window=12, eject_min_samples=6,
                             eject_backoff_s=0.5, eject_max_backoff_s=2.0)
        router.poll_once()
        router.start_polling()

        # -- phase 1: fault-free baseline (also warms hedge samples) ------
        rec["baseline"] = storm(router)

        # -- phase 2: faulted storm ---------------------------------------
        outlier = urls[-1]
        pre_attempts = attempts_total()
        pre_inject = counter("dl4j_faults_injected_total")
        pre_hedge = {o: counter("dl4j_fleet_hedges_total", outcome=o)
                     for o in ("launched", "won", "suppressed")}
        pre_denied = counter("dl4j_fleet_budget_denials_total")
        faults.inject("fleet.dispatch", kind="delay", rate=1.0, seed=11,
                      delay_s=outlier_delay_s,
                      predicate=lambda ctx: ctx.get("url") == outlier
                      and ctx.get("phase") == "connect")
        faults.inject("fleet.dispatch", kind="error", rate=fault_rate,
                      seed=7,
                      predicate=lambda ctx: ctx.get("url") != outlier
                      and ctx.get("phase") == "connect")
        try:
            faulted = storm(router)
        finally:
            faults.clear("fleet.dispatch")
        faulted["injected"] = int(counter("dl4j_faults_injected_total")
                                  - pre_inject)
        faulted["attempts"] = int(attempts_total() - pre_attempts)
        faulted["extra_dispatches"] = (faulted["attempts"]
                                       - faulted["offered"])
        faulted["hedges"] = {
            o: int(counter("dl4j_fleet_hedges_total", outcome=o)
                   - pre_hedge[o])
            for o in ("launched", "won", "suppressed")}
        faulted["budget_denials"] = int(
            counter("dl4j_fleet_budget_denials_total") - pre_denied)
        rec["faulted"] = faulted
        rec["p99_ratio"] = (
            round(faulted["p99_ms"] / max(rec["baseline"]["p99_ms"], 1e-9),
                  3)
            if faulted["p99_ms"] is not None
            and rec["baseline"]["p99_ms"] is not None else None)

        # -- phase 3: probe re-admission after the faults clear -----------
        def readmissions():
            return counter("dl4j_fleet_readmissions_total",
                           replica=outlier)

        deadline = time.perf_counter() + (10 if tiny else 20)
        while readmissions() < 1 and time.perf_counter() < deadline:
            try:
                router.route("POST", "/v1/models/bench/predict", body,
                             headers=[("Content-Type",
                                       "application/json")],
                             model="bench", timeout_s=30)
            except NoReplicaError:
                pass
            time.sleep(0.05)
        rec["outlier"] = {
            "url": outlier,
            "ejections": int(counter("dl4j_fleet_ejections_total",
                                     replica=outlier)),
            "readmissions": int(readmissions())}
    finally:
        if router is not None:
            router.stop_polling()
        for reg, srv in members:
            try:
                srv.stop()
            except Exception:
                pass
            try:
                reg.drain_all(save_manifests=False)
            except Exception:
                pass
    ok, reason = check_fleet_resilience(rec)
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def check_fleet_resilience(rec, max_p99_ratio=3.0):
    """(ok, reason): gates a fleet_resilience record must pass.

    - the faulted storm must actually have injected faults AND launched
      at least one hedge — a drill where nothing fired proves nothing;
    - zero lost requests in both storms: every non-shed request answers
      200 through the fault storm (failover + hedging absorb the 20%
      connect-error rate and the outlier's 10x latency);
    - faulted p99 <= ``max_p99_ratio`` x the fault-free p99 — the tail
      stays bounded while a third of the fleet is a zombie;
    - hedge+retry overhead stays inside the configured budget: extra
      dispatch attempts <= ratio x offered + burst (hedges and
      failovers draw from the same token bucket);
    - the outlier was ejected on observed dispatch outcomes and then
      probe-re-admitted once the faults cleared."""
    b, f = rec["baseline"], rec["faulted"]
    if f["injected"] < 1:
        return False, (
            "the faulted storm fired no injected faults: the resilience "
            "claim is untested")
    if b["failed"] > 0:
        return False, (
            f"{b['failed']} request(s) failed in the FAULT-FREE baseline "
            "storm: the p99 yardstick is meaningless")
    if f["failed"] > 0:
        return False, (
            f"{f['failed']} non-shed request(s) lost in the fault storm "
            "(gate: 0): hedging + budgeted failover is dropping traffic")
    if rec["p99_ratio"] is None or rec["p99_ratio"] > max_p99_ratio:
        return False, (
            f"faulted p99 {f['p99_ms']}ms is {rec['p99_ratio']}x the "
            f"fault-free {b['p99_ms']}ms (gate: <= {max_p99_ratio}x): "
            "the tail is not being hedged around the outlier")
    allowance = (rec["budget"]["ratio"] * f["offered"]
                 + rec["budget"]["burst"])
    if f["extra_dispatches"] > allowance:
        return False, (
            f"{f['extra_dispatches']} extra dispatch attempts over "
            f"{f['offered']} offered exceeds the retry budget allowance "
            f"{allowance:.1f} (ratio {rec['budget']['ratio']} x offered "
            f"+ burst {rec['budget']['burst']}): hedging is unbounded")
    if f["hedges"]["launched"] < 1:
        return False, (
            "no hedge was launched during the fault storm: the hedging "
            "path is untested (latency samples never warmed?)")
    o = rec["outlier"]
    if o["ejections"] < 1:
        return False, (
            f"the 10x-latency outlier {o['url']} was never ejected: "
            "dispatch-outcome outlier detection is not firing")
    if o["readmissions"] < 1:
        return False, (
            f"the ejected outlier {o['url']} was never probe-re-admitted "
            "after the faults cleared: ejection is permanent")
    return True, "ok"


def bench_observability_plane(jax, jnp, tiny):
    """The fleet observability plane's three contracts, proven live on
    a 3-replica fleet through the real HTTP front door:

    1. **stitched hedge trace** — after a storm warms the router's
       per-model latency samples, a connect-delay fault on every
       replica forces one traced predict to hedge; the fleet's
       ``/debug/trace/<id>`` must render ONE cross-process tree holding
       BOTH ``fleet/attempt`` spans (primary + hedge — the abandoned
       loser included) and, under the winning attempt, the replica's
       server-side ``serving/request`` → ``serving/admission`` →
       ``inference/dispatch`` subtree; the response's ``X-Trace-Id``
       must echo the trace id the client minted in ``traceparent``.
    2. **percentile parity** — the fleet's merged histogram series must
       carry bucket counts equal to the client-side pooling of every
       replica's ``/metrics.json`` buckets, with p50/p90/p99 EXACTLY
       the percentiles of that pooled distribution (bucket-wise sums,
       never an average of averages).
    3. **signals rollup** — ``/fleet/signals`` must list every replica,
       and the fleet rollup's summed capacity fields (waiters,
       queue_depth, active) must equal the sum over its own per-replica
       rows."""
    import threading
    import urllib.request

    from deeplearning4j_tpu.common import faults
    from deeplearning4j_tpu.common.environment import environment
    from deeplearning4j_tpu.common.tracing import (TraceContext,
                                                   format_traceparent,
                                                   new_span_id,
                                                   new_trace_id)
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.serving import ModelRegistry, ModelServer
    from deeplearning4j_tpu.serving.fleet import (FleetRouter, FleetServer,
                                                  histogram_quantile)

    n_in, hidden, n_out, B = 16, 32, 4, 4
    n_threads = 4
    per_thread = 10 if tiny else 25
    # the connect fault must dwarf the storm's p90 (the armed hedge
    # delay) so the hedge reliably launches while the primary sleeps
    hedge_fault_delay_s = 0.75
    fam_name = "dl4j_inference_latency_seconds"

    def _mlp(seed=0):
        b = NeuralNetConfiguration.builder().seed(seed).list()
        b.layer(DenseLayer(n_in=n_in, n_out=hidden, activation="tanh"))
        conf = b.layer(OutputLayer(n_in=hidden, n_out=n_out)).build()
        return MultiLayerNetwork(conf).init()

    x = np.random.RandomState(0).randn(B, n_in).astype(np.float32)
    body = json.dumps({"inputs": x.tolist()}).encode()
    rec = {"replicas": 3, "storm_requests": n_threads * per_thread,
           "histogram_family": fam_name}

    def _http(method, url, data=None, headers=None, timeout=30):
        req = urllib.request.Request(url, data=data,
                                     headers=dict(headers or {}),
                                     method=method)
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), resp.read()

    reg = environment().metrics()
    prev_enabled = reg.enabled
    reg.set_enabled(True)
    members, urls = [], []
    router, front = None, None
    try:
        for i in range(3):
            sreg = ModelRegistry(manifest_dir=None)
            sreg.deploy("bench", "v1", _mlp(), example=x, max_batch=8)
            srv = ModelServer(sreg, max_concurrent=4)
            port = srv.start()
            members.append((sreg, srv))
            urls.append(f"http://127.0.0.1:{port}")
        router = FleetRouter(urls, poll_s=0.25, retries=3, timeout_s=30,
                             retry_budget=0.5, retry_burst=10.0,
                             hedge_pctl=90, hedge_min_samples=8)
        router.poll_once()
        router.start_polling()
        front = FleetServer(router)
        base = f"http://127.0.0.1:{front.start()}"

        # -- phase 1: storm through the front door ------------------------
        # fills every replica's histograms and warms the router's latency
        # samples so the hedge delay is armed for phase 2
        ok_count = [0]
        lock = threading.Lock()

        def client():
            for _ in range(per_thread):
                status, _, _ = _http(
                    "POST", base + "/v1/models/bench/predict", body,
                    {"Content-Type": "application/json"})
                if status == 200:
                    with lock:
                        ok_count[0] += 1

        threads = [threading.Thread(target=client)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rec["storm_ok"] = ok_count[0]

        # -- phase 2: percentile parity -----------------------------------
        # quiesced fleet: force one synchronous scrape so the aggregator
        # holds exactly what the replicas will answer next
        router.poll_once()
        pooled = {}
        for url in urls:
            _, _, payload = _http("GET", url + "/metrics.json")
            fam = json.loads(payload).get(fam_name, {})
            for entry in fam.get("series", ()):
                labels = entry.get("labels", {})
                key = tuple(sorted(labels.items()))
                bounds = tuple(entry["bounds"])
                agg = pooled.setdefault(
                    key, [bounds, [0.0] * len(entry["bucket_counts"])])
                if agg[0] == bounds:
                    for j, c in enumerate(entry["bucket_counts"]):
                        agg[1][j] += c
        _, _, payload = _http("GET", base + "/metrics.json")
        fleet_series = json.loads(payload).get(fam_name, {}).get(
            "series", ())
        checked, max_diff, missing = 0, 0.0, 0
        for key, (bounds, counts) in pooled.items():
            if not sum(counts):
                continue
            merged = next(
                (e for e in fleet_series
                 if "replica" not in e.get("labels", {})
                 and tuple(sorted(e["labels"].items())) == key
                 and e.get("bucket_counts") == counts), None)
            if merged is None:
                missing += 1
                continue
            checked += 1
            for q, k in ((0.50, "p50"), (0.90, "p90"), (0.99, "p99")):
                want = histogram_quantile(bounds, counts, q)
                got = merged.get(k)
                if want is None or got is None:
                    max_diff = max(max_diff, float("inf")
                                   if want != got else 0.0)
                else:
                    max_diff = max(max_diff, abs(want - got))
        rec["percentile_parity"] = {"series_checked": checked,
                                    "series_missing": missing,
                                    "max_abs_diff": max_diff}

        # -- phase 3: /fleet/signals rollup consistency -------------------
        _, _, payload = _http("GET", base + "/fleet/signals")
        sig = json.loads(payload)
        rows = sig.get("replicas", {})
        fleet = sig.get("fleet", {})
        sums_ok = True
        for field in ("waiters", "queue_depth", "active"):
            for model, roll in (fleet.get("admission") or {}).items():
                want = sum(
                    (row.get("admission") or {}).get(model, {})
                    .get(field) or 0.0 for row in rows.values())
                got = roll.get(field)
                if got is None or abs(got - want) > 1e-9:
                    sums_ok = False
        rec["signals"] = {"replica_rows": len(rows),
                          "fleet_ready": fleet.get("ready"),
                          "rollup_consistent": sums_ok}

        # -- phase 4: forced hedge, stitched over real HTTP ---------------
        trace_id = new_trace_id()
        faults.inject("fleet.dispatch", kind="delay", rate=1.0, seed=5,
                      delay_s=hedge_fault_delay_s,
                      predicate=lambda ctx: ctx.get("phase") == "connect")
        try:
            status, hdrs, _ = _http(
                "POST", base + "/v1/models/bench/predict", body,
                {"Content-Type": "application/json",
                 # a real client span id: an all-zero parent-id is
                 # invalid per W3C and would be discarded downstream
                 "traceparent": format_traceparent(
                     TraceContext(trace_id, new_span_id()))})
        finally:
            faults.clear("fleet.dispatch")
        stitched = {"status": status,
                    "echoed_trace_id": hdrs.get("X-Trace-Id"),
                    "trace_id": trace_id}
        # the abandoned loser's span lands from ITS attempt thread once
        # the faulted connect wakes up — poll until the tree is whole
        deadline = time.perf_counter() + (10 if tiny else 20)
        kinds, doc = [], {}
        while time.perf_counter() < deadline:
            _, _, payload = _http("GET",
                                  base + "/debug/trace/" + trace_id)
            doc = json.loads(payload)
            kinds = [e["args"].get("kind")
                     for e in doc.get("events", ())
                     if e.get("name") == "fleet/attempt"]
            if len(kinds) >= 2 and _subtree_names(
                    doc.get("tree", ()), "fleet/attempt") \
                    >= {"serving/request", "serving/admission",
                        "inference/dispatch"}:
                break
            time.sleep(0.1)
        stitched["attempt_kinds"] = sorted(kinds)
        stitched["outcomes"] = sorted(
            e["args"].get("outcome") for e in doc.get("events", ())
            if e.get("name") == "fleet/attempt")
        stitched["replicas_stitched"] = doc.get("replicas", [])
        stitched["winner_subtree"] = sorted(_subtree_names(
            doc.get("tree", ()), "fleet/attempt"))
        rec["stitched"] = stitched
    finally:
        reg.set_enabled(prev_enabled)
        if front is not None:
            try:
                front.stop()
            except Exception:
                pass
        if router is not None:
            router.stop_polling()
        for sreg, srv in members:
            try:
                srv.stop()
            except Exception:
                pass
            try:
                sreg.drain_all(save_manifests=False)
            except Exception:
                pass
    ok, reason = check_observability_plane(rec)
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def _subtree_names(tree, root_name):
    """Every span name that appears under a node named `root_name`
    anywhere in a span_tree — the 'what hangs under the attempts'
    probe for the stitched-trace gate."""
    names = set()

    def walk(nodes, inside):
        for n in nodes:
            hit = inside or n.get("name") == root_name
            if inside:
                names.add(n.get("name"))
            walk(n.get("children", ()), hit)

    walk(tree, False)
    return names


def check_observability_plane(rec):
    """(ok, reason): gates an observability_plane record must pass.

    - the storm lost nothing (a broken fleet invalidates the rest);
    - the hedged predict answered 200 and echoed the client's minted
      trace id in ``X-Trace-Id`` — trace context survived front door →
      router → replica and back;
    - the stitched tree holds BOTH attempt spans (a ``primary`` and a
      ``hedge``) and the winner's server-side subtree
      (``serving/request`` → ``serving/admission`` →
      ``inference/dispatch``) — one trace for one logical request,
      however many processes served it;
    - fleet-merged percentiles are EXACT: at least one histogram series
      checked, none missing from the fleet exposition, zero difference
      vs percentiles over the pooled per-replica buckets;
    - ``/fleet/signals`` lists all 3 replicas and its fleet rollup sums
      match its own per-replica rows."""
    if rec["storm_ok"] < rec["storm_requests"]:
        return False, (
            f"only {rec['storm_ok']}/{rec['storm_requests']} storm "
            "requests answered 200: the fleet under test is unhealthy")
    st = rec["stitched"]
    if st["status"] != 200:
        return False, (
            f"the hedged predict answered {st['status']}, not 200")
    if st["echoed_trace_id"] != st["trace_id"]:
        return False, (
            f"X-Trace-Id {st['echoed_trace_id']} != minted trace id "
            f"{st['trace_id']}: trace context was dropped on the "
            "front-door path")
    kinds = st["attempt_kinds"]
    if "hedge" not in kinds or "primary" not in kinds:
        return False, (
            f"stitched trace holds attempt kinds {kinds}: need both the "
            "primary and the hedge span in ONE trace")
    want = {"serving/request", "serving/admission", "inference/dispatch"}
    if not want <= set(st["winner_subtree"]):
        return False, (
            f"winner subtree {st['winner_subtree']} is missing "
            f"{sorted(want - set(st['winner_subtree']))}: the replica's "
            "server-side spans did not stitch under the fleet attempt")
    par = rec["percentile_parity"]
    if par["series_checked"] < 1:
        return False, "no histogram series had observations to check"
    if par["series_missing"] > 0:
        return False, (
            f"{par['series_missing']} pooled series missing from the "
            "fleet /metrics.json merged exposition")
    if par["max_abs_diff"] > 0.0:
        return False, (
            f"fleet-merged percentiles differ from pooled-bucket "
            f"percentiles by {par['max_abs_diff']}: the merge is not "
            "exact")
    sig = rec["signals"]
    if sig["replica_rows"] != rec["replicas"]:
        return False, (
            f"/fleet/signals lists {sig['replica_rows']} replicas, "
            f"expected {rec['replicas']}")
    if not sig["rollup_consistent"]:
        return False, (
            "/fleet/signals fleet rollup does not equal the sum of its "
            "own per-replica rows")
    return True, "ok"


def bench_fleet_cold_start(jax, jnp, tiny):
    """Fleet-scale cold start over the shared artifact store (the
    ArtifactStore tentpole's headline): with DL4J_TPU_REMOTE_CACHE
    pointed at a shared filesystem-rooted store, a second "replica"
    booting with an EMPTY local cache must reach ready (full ladder
    warmed + first inference served) with zero live compiles — every
    bucket a store hit, pulled from the remote — and in <= 1.2x the
    time-to-ready of a fully-warm local restart. Three phases, each a
    fresh network/engine + jax.clear_caches() (a process restart in
    miniature): seed (replica 1 compiles and write-populates local +
    remote), warm_restart (replica 1 again, all local hits — the
    baseline), cold_join (replica 2: empty local dir, everything pulled
    from the shared store)."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.common.environment import (SystemProperties,
                                                       environment)
    from deeplearning4j_tpu.common.metrics import registry
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.runtime import compile_cache
    from deeplearning4j_tpu.runtime.inference import InferenceEngine

    # same sizing as bench_cold_start: deep enough that XLA compile time
    # (what the store removes) dominates the cold path
    n_in, hidden, n_out, depth = (16, 64, 4, 8) if tiny \
        else (256, 1024, 64, 12)
    max_batch = 8 if tiny else 32

    def build():
        b = NeuralNetConfiguration.builder().seed(0).list()
        b.layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
        for _ in range(depth - 2):
            b.layer(DenseLayer(n_in=hidden, n_out=hidden,
                               activation="relu"))
        conf = b.layer(OutputLayer(n_in=hidden, n_out=n_out)).build()
        return MultiLayerNetwork(conf).init()

    def live_compiles():
        # miss/bypass = XLA actually ran (or would have): what a warm
        # joiner must record zero of. hit = loaded from the store.
        fam = registry().get("dl4j_compiles_total")
        out = {"live": 0, "hit": 0}
        for key, child in (fam.children() if fam else []):
            if len(key) == 2:
                out["live" if key[1] in ("miss", "bypass")
                    else "hit"] += int(child.value())
        return out

    rng = np.random.RandomState(0)
    x = rng.randn(3, n_in).astype(np.float32)

    env = environment()
    saved = {p: env.property_override(p)
             for p in (SystemProperties.CACHE_DIR,
                       SystemProperties.REMOTE_CACHE,
                       SystemProperties.CACHE_TIER)}
    root = tempfile.mkdtemp(prefix="dl4j-fleet-cold-")
    dirs = {name: os.path.join(root, name)
            for name in ("remote", "local1", "local2")}
    rec = {"max_batch": max_batch, "model_depth": depth}
    keep = []  # nets stay alive so id()-keyed compile tags never collide
    try:
        env.set_remote_cache(dirs["remote"])
        env.set_cache_tier("auto")
        for phase, local in (("seed", "local1"),
                             ("warm_restart", "local1"),
                             ("cold_join", "local2")):
            env.set_cache_dir(dirs[local])
            compile_cache.reset_cache()
            jax.clear_caches()
            cc = compile_cache.cache()
            c0, h0 = live_compiles(), cc.stats["hits"]
            net = build()
            keep.append(net)
            eng = InferenceEngine(net, max_batch=max_batch)
            # time-to-ready: what /readyz gates on — the full ladder
            # warmed plus the first real inference answered
            t0 = time.perf_counter()
            warmed = eng.warmup(jnp.asarray(x))
            jax.block_until_ready(eng.infer(jnp.asarray(x)).jax())
            ttr = time.perf_counter() - t0
            c1 = live_compiles()
            rec[phase] = {
                "ttr_s": round(ttr, 4),
                "buckets_warmed": len(warmed),
                "live_compiles": c1["live"] - c0["live"],
                "hit_compiles": c1["hit"] - c0["hit"],
                "store_hits": cc.stats["hits"] - h0,
            }
            eng.close(timeout_s=10.0)
        remote_stat = compile_cache.RemoteStore(dirs["remote"]).stat()
        rec["remote_entries"] = remote_stat["entries"]
        rec["remote_bytes"] = remote_stat["bytes"]
    finally:
        for prop, value in saved.items():
            if value is None:
                env.clear_property(prop)
            else:
                env.set_property(prop, value)
        compile_cache.reset_cache()
        shutil.rmtree(root, ignore_errors=True)
    rec["ttr_ratio"] = round(
        rec["cold_join"]["ttr_s"] / max(rec["warm_restart"]["ttr_s"],
                                        1e-9), 3)
    ok, reason = check_fleet_cold_start(rec)
    rec["gate_ok"], rec["gate_reason"] = ok, reason
    return rec


def check_fleet_cold_start(rec, max_ratio=1.2):
    """(ok, reason): gates a fleet_cold_start record must pass.

    - the seed phase must have published executables to the shared store
      (remote_entries > 0) — without that the "cold join" would just be
      measuring local recompiles;
    - the cold joiner must record ZERO live (miss/bypass) compiles: its
      whole ladder must resolve as store hits, at least one per warmed
      bucket — the download-don't-compile contract;
    - the joiner's time-to-ready must be <= ``max_ratio`` (1.2x) of the
      fully-warm local restart's: pulling from the shared store may cost
      a transfer, never a compile-shaped wait."""
    if rec.get("remote_entries", 0) <= 0:
        return False, ("the seed phase published no executables to the "
                       "shared store: nothing for a joiner to pull, the "
                       "cold-join claim is untested")
    cold = rec["cold_join"]
    if cold.get("live_compiles", 0) > 0:
        return False, (
            f"the cold joiner ran {cold['live_compiles']} live "
            "compile(s) (gate: 0): its empty local cache was not fully "
            "served by the shared store")
    if cold.get("store_hits", 0) < cold.get("buckets_warmed", 0):
        return False, (
            f"the cold joiner loaded {cold['store_hits']} executable(s) "
            f"from the store for {cold['buckets_warmed']} warmed "
            "buckets: part of the ladder came from somewhere other than "
            "the shared store")
    ratio = rec["cold_join"]["ttr_s"] / max(rec["warm_restart"]["ttr_s"],
                                            1e-9)
    if ratio > max_ratio:
        return False, (
            f"cold-join time-to-ready {rec['cold_join']['ttr_s']:.4f}s "
            f"is {ratio:.2f}x the fully-warm restart's "
            f"{rec['warm_restart']['ttr_s']:.4f}s (gate: <= "
            f"{max_ratio}x): the store pull is not bounding the "
            "joiner's cold start")
    return True, "ok"


def bench_flash_attention(jax, jnp, tiny):
    """Pallas flash attention vs XLA attention at long sequence length.

    Timing runs N chained iterations inside ONE jitted lax.scan with a
    scalar readback, so one dispatch's wall time covers N kernels."""
    from deeplearning4j_tpu.kernels import flash_attention

    B, S, H, D = (1, 256, 2, 32) if tiny else (4, 2048, 12, 64)
    N = 3 if tiny else 20
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    q, k, v = mk(), mk(), mk()

    def xla_attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def timed(fn, grad):
        if grad:
            def one(c):
                d = jax.grad(lambda a: jnp.sum(fn(a, k, v) ** 2))(c)
                return c - 1e-6 * d
        else:
            def one(c):
                return fn(c, k, v)

        @jax.jit
        def many(q):
            out, _ = jax.lax.scan(lambda c, _: (one(c), ()), q, None,
                                  length=N)
            return jnp.sum(out)

        float(many(q))  # compile + warm
        runs = []
        for _ in range(3):
            t0 = time.perf_counter()
            float(many(q))
            runs.append((time.perf_counter() - t0) / N)
        return sorted(runs)[1]  # median

    fwd = timed(xla_attn, False) / timed(flash_attention, False)
    train = timed(xla_attn, True) / timed(flash_attention, True)
    return fwd, train


def bench_ring_flash(jax, jnp, tiny):
    """Single-chip ring(flash)-vs-monolithic-flash overhead ratio.

    On a 1-device seq mesh the ring path degenerates to one scan step
    around the same Pallas kernel, so the ratio isolates what the SP
    wrapper (shard_map + scan + merge) costs over calling the kernel
    directly. ~1.0 means composing flash into the ring is free on-chip;
    the multi-chip win comes from the ppermute overlap the dryrun checks.
    """
    from deeplearning4j_tpu.kernels import flash_attention
    from deeplearning4j_tpu.parallel.mesh import MeshConfig, make_mesh
    from deeplearning4j_tpu.parallel.ring_attention import ring_attention

    B, S, H, D = (1, 256, 2, 32) if tiny else (4, 2048, 12, 64)
    N = 3 if tiny else 8
    mesh = make_mesh(MeshConfig(data=1, seq=1), devices=jax.devices()[:1])
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    q, k, v = mk(), mk(), mk()

    def timed(fn):
        @jax.jit
        def many(q):
            out, _ = jax.lax.scan(lambda c, _: (fn(c), ()), q, None,
                                  length=N)
            return jnp.sum(out)

        float(many(q))  # compile + warm
        runs = []
        for _ in range(3):
            t0 = time.perf_counter()
            float(many(q))
            runs.append((time.perf_counter() - t0) / N)
        return sorted(runs)[1]

    t_mono = timed(lambda c: flash_attention(c, k, v))
    t_ring = timed(lambda c: ring_attention(c, k, v, mesh, use_flash=True))
    return t_mono / t_ring


def bench_flash_longseq(jax, jnp, tiny):
    """S=8192 attention training step: the XLA path cannot even compile on
    one chip (the [B,H,S,S] f32 score tensor is 12.9 GB / blows scoped
    vmem); the Pallas fwd+bwd kernels train it in O(S) memory."""
    from deeplearning4j_tpu.kernels import flash_attention

    B, S, H, D = (1, 512, 2, 32) if tiny else (4, 8192, 12, 64)
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
               for _ in range(3)]
    g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v)
                                                 ** 2), argnums=(0, 1, 2)))
    out = g(q, k, v)
    jax.block_until_ready(out)
    return "ok"


def main():
    # jax's persistent compile cache stays where the outside put it;
    # otherwise one fixed directory in the checkout (the path is part of
    # the cache key — a directory that moves never hits). jax reads the
    # variable at import. The cold-start sections move only the
    # executable store (DL4J_TPU_CACHE_DIR), which jax's cache no longer
    # follows once this is set. A run pinned to the CPU (a BENCH_TINY
    # rehearsal) places none: XLA:CPU proved unstable reloading donated
    # train steps (see compile_cache._backstop_wanted).
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".jax_cache"))
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    platform = dev.platform
    tiny = bool(os.environ.get("BENCH_TINY"))
    skip_extras = bool(os.environ.get("BENCH_SKIP_EXTRAS"))

    peak = _peak_flops(dev)
    r = bench_bert(jax, jnp, tiny, peak)
    name, rec = select_headline(r["variants"])  # raises if none sane

    out = {
        "metric": "bert_base_mlm_train_samples_per_sec_per_chip",
        "value": round(rec["samples_per_sec"], 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(rec["mfu"] / 0.35, 4),  # 35% MFU == 1.0
        "mfu": round(rec["mfu"], 4),
        "batch": r["B"], "seq_len": r["T"], "platform": platform,
        "loss": round(rec["loss_last"], 4),
        "flash_attn": rec["variant"].get("use_flash", False),
        # measurement methodology: one jitted lax.scan of n_chained steps
        # per dispatch, median of 5 dispatches, spread = (max-min)/median
        "n_chained_steps": r["n_chained"],
        "time_spread_pct": rec["spread_pct"],
        "bert_variants": {
            k: {"samples_per_sec": round(v["samples_per_sec"], 2),
                "mfu": round(v["mfu"], 4), "sane": v["sane"],
                "reason": v["reason"]}
            for k, v in r["variants"].items()},
    }

    import gc

    def _release():
        # free HBM held by dead params + jit executable caches so later
        # sections (flash S=2048 grad needs multi-GB live) never OOM
        # against buffers leaked from earlier ones
        gc.collect()
        jax.clear_caches()

    # a section that raises is still recorded, as "error: <type>", so the
    # other sections' numbers survive — but main() then exits non-zero: a
    # record with a hole in it is never a passing run
    errored = [f"bert_variants.{k}" for k, v in r["variants"].items()
               if v["reason"].startswith("error:")]

    def section(key, fn):
        try:
            out[key] = fn()
        except Exception as e:
            out[key] = f"error: {type(e).__name__}"
            errored.append(key)
        _release()

    def flash_speedups():
        fwd, train = bench_flash_attention(jax, jnp, tiny)
        out["flash_attn_train_speedup_vs_xla"] = round(train, 3)
        return round(fwd, 3)

    if not skip_extras:
        for key, fn in (
                ("resnet50_imgs_per_sec", bench_resnet50),
                ("vgg16_imgs_per_sec", bench_vgg16),
                ("lenet_imgs_per_sec", bench_lenet),
                ("word2vec_words_per_sec", bench_word2vec),
                ("seq2seq_samples_per_sec", bench_seq2seq)):
            section(key, lambda fn=fn: round(fn(jax, jnp, tiny), 2))
        # vision MFU: same peak table as the headline, so the ResNet/VGG
        # utilization gap is visible in the artifact itself
        if peak and not tiny:
            for key, model in (("resnet50_imgs_per_sec", "resnet50"),
                               ("vgg16_imgs_per_sec", "vgg16")):
                v = out.get(key)
                if isinstance(v, (int, float)):
                    out[f"{model}_mfu"] = round(
                        v * VISION_TRAIN_FLOPS_PER_IMG[model] / peak, 4)
        for key, fn in (
                ("inference_serving", bench_inference_serving),
                ("train_memory", bench_train_memory),
                ("telemetry_overhead", bench_telemetry_overhead),
                ("cold_start", bench_cold_start),
                ("serving_overload", bench_serving_overload),
                ("generative_decode", bench_generative_decode),
                ("prefix_reuse", bench_prefix_reuse),
                ("quantized_inference", bench_quantized_inference),
                ("pallas_decode", bench_pallas_decode),
                ("serving_resilience", bench_serving_resilience),
                ("static_analysis", bench_static_analysis),
                ("sharded_serving", bench_sharded_serving),
                ("fleet_resilience", bench_fleet_resilience),
                ("observability_plane", bench_observability_plane),
                ("fleet_cold_start", bench_fleet_cold_start)):
            section(key, lambda fn=fn: fn(jax, jnp, tiny))
        section("flash_attn_speedup_vs_xla", flash_speedups)
        section("ring_flash_fwd_vs_monolithic",
                lambda: round(bench_ring_flash(jax, jnp, tiny), 3))
        section("flash_attn_s8192_train",
                lambda: bench_flash_longseq(jax, jnp, tiny))

    if os.environ.get("BENCH_OPS"):
        # optional per-op microbench sweep (see benchmarks/opbench.py); off
        # by default — it adds minutes and its output is a file, not a key
        def opbench():
            from deeplearning4j_tpu.benchmarks.opbench import run_opbench
            ops = run_opbench(n_iter=5 if tiny else 20)
            with open("OPBENCH.json", "w") as f:
                json.dump(ops, f, indent=1)
            return ops["n_benched"]

        section("opbench_n", opbench)

    print(json.dumps(out))
    if errored:
        sys.exit(f"bench: {len(errored)} section(s) raised: "
                 + ", ".join(errored))


if __name__ == "__main__":
    main()
