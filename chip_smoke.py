"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: train, serve, store, kernels
    python chip_smoke.py --chips 4   # one four-chip host: the sharded paths only

One process, no child that needs JAX (a chip belongs to one process at a
time). Every phase drives the normal entry points at the full width of a
model the repo supports, with random weights made from a seed, checks what
comes out, and prints one JSON line. A phase that raises or fails a check
ends the run with a traceback and a non-zero exit: nothing here catches a
phase's exception and carries on. On anything but a TPU the script exits
non-zero before any phase and prints no result. The last line of a passing
run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Every figure on the earlier lines is a smoke run's wall time, compile
included — not a benchmark.

JAX's persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says; when the outside has not spoken it is ``.jax_cache/`` under this
checkout — one fixed path, because the path is part of the cache key. The
executable store the *store* phase exercises lives under that directory
too and is emptied first, so its first deploy writes what its second reads.
"""
import argparse
import json
import os
import shutil
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

#: The one tolerance for every comparison of two paths. The models are
#: bf16 (8 significant bits), so two correct reductions in different
#: order differ by a few units in the last place of their largest
#: element: outputs and logits must agree within 2**-5 (eight bf16 ulps)
#: of the reference's largest magnitude (at least 1), and greedy tokens
#: must be equal wherever the reference's top-two logit margin exceeds
#: that same bound.
TOL = 2.0 ** -5


class SmokeFailure(AssertionError):
    """A check of this script was false."""


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _bound(ref) -> float:
    import numpy as np
    return TOL * max(1.0, float(np.max(np.abs(np.asarray(ref, np.float32)))))


def close(ref, got, what: str) -> float:
    """Max abs error of ``got`` against ``ref``; fails past the margin."""
    import numpy as np
    ref = np.asarray(ref, np.float32)
    got = np.asarray(got, np.float32)
    check(ref.shape == got.shape, f"{what}: shape {got.shape} != {ref.shape}")
    check(bool(np.all(np.isfinite(got))), f"{what}: non-finite values")
    err = float(np.max(np.abs(ref - got)))
    check(err <= _bound(ref),
          f"{what}: max abs error {err:.4g} > {_bound(ref):.4g}")
    return err


def decided(ref_logits):
    """(argmax, decided) per row of reference logits ``[..., V]``: a row
    is decided when its top-two margin exceeds the tolerance."""
    import numpy as np
    ref = np.asarray(ref_logits, np.float32)
    top2 = np.partition(ref, -2, axis=-1)[..., -2:]
    return ref.argmax(-1), (top2[..., 1] - top2[..., 0]) > _bound(ref)


def tokens_agree(ref_logits, tokens, what: str) -> dict:
    """Margin rule, teacher-forced: ``tokens[i]`` must be the argmax of
    ``ref_logits[i]`` wherever that row is decided."""
    import numpy as np
    want, sure = decided(ref_logits)
    tokens = np.asarray(tokens)
    same = want == tokens
    check(bool(np.all(same | ~sure)),
          f"{what}: token differs from the reference argmax at a decided "
          f"position (rows {np.nonzero(~same & sure)[0].tolist()[:8]})")
    return {"agreement": round(float(same.mean()), 4),
            "decided": round(float(sure.mean()), 4)}


def same_continuation(ref_tokens, ref_sure, tokens, what: str) -> float:
    """Two greedy continuations of one prompt: equal, or first differing
    at a position the reference left undecided (after which they
    legitimately part). Returns the share of equal positions."""
    check(len(tokens) == len(ref_tokens), f"{what}: lengths differ")
    same = [a == b for a, b in zip(ref_tokens, tokens)]
    if not all(same):
        first = same.index(False)
        check(not bool(ref_sure[first]),
              f"{what}: continuations part at decided position {first}")
    return round(sum(same) / max(len(same), 1), 4)


def _children(family: str):
    from deeplearning4j_tpu.common.metrics import registry
    fam = registry().get(family)
    return fam.children() if fam else []


def compile_labels() -> dict:
    """``dl4j_compiles_total`` summed by its ``cache=`` label
    (hit / miss / bypass)."""
    out = {}
    for (_, cache), child in _children("dl4j_compiles_total"):
        out[cache] = out.get(cache, 0) + int(child.value())
    return out


def compile_observations() -> dict:
    """``dl4j_compile_seconds`` observation counts by ``kind/cache``; here
    the cache label carries its reason (``bypass:donation`` ...)."""
    return {f"{kind}/{cache}": int(child.count())
            for (kind, cache), child in _children("dl4j_compile_seconds")
            if child.count()}


def cache_errors() -> dict:
    """The observations under an error label of the executable store
    (``bypass:deserialize-error``, ``:lower-error``, ``:compile-error``,
    ``:serialize-error``, ``:store-error``, ``:call-error``). Ineligible
    entries (``bypass:donation`` ...) are by design and not counted."""
    return {k: v for k, v in compile_observations().items()
            if k.endswith("-error")}


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def device_ids(tree) -> list:
    import jax
    return sorted({d.id for leaf in jax.tree_util.tree_leaves(tree)
                   for d in leaf.sharding.device_set})


def mlm_batch(config, B: int, T: int, seed: int):
    """The ``bench_bert`` batch: random ids, 15% of positions labelled."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(seed)
    return {
        "input_ids": jnp.asarray(rng.randint(0, config.vocab_size, (B, T)),
                                 jnp.int32),
        "labels": jnp.asarray(
            np.where(rng.rand(B, T) < 0.15,
                     rng.randint(0, config.vocab_size, (B, T)), -100),
            jnp.int32),
        "attention_mask": jnp.ones((B, T), jnp.int32),
    }


def reference_logits(model, prompt, tokens):
    """Full-sequence causal forward (plain XLA, no cache) over
    ``prompt + tokens``: the logits that decided each generated token."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.models import causal_lm

    seq = list(prompt) + list(tokens)
    pad = -(-len(seq) // 64) * 64  # causal: right padding changes nothing
    ids = np.zeros((1, pad), np.int32)
    ids[0, :len(seq)] = seq
    fwd = jax.jit(lambda p, x: causal_lm.forward(p, x, model.config))
    logits = fwd(model.params, jnp.asarray(ids))[0]
    return np.asarray(logits[len(prompt) - 1:len(seq) - 1], np.float32)


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def phase_train(config, B: int, T: int, steps: int = 5, seed: int = 0):
    """BERT MLM through ``make_train_step`` on one device: ``steps`` steps
    on one batch; losses finite, not all equal, last below first."""
    import jax
    import numpy as np
    from deeplearning4j_tpu.models import bert

    t0 = time.perf_counter()
    params = bert.init_params(jax.random.key(seed), config)
    opt = bert.init_opt_state(params)
    step = bert.make_train_step(config, None, remat=False)
    batch = mlm_batch(config, B, T, seed)
    losses, secs = [], []
    for it in range(steps):
        t1 = time.perf_counter()
        params, opt, loss = step(params, opt, batch, it)
        jax.block_until_ready(loss)
        secs.append(round(time.perf_counter() - t1, 4))
        losses.append(float(loss))
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    check(len(set(losses)) > 1, f"losses all equal: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return {"B": B, "T": T, "params": bert.count_params(params),
            "losses": [round(l, 4) for l in losses],
            "first_step_seconds_with_compile": secs[0],
            "step_seconds": secs[1:],
            "seconds": round(time.perf_counter() - t0, 2),
            "peak_bytes_in_use": peak_bytes()}


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def _post(url: str, doc: dict):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=300)


def phase_serve(config, *, slots: int, max_ctx: int, buckets,
                prompt_lens, gen_lens, greedy_prompt: int = 24,
                greedy_tokens: int = 16, seed: int = 0):
    """A decoder behind ``ModelRegistry.deploy`` + ``ModelServer``:
    greedy, streamed and concurrent requests over HTTP."""
    import numpy as np
    from deeplearning4j_tpu.common.environment import environment
    from deeplearning4j_tpu.kernels import dispatch_snapshot
    from deeplearning4j_tpu.models import causal_lm
    from deeplearning4j_tpu.serving import ModelRegistry, ModelServer

    t0 = time.perf_counter()
    errs0, seen0 = cache_errors(), compile_observations()
    model = causal_lm.CausalLM(config, seed=seed)
    registry = ModelRegistry(manifest_dir=None)
    server = ModelServer(registry)
    rng = np.random.RandomState(seed)
    mk = lambda n: [int(t) for t in rng.randint(0, config.vocab_size, n)]
    try:
        registry.deploy("lm", "v1", model, decode_slots=slots,
                        decode_max_ctx=max_ctx,
                        decode_prompt_buckets=list(buckets))
        deploy_s = time.perf_counter() - t0
        warm_compiles = environment().compile_count()
        base = f"http://127.0.0.1:{server.start()}/v1/models/lm/generate"

        prompt = mk(greedy_prompt)
        r = _post(base, {"prompt": prompt, "max_tokens": greedy_tokens})
        check(r.status == 200, f"greedy: HTTP {r.status}")
        first = json.loads(r.read())
        check(len(first["tokens"]) == greedy_tokens,
              f"greedy: {len(first['tokens'])} tokens, asked "
              f"{greedy_tokens}")

        r = _post(base, {"prompt": mk(greedy_prompt), "max_tokens": 8,
                         "stream": True})
        check(r.status == 200, f"stream: HTTP {r.status}")
        lines = [json.loads(l) for l in r if l.strip()]
        streamed = [d["token"] for d in lines if "token" in d]
        check(len(streamed) == 8, f"stream: {len(streamed)} tokens, asked 8")
        check("finish_reason" in lines[-1], "stream: no closing line")

        results, errors = {}, []

        def one(i, p, gen):
            try:
                r = _post(base, {"prompt": p, "max_tokens": gen})
                results[i] = (r.status, json.loads(r.read()))
            except Exception as e:  # re-raised below, on the main thread
                errors.append(e)

        threads = [threading.Thread(target=one, args=(i, mk(p), g))
                   for i, (p, g) in enumerate(zip(prompt_lens, gen_lens))]
        t1 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        burst_s = time.perf_counter() - t1
        if errors:
            raise errors[0]
        for i, g in enumerate(gen_lens):
            status, doc = results[i]
            check(status == 200, f"concurrent {i}: HTTP {status}")
            check(len(doc["tokens"]) == g,
                  f"concurrent {i}: {len(doc['tokens'])} tokens, asked {g}")

        r = _post(base, {"prompt": prompt, "max_tokens": greedy_tokens})
        check(r.status == 200, f"repeat: HTTP {r.status}")
        second = json.loads(r.read())

        # margin rule against the uncached full forward
        ref = reference_logits(model, prompt, first["tokens"])
        agree = tokens_agree(ref, first["tokens"], "greedy vs forward")
        repeat = same_continuation(first["tokens"], decided(ref)[1],
                                   second["tokens"], "repeat vs first")
        stats = registry.get("lm").engine.stats()
        steady = environment().compile_count() - warm_compiles
        # the reference forward above is a plain jax.jit, outside the
        # recompile counter: the count is the serving path's alone
        check(steady == 0, f"{steady} compiles after warm-up")
        errs = delta(cache_errors(), errs0)
        check(not errs, f"executable-store errors: {errs}")
    finally:
        server.stop()
        registry.drain_all(save_manifests=False)
    return {"config": {"vocab": config.vocab_size,
                       "hidden": config.hidden_size,
                       "layers": config.num_layers,
                       "heads": config.num_heads, "max_ctx": max_ctx},
            "params": causal_lm.count_params(model.params),
            "deploy_seconds_with_compile": round(deploy_s, 2),
            "compiles_by_kind_and_cache": delta(compile_observations(),
                                                seen0),
            "compiles_after_warmup": steady,
            "greedy_tokens": first["tokens"],
            "greedy_ttft_s": first["ttft_s"],
            "greedy_vs_forward": agree, "repeat_same_share": repeat,
            "prefix_hits": stats.get("prefix_hits"),
            "concurrent": {"requests": len(gen_lens),
                           "tokens": int(sum(gen_lens)),
                           "seconds": round(burst_s, 3)},
            "decode_steps": stats.get("decode_steps"),
            "dispatch": dispatch_snapshot(),
            "seconds": round(time.perf_counter() - t0, 2),
            "peak_bytes_in_use": peak_bytes()}


# ---------------------------------------------------------------------------
# phase: predict + store
# ---------------------------------------------------------------------------

def _as_numpy(out):
    import numpy as np
    if isinstance(out, (list, tuple)):
        return [np.asarray(o.jax()) for o in out]
    return [np.asarray(out.jax())]


def phase_store(build, x, store_dir: str, name: str = "model"):
    """Deploy ``build()`` for predict, reset the executable store, deploy
    a second ``build()``: the second's executables must load from what
    the first wrote (``hit``), and answer the same."""
    import numpy as np
    from deeplearning4j_tpu.common.environment import (SystemProperties,
                                                       environment)
    from deeplearning4j_tpu.runtime import compile_cache
    from deeplearning4j_tpu.serving import ModelRegistry

    t0 = time.perf_counter()
    errs0 = cache_errors()
    env = environment()
    prev = env.property_override(SystemProperties.CACHE_DIR)
    shutil.rmtree(store_dir, ignore_errors=True)
    env.set_cache_dir(store_dir)
    compile_cache.reset_cache()
    registry = ModelRegistry(manifest_dir=None)
    B = int(x.shape[0])
    rec = {}
    try:
        outs = []
        for version in ("v1", "v2"):
            before = compile_labels()
            t1 = time.perf_counter()
            registry.deploy(name, version, build(), max_batch=B, example=x)
            deploy_s = time.perf_counter() - t1
            outs.append(_as_numpy(registry.predict(name, x)))
            rec[version] = {
                "deploy_seconds": round(deploy_s, 2),
                "compiles_by_cache": delta(compile_labels(), before)}
            if version == "v1":
                compile_cache.reset_cache()
        for o in outs[0]:
            check(o.shape[0] == B and bool(np.all(np.isfinite(
                o.astype(np.float32)))), "predict: bad output")
        first, second = (rec[v]["compiles_by_cache"] for v in ("v1", "v2"))
        check(first.get("miss", 0) >= 1 and not first.get("hit"),
              f"first deploy should compile and store: {first}")
        check(second.get("hit", 0) >= 1 and set(second) == {"hit"},
              f"second deploy should load every entry from the store: "
              f"{second}")
        check(second["hit"] == first["miss"],
              f"hits {second['hit']} != entries written {first['miss']}")
        for a, b in zip(*outs):
            check(np.array_equal(a, b),
                  "stored executable answers differently from the "
                  "compiled one")
        errs = delta(cache_errors(), errs0)
        check(not errs, f"executable-store errors: {errs}")
        rec["store_stats"] = dict(compile_cache.cache().stats)
    finally:
        registry.drain_all(save_manifests=False)
        if prev is None:
            env.clear_property(SystemProperties.CACHE_DIR)
        else:
            env.set_property(SystemProperties.CACHE_DIR, prev)
        compile_cache.reset_cache()
    rec.update(batch=B, output_shapes=[list(o.shape) for o in outs[0]],
               seconds=round(time.perf_counter() - t0, 2),
               peak_bytes_in_use=peak_bytes())
    return rec


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

def _kernel_flash(shape, dtype, seed):
    """``flash_attention`` against jnp attention at [B, S, H, D], forward
    and gradient."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.kernels import (attention_dispatch,
                                            dispatch_snapshot,
                                            flash_attention)

    B, H, S, D = shape
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k, v, ct = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
                   .astype(dtype) for kk in keys)

    def xla_attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * (D ** -0.5)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    # the decision a flash=True model takes at this length
    check(attention_dispatch(S, head_dim=D) == "flash",
          f"attention_dispatch({S}, head_dim={D}) did not pick the kernel")
    snap = dispatch_snapshot()["attention"]

    def grads(fn):
        loss = lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * ct.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    rec = {"shape_BHSD": list(shape), "dtype": str(jnp.dtype(dtype)),
           "dispatch": snap,
           "fwd_max_err": close(jax.jit(xla_attn)(q, k, v),
                                jax.jit(flash_attention)(q, k, v),
                                "flash forward")}
    for name, a, b in zip("qkv", grads(xla_attn), grads(flash_attention)):
        rec[f"d{name}_max_err"] = close(a, b, f"flash d{name}")
    return rec


def _kernel_paged(config, *, slots, max_ctx, bucket, steps, seed):
    """``paged_decode`` of the serve model, kernel forced on against the
    gather, ``steps`` teacher-forced decode steps over a prefilled pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.common.environment import environment
    from deeplearning4j_tpu.kernels import dispatch_snapshot
    from deeplearning4j_tpu.models import causal_lm

    env = environment()
    model = causal_lm.CausalLM(config, seed=seed)
    bs = min(env.kv_block_size(), max_ctx)
    mb = -(-max_ctx // bs)
    rng = np.random.RandomState(seed)
    lengths = rng.randint(bucket // 4, bucket + 1, slots).astype(np.int32)
    ids = np.zeros((slots, bucket), np.int32)
    for s, n in enumerate(lengths):
        ids[s, :n] = rng.randint(0, config.vocab_size, n)
    # slot s owns blocks 1 + s*mb ... (block 0 is the scratch block)
    tables = (1 + np.arange(slots * mb, dtype=np.int32)).reshape(slots, mb)
    cache = model.init_paged_kv_cache(slots * mb + 1, bs)
    cache, logits = jax.jit(model.paged_prefill, donate_argnums=(1,))(
        model.params, cache, jnp.asarray(ids), jnp.asarray(tables),
        jnp.asarray(lengths))
    tok0 = np.asarray(jnp.argmax(logits, -1), np.int32)

    def run(mode, feed):
        """``steps`` decode steps; ``feed`` None = greedy on own argmax."""
        env.set_paged_kernel(mode)
        try:
            # a fresh function per mode: the path is decided at trace
            # time, and jit would hand back the other mode's trace
            step = jax.jit(lambda *a: model.paged_decode(*a),
                           donate_argnums=(1,))
            c = jax.tree_util.tree_map(jnp.copy, cache)
            tok, lens, out, toks = tok0, lengths.copy(), [], []
            for i in range(steps):
                c, lg = step(model.params, c, jnp.asarray(tables),
                             jnp.asarray(tok[:, None]), jnp.asarray(lens))
                lg = np.asarray(lg[:, 0], np.float32)
                out.append(lg)
                toks.append(tok)
                tok = (lg.argmax(-1).astype(np.int32) if feed is None
                       else feed[i + 1] if i + 1 < steps else tok)
                lens = lens + 1
            return np.stack(out), np.stack(toks), \
                dispatch_snapshot()["paged_decode"]
        finally:
            env.set_paged_kernel(None)

    ref, fed, snap_ref = run("off", None)
    got, _, snap = run("on", fed)
    check(snap_ref["path"] == "paged", f"gather path not taken: {snap_ref}")
    check(snap["path"] == "paged_flash", f"kernel path not taken: {snap}")
    err = close(ref, got, "paged_decode logits")
    # and the cached path itself against the uncached full forward over
    # prompt + fed tokens: step i's logits sit at position lengths[s] + i
    seq = np.zeros((slots, -(-(bucket + steps) // 64) * 64), np.int32)
    seq[:, :bucket] = ids
    for s, n in enumerate(lengths):
        seq[s, n:n + steps] = fed[:, s]
    full = jax.jit(lambda p, x: causal_lm.forward(p, x, config))(
        model.params, jnp.asarray(seq))
    at = lengths[None, :] + np.arange(steps)[:, None]           # [steps, S]
    full = np.asarray(full, np.float32)[np.arange(slots)[None, :], at]
    err_fwd = close(full, ref, "paged_decode (gather) vs full forward")
    agree = tokens_agree(ref.reshape(-1, ref.shape[-1]),
                         got.argmax(-1).reshape(-1), "paged_decode tokens")
    return {"slots": slots, "block_size": bs, "table_columns": mb,
            "head_dim": config.head_dim, "steps": steps, "dispatch": snap,
            "logits_max_err": err, "gather_vs_forward_max_err": err_fwd,
            **agree}


def _kernel_dequant(shape, dtype, seed):
    """Fused int8 dequant-matmul against cast-then-dot at (M, K, N)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.common.environment import environment
    from deeplearning4j_tpu.kernels import dispatch_snapshot
    from deeplearning4j_tpu.quant.transforms import (dequant_matmul,
                                                     quantize_tensor)

    M, K, N = shape
    kx, kw = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (M, K), jnp.float32).astype(dtype)
    w = quantize_tensor(
        (0.02 * jax.random.normal(kw, (K, N), jnp.float32)).astype(dtype))
    env = environment()
    outs, snaps = {}, {}
    try:
        for mode in ("off", "on"):
            env.set_fused_dequant(mode)
            # a fresh function per mode: the path is decided at trace
            # time, and jit would hand back the other mode's trace
            outs[mode] = jax.jit(lambda x, w: dequant_matmul(x, w))(x, w)
            snaps[mode] = dispatch_snapshot()["dequant_matmul"]
    finally:
        env.set_fused_dequant(None)
    check(snaps["off"]["path"] == "xla", f"xla path not taken: {snaps}")
    check(snaps["on"]["path"] == "fused", f"kernel path not taken: {snaps}")
    return {"shape_MKN": list(shape), "dtype": str(jnp.dtype(dtype)),
            "dispatch": snaps["on"],
            "max_err": close(outs["off"], outs["on"], "dequant_matmul")}


def phase_kernels(*, interpret: bool, flash_shape, lm_config, slots: int,
                  max_ctx: int, bucket: int, decode_steps: int, mm_shape,
                  dtype, seed: int = 0):
    """The three Pallas kernels against their XLA paths. ``interpret`` is
    what the kernels must be doing here: False on the chip (compiled),
    True only in the CPU rehearsal."""
    import importlib
    # the package re-exports the function under the module's name
    fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")

    t0 = time.perf_counter()
    check(fa._interpret() is interpret,
          f"kernels interpret={fa._interpret()}, expected {interpret}")
    rec = {"interpret": interpret,
           "flash_attention": _kernel_flash(flash_shape, dtype, seed),
           "paged_decode": _kernel_paged(lm_config, slots=slots,
                                         max_ctx=max_ctx, bucket=bucket,
                                         steps=decode_steps, seed=seed),
           "dequant_matmul": _kernel_dequant(mm_shape, dtype, seed)}
    rec["seconds"] = round(time.perf_counter() - t0, 2)
    rec["peak_bytes_in_use"] = peak_bytes()
    return rec


# ---------------------------------------------------------------------------
# phase: four chips (--chips 4)
# ---------------------------------------------------------------------------

def phase_four_chips(bert_config, lm_config, *, B: int, T: int,
                     max_ctx: int, bucket: int, gen_tokens: int,
                     steps: int = 3, seed: int = 0, devices=None):
    """The paths that exist only across chips, each against its
    single-device comparison: BERT on a data=2 x tensor=2 mesh, and the
    serve model tensor-parallel over a (1, 4) ``model`` axis."""
    import jax
    import numpy as np
    from deeplearning4j_tpu.common.mesh import (MeshConfig, make_mesh,
                                                serving_mesh)
    from deeplearning4j_tpu.models import bert, causal_lm
    from deeplearning4j_tpu.serving import ModelRegistry

    t0 = time.perf_counter()
    errs0 = cache_errors()
    devices = list(devices if devices is not None else jax.devices())
    check(len(devices) == 4, f"{len(devices)} devices, need 4")
    want_ids = sorted(d.id for d in devices)

    # -- train: sharded step against the single-device step -----------------
    batch = mlm_batch(bert_config, B, T, seed)
    params = bert.init_params(jax.random.key(seed), bert_config)
    single = bert.make_train_step(bert_config, None, remat=False)
    _, _, loss1 = single(params, bert.init_opt_state(params), batch, 0)
    loss1 = float(loss1)  # params/opt were donated; rebuild from the seed
    mesh = make_mesh(MeshConfig(data=2, tensor=2), devices=devices)
    params = bert.place_params(
        bert.init_params(jax.random.key(seed), bert_config), bert_config,
        mesh)
    opt = bert.init_opt_state(params)
    sharded = bert.make_train_step(bert_config, mesh, remat=False)
    losses = []
    for it in range(steps):
        params, opt, loss = sharded(params, opt, batch, it)
        losses.append(float(loss))
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    check(abs(losses[0] - loss1) <= 2e-2,
          f"sharded step-1 loss {losses[0]} vs single-device {loss1}")
    check(losses[-1] < losses[0], f"sharded loss did not fall: {losses}")
    train_ids = device_ids(params)
    check(train_ids == want_ids,
          f"BERT parameters on devices {train_ids}, expected {want_ids}")
    train = {"mesh": {"data": 2, "tensor": 2}, "B": B, "T": T,
             "single_device_loss": round(loss1, 5),
             "sharded_losses": [round(l, 5) for l in losses],
             "param_device_ids": train_ids,
             "seconds": round(time.perf_counter() - t0, 2)}
    del params, opt

    # -- serve: tensor-parallel deploy against the single-device deploy -----
    t1 = time.perf_counter()
    model = causal_lm.CausalLM(lm_config, seed=seed)
    registry = ModelRegistry(manifest_dir=None)
    rng = np.random.RandomState(seed)
    prompt = [int(t) for t in rng.randint(0, lm_config.vocab_size,
                                          bucket // 4)]
    try:
        kw = dict(decode_slots=4, decode_max_ctx=max_ctx,
                  decode_prompt_buckets=[bucket], decode_prefill_batch=1)
        registry.deploy("lm1", "v1", model, **kw)
        tp = registry.deploy("lm4", "v1", model,
                             mesh=serving_mesh(devices=devices), **kw)
        one = registry.generate("lm1", prompt,
                                max_tokens=gen_tokens)["tokens"]
        four = registry.generate("lm4", prompt,
                                 max_tokens=gen_tokens)["tokens"]
        check(len(one) == gen_tokens and len(four) == gen_tokens,
              f"asked {gen_tokens} tokens, got {len(one)} and {len(four)}")
        # the engine's placed state: parameters and the paged KV pool
        param_ids = device_ids(tp.engine._params)
        pool_ids = device_ids(tp.engine._cache)
        pool_spec = str(tp.engine._cache["k"].sharding.spec)
        check(param_ids == want_ids,
              f"serve parameters on devices {param_ids}")
        check(pool_ids == want_ids, f"KV pool on devices {pool_ids}")
        ref = reference_logits(model, prompt, one)
        agree = tokens_agree(ref, one, "single-device vs forward")
        share = same_continuation(one, decided(ref)[1], four,
                                  "sharded vs single-device")
        errs = delta(cache_errors(), errs0)
        check(not errs, f"executable-store errors: {errs}")
    finally:
        registry.drain_all(save_manifests=False)
    serve = {"mesh": {"data": 1, "model": 4}, "tokens": gen_tokens,
             "single_device_tokens": one, "sharded_tokens": four,
             "single_vs_forward": agree, "sharded_same_share": share,
             "param_device_ids": param_ids, "pool_device_ids": pool_ids,
             "pool_spec": pool_spec,
             "seconds": round(time.perf_counter() - t1, 2)}
    return {"train": train, "serve": serve,
            "seconds": round(time.perf_counter() - t0, 2)}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def emit(phase: str, rec: dict):
    print(json.dumps({"phase": phase, **rec}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths and what they are "
                         "compared with, on a four-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # jax reads this at import: place its cache before the first import.
    # The executable store follows its own variable; by default it sits
    # beside jax's cache, so the run writes nothing outside one directory
    cache_root = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                       os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("DL4J_TPU_CACHE_DIR",
                          os.path.join(cache_root, "dl4j-store"))
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees {len(devs)} "
              "device(s); nothing was run", file=sys.stderr)
        return 2

    import jax.numpy as jnp
    from deeplearning4j_tpu import native
    from deeplearning4j_tpu.models import bert, causal_lm

    emit("start", {
        "jax": jax.__version__, "device_kind": dev.device_kind,
        "count": len(devs), "tolerance": TOL, "seed": args.seed,
        "jax_compilation_cache_dir": jax.config.jax_compilation_cache_dir,
        "executable_store_dir": os.environ["DL4J_TPU_CACHE_DIR"],
        "native_available": native.available(),
        "native_build_error": native.build_error()})

    lm_config = causal_lm.CausalLMConfig()
    if args.chips == 4:
        emit("four_chips", phase_four_chips(
            bert.BertConfig.base(), lm_config, B=128, T=128, max_ctx=1024,
            bucket=128, gen_tokens=32, seed=args.seed))
    else:
        emit("train", phase_train(bert.BertConfig.base(), B=128, T=128,
                                  steps=5, seed=args.seed))
        emit("serve", phase_serve(
            lm_config, slots=8, max_ctx=1024, buckets=[128, 512],
            prompt_lens=[4, 100, 24, 400, 200, 40],
            gen_lens=[24, 6, 16, 8, 12, 4], greedy_prompt=100,
            seed=args.seed))

        def resnet50():
            from deeplearning4j_tpu.zoo import ResNet50
            return ResNet50(num_classes=1000, input_shape=(3, 224, 224),
                            dtype="bfloat16").init_model()

        x = jax.random.normal(jax.random.key(args.seed), (8, 3, 224, 224),
                              jnp.float32)
        emit("store", phase_store(
            resnet50, x, os.path.join(cache_root, "dl4j-store-smoke"),
            name="resnet50"))
        emit("kernels", phase_kernels(
            interpret=False, flash_shape=(4, 12, 2048, 64),
            lm_config=lm_config, slots=8, max_ctx=1024, bucket=128,
            decode_steps=16, mm_shape=(8, 768, 3072), dtype=jnp.bfloat16,
            seed=args.seed))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
