"""Driver for `kind: train_mlm`: BERT masked-LM pretraining through the
program's `bert.make_train_step` on one chip.

Set-up builds ONE object — the compiled step with its state — drives it
from the seed through its first three steps (the window's own call, on
batches whose rows all differ), keeps what the comparison needs from them
(the losses, each leaf's gradient norm from Adam's first moment after
step 1, each leaf's change in norm after step 3) and hands the same step
and state to the window. The reference (`benchmark/reference/bert_mlm.py`)
follows those three steps after the window has closed and the program's
state is freed.
"""
from __future__ import annotations

import statistics
import time

from benchmark import harness
from benchmark.reference import bert_mlm as ref


def _program_config(cfg):
    from deeplearning4j_tpu.models import bert
    return bert.BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        layer_norm_eps=cfg["layer_norm_eps"])


def make_inputs(cfg, seed):
    """Weights (nested, stored types) and the pool of batches, each made
    on the device in one jitted call from the seed."""
    import jax
    t = cfg["train"]
    lo, hi = harness.seed_words(seed)

    @jax.jit
    def weights(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        return ref.make_flat_params(key, cfg)

    @jax.jit
    def batches(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        return ref.make_batches(key, cfg, t["batches"], t["batch"],
                                t["seq_len"], t["masked_share"])

    return weights, batches, (lo, hi)


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in leaves])


def setup(cell, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.models import bert

    cfg = cell["config"]
    t = cfg["train"]
    weights, batches, words = make_inputs(cfg, seed)
    params = jax.jit(lambda f: ref.nest(f, cfg["num_hidden_layers"]))(
        weights(*words))
    names = ref.leaf_names(params)
    pool = batches(*words)
    feed = [{k: v[i] for k, v in pool.items()} for i in range(t["batches"])]
    opt = bert.init_opt_state(params)
    step = bert.make_train_step(_program_config(cfg), None,
                                learning_rate=t["learning_rate"],
                                remat=t["remat"])
    norms = jax.jit(_leaf_norms)
    change = jax.jit(lambda a, b: _leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))

    start = jax.tree_util.tree_map(jnp.copy, params)
    losses, grad_norms = [], None
    steps = cfg["reference"]["steps"]
    for it in range(steps):
        params, opt, loss = step(params, opt, feed[it % len(feed)], it)
        losses.append(loss)
        if it == 0:
            # Adam's first moment after one step is (1 - beta1) * gradient
            grad_norms = norms(opt[1]) / (1.0 - ref.ADAM_B1)
    change_norms = change(params, start)
    del start
    first = {
        "losses": [float(x) for x in jax.device_get(losses)],
        "grad_norms": dict(zip(names, np.asarray(grad_norms, float))),
        "change_norms": dict(zip(names, np.asarray(change_norms, float))),
    }
    return {"cell": cell, "seed": seed, "step": step, "params": params,
            "opt": opt, "feed": feed, "it": steps, "first": first,
            "inputs": (weights, batches, words)}


def measure(session, seconds, profile):
    import jax
    cell = session["cell"]
    t = cell["config"]["train"]
    traffic = cell["traffic"]
    step, feed = session["step"], session["feed"]
    params, opt, it = session["params"], session["opt"], session["it"]
    ahead = int(traffic["run_ahead"])
    pending, done = [], 0

    def one_step():
        nonlocal params, opt, it
        params, opt, loss = step(params, opt, feed[it % len(feed)], it)
        it += 1
        pending.append(loss)
        if len(pending) > ahead:
            jax.block_until_ready(pending.pop(0))

    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        one_step()
        done += 1
    jax.block_until_ready((params, pending))
    elapsed = time.monotonic() - t0
    if profile is not None:
        # the traced steps follow the window, so that starting and
        # stopping the profiler costs the window's rate nothing
        profile.start()
        t1 = time.monotonic()
        while time.monotonic() - t1 < traffic["trace_seconds"]:
            one_step()
        jax.block_until_ready((params, pending))
        profile.stop()
    last_loss = float(pending[-1]) if pending else None
    session.update(params=params, opt=opt, it=it)
    tokens = done * t["batch"] * t["seq_len"]
    return {"end_to_end": {"train_tokens_per_s": tokens / elapsed},
            "attempted": done, "failed": 0,
            "notes": {"steps": done, "window_s": elapsed,
                      "tokens_per_step": t["batch"] * t["seq_len"],
                      "last_loss": last_loss,
                      "first_losses": session["first"]["losses"]}}


def release(session):
    """Free the program's state before the reference runs."""
    for k in ("params", "opt", "step", "feed"):
        session.pop(k, None)


def compare(first, want, limits):
    """The numbers compared, each beside its limit. `first` is what the
    program's first steps gave, `want` the reference's. `loss_gap` is the
    worst of the steps' relative loss gaps. Gaps of norms are
    taken by the worst leaf: |program's norm - reference's norm| over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Leaves whose reference gradient is under a thousandth of the
    median leaf's move by round-off alone under Adam and are left out of
    the change."""
    checks = [{"name": "loss_gap", "value": max(
        abs(a - b) / abs(b)
        for a, b in zip(first["losses"], want["losses"]))}]
    g_ref = want["grad_norms"]
    g_med = statistics.median(g_ref.values())
    ranked = lambda got, ref_, med, keys: sorted(
        ((abs(got[k] - ref_[k]) / max(ref_[k], med), k, got[k], ref_[k])
         for k in keys), reverse=True)[:5] or [(None, None)]
    g_top = ranked(first["grad_norms"], g_ref, g_med, sorted(g_ref))
    gv, gk = g_top[0][:2]
    moved = [k for k in sorted(g_ref) if g_ref[k] >= 1e-3 * g_med]
    c_ref = want["change_norms"]
    c_med = statistics.median(c_ref[k] for k in moved)
    c_top = ranked(first["change_norms"], c_ref, c_med, moved)
    cv, ck = c_top[0][:2]
    checks.append({"name": "grad_norm_gap", "value": gv, "worst": g_top})
    checks.append({"name": "change_norm_gap", "value": cv, "worst": c_top,
                   "median_change": c_med})
    for c in checks:
        c["limit"] = limits.get(c["name"], 0.0)
    return checks


def reference_steps(cfg, inputs, precision="f32", half=False):
    weights, batches, words = inputs
    t = cfg["train"]
    feed = batches(*words)
    if half:     # the planted fault: the second half of every batch left out
        feed = {k: v[:, :v.shape[1] // 2] for k, v in feed.items()}
    return ref.train_steps(
        weights(*words), feed, cfg["reference"]["steps"],
        lr=t["learning_rate"], eps=cfg["layer_norm_eps"],
        rows=cfg["reference"]["rows"], precision=precision)


def check(session, window):
    cfg = session["cell"]["config"]
    want = reference_steps(cfg, session["inputs"])
    return compare(session["first"], want, cfg["limits"])


def readings(session, window, faults=False):
    """Lower and upper readings for the limits (benchmark/readings.py):
    the program against the reference; the control (the reference with
    fp8 matmul operands in the program's place); and, where `faults`, the
    reference with half of the batch left out and the mean taken over the
    rest. (A step that returns its state unchanged reads 1 on
    change_norm_gap by construction.)"""
    cfg = session["cell"]["config"]
    want = reference_steps(cfg, session["inputs"])
    values = lambda got: {c["name"]: c["value"]
                          for c in compare(got, want, {})}
    out = {"program": values(session["first"]),
           "control_fp8": values(reference_steps(cfg, session["inputs"],
                                                 precision="fp8"))}
    if faults:
        out["fault_half_batch"] = values(
            reference_steps(cfg, session["inputs"], half=True))
    return out
