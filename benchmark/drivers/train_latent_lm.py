"""Driver for `kind: train_latent_lm`: next-token + multi-token-prediction
training of the DeepSeek-V3 family's decoder (latent attention with a rotary
part, gated-SiLU sparse experts, one MTP module; the keys of
`jdopensource/JoyAI-LLM-Flash`) through the program's
`hybrid_lm.make_train_step` on one chip, which holds its share of a stated
deployment: some of each expert layer's experts, a slice of the vocabulary,
the leading dense layer, a few expert layers and the MTP module.

It is `drivers/train_hybrid_lm.py`'s job with another model: the learning
rate is that driver's schedule (a linear warm-up over `train.warmup_steps`
to `train.learning_rate`, so the window times the job's first steps under a
router as the seed drew it), the window, the traced steps after it, the
counters' deltas and the scope table are that driver's `measure` as it
stands, and the step that is compared is the one that driver compares: the
first from the seed's state and zero moments at the schedule's peak, run
before the job starts, the reference (`benchmark/reference/joyai_flash.py`)
after the window has closed and the program's state is freed.

What is compared differs (`compare_classes`): the step's loss, its MTP loss
position by position, and the gradient and change norms as ONE number a
class of leaves (the root mean square of the leaves' relative gaps), not
the worst leaf: which leaf reads worst follows the forward's last bits
(PERF.md section 7 d), a class's rms does not.
"""
from __future__ import annotations

import json
import math
import statistics
import sys
import time

import numpy as np

from benchmark import harness
from benchmark.drivers import train_hybrid_lm as hybrid
from benchmark.reference import joyai_flash as ref

release = hybrid.release


def _program_config(cfg):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import hybrid_lm
    d = ref.dims(cfg)
    return hybrid_lm.HybridLMConfig(
        vocab_size=d["V"], hidden_size=d["E"],
        hybrid_override_pattern=d["pattern"], norm_eps=d["eps"],
        num_attention_heads=d["heads"], q_lora_rank=d["q_rank"],
        kv_lora_rank=d["kv_rank"], qk_nope_head_dim=d["dn"],
        qk_rope_head_dim=d["dr"], v_head_dim=d["dv"], rope_theta=d["theta"],
        intermediate_size=d["Fd"], mlp_hidden_act="silu",
        moe_hidden_act="silu", n_routed_experts=d["experts"],
        num_experts_per_tok=d["top_k"], moe_intermediate_size=d["F"],
        moe_shared_expert_intermediate_size=d["Fs"],
        routed_scaling_factor=d["scale"], first_expert=d["first"],
        experts_held=d["held"], rescale_layers=d["depth"],
        num_nextn_predict_layers=d["mtp"], mtp_loss_weight=d["lam"],
        # the tests' tiny rehearsal states float32 activations
        dtype=jnp.dtype(cfg["train"].get("activations", "bfloat16")))


def make_inputs(cfg, seed):
    """Weights (flat, stored types) and the pool of batches, each made on
    the device in one jitted call from the seed."""
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
                                t["seq_len"])

    return weights, batches, (lo, hi)


def _first_step(step, config, fresh, words, batch, cfg):
    """The compared step: from the seed's state and zero moments at the
    schedule's peak. (state after it, what `compare_classes` reads of it)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import hybrid_lm
    t = cfg["train"]
    params = fresh(*words)
    norms = hybrid._leaf_norms(ref.leaf_names(params))
    change = jax.jit(lambda a, b: norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    opt = hybrid_lm.init_opt_state(params)
    params, opt, aux = step(params, opt, batch,
                            max(int(t.get("warmup_steps", 0)) - 1, 0))
    first = {
        "losses": [hybrid_lm.observe(aux, config, t["batch"] * t["seq_len"])],
        "mtp_loss": float(aux["mtp_loss"]),
        "mtp_token_loss": np.asarray(jax.device_get(aux["mtp_token_loss"])),
        # Adam's first moment after one step is (1 - beta1) * gradient
        "grad_norms": {k: v / (1.0 - ref.ADAM_B1) for k, v in ref.expand(
            jax.device_get(norms(opt[1]))).items()},
        "change_norms": ref.expand(jax.device_get(
            change(params, fresh(*words)))),
        "expert_tokens": jax.device_get(aux["expert_tokens"]).tolist(),
    }
    return params, opt, first


def _make_step(cfg, config):
    from deeplearning4j_tpu.models import hybrid_lm
    t = cfg["train"]
    return hybrid_lm.make_train_step(config, None,
                                     learning_rate=hybrid.learning_rate(t),
                                     remat=t["remat"])


def setup(cell, seed):
    import jax
    from deeplearning4j_tpu.models import hybrid_lm

    cfg = cell["config"]
    t = cfg["train"]
    config = _program_config(cfg)
    weights, batches, words = make_inputs(cfg, seed)
    fresh = jax.jit(lambda lo, hi: ref.nest(weights(lo, hi)))
    pool = batches(*words)["input_ids"]
    feed = [{"input_ids": pool[i]} for i in range(t["batches"])]
    step = _make_step(cfg, config)
    tokens = t["batch"] * t["seq_len"]

    params, opt, first = _first_step(step, config, fresh, words, feed[0], cfg)
    it = 1
    if int(t.get("warmup_steps", 0)):
        # the job starts here: the seed's state again, iteration 0
        del params, opt
        params = fresh(*words)
        opt = hybrid_lm.init_opt_state(params)
        it = 0
    for _ in range(2):       # the window's loop, warm: the host path too
        params, opt, aux = step(params, opt, feed[it % len(feed)], it)
        hybrid_lm.observe(aux, config, tokens)
        it += 1
    return {"cell": cell, "seed": seed, "step": step, "params": params,
            "opt": opt, "feed": feed, "it": it, "first": first,
            "config": config, "inputs": (weights, batches, words)}


def measure(session, seconds, profile):
    """`train_hybrid_lm.measure` (the same session keys), with the MTP
    positions the program counted so far in the notes."""
    window = hybrid.measure(session, seconds, profile)
    from deeplearning4j_tpu.common.metrics import registry
    fam = registry().get("dl4j_mtp_positions_total")
    window["notes"]["mtp_positions_total"] = (
        None if fam is None else float(fam.value()))
    window["notes"]["first_mtp_loss"] = session["first"]["mtp_loss"]
    return window


def reference_step(cfg, inputs, precision="f32", fault=None):
    """What the reference's first step gives, under `compare_classes`'
    names."""
    weights, batches, words = inputs
    t = cfg["train"]
    got = ref.first_step(weights(*words), batches(*words)["input_ids"][0],
                         cfg, lr=t["learning_rate"],
                         t=max(int(t.get("warmup_steps", 0)), 1),
                         precision=precision, fault=fault)
    return {"losses": [got["loss"]], "mtp_loss": got["mtp_loss"],
            "mtp_token_loss": got["mtp_token_loss"],
            "grad_norms": got["grad_norms"],
            "change_norms": got["change_norms"]}


def class_rms_gap(got, want, keys, floor):
    """(root mean square over `keys` of |got - want| / max(want, floor),
    the five leaves that read worst)."""
    gaps = sorted(((abs(got[k] - want[k]) / max(want[k], floor), k)
                   for k in keys), reverse=True)
    if not gaps:
        return None, []
    return math.sqrt(sum(g * g for g, _ in gaps) / len(gaps)), gaps[:5]


def compare_classes(first, want, limits):
    """The numbers compared, each beside its limit. `loss_gap`: the step's
    loss (main + lambda MTP), relative. `mtp_loss_gap`: the root mean square
    over the predicting positions of the program's MTP cross entropy less
    the reference's, over the reference's mean (a module that predicts the
    wrong position reads a tenth or more; its mean alone would hardly
    move). `grad_norm_gap` and `change_norm_gap`: over the leaves every
    token reaches (embedding, head, norms, attention, MLP, routers, shared
    experts, the MTP module's own), the rms of the leaves' relative gaps in
    norm, each gap over the reference's norm of that leaf or of the median
    leaf, whichever is larger; `expert_grad_norm_gap` and
    `expert_change_norm_gap`: the same over each held expert's two
    matrices, leaves of their own. Leaves whose reference gradient is under
    a thousandth of the median leaf's move by round-off alone under Adam
    and are left out of the change."""
    checks = [{"name": "loss_gap", "value": max(
        abs(a - b) / abs(b)
        for a, b in zip(first["losses"], want["losses"]))}]
    a, b = first["mtp_token_loss"], want["mtp_token_loss"]
    checks.append({"name": "mtp_loss_gap", "value": float(
        np.sqrt(np.mean(np.square(a[:, :-2] - b[:, :-2])))
        / np.mean(b[:, :-2])), "mean_gap": abs(
            first["mtp_loss"] - want["mtp_loss"]) / want["mtp_loss"]})
    g_ref, c_ref = want["grad_norms"], want["change_norms"]
    for prefix, mine in (("", lambda k: not ref.per_expert_leaf(k)),
                         ("expert_", ref.per_expert_leaf)):
        keys = [k for k in sorted(g_ref) if mine(k)]
        g_med = statistics.median(g_ref[k] for k in keys)
        gv, g_top = class_rms_gap(first["grad_norms"], g_ref, keys, g_med)
        moved = [k for k in keys if g_ref[k] >= 1e-3 * g_med]
        c_med = statistics.median(c_ref[k] for k in moved)
        cv, c_top = class_rms_gap(first["change_norms"], c_ref, moved, c_med)
        checks.append({"name": prefix + "grad_norm_gap", "value": gv,
                       "worst": g_top})
        checks.append({"name": prefix + "change_norm_gap", "value": cv,
                       "worst": c_top})
    for c in checks:
        c["limit"] = limits.get(c["name"], 0.0)
    return checks


def check(session, window):
    """The first step against the reference's, by `compare_classes`."""
    cfg = session["cell"]["config"]
    t0 = time.monotonic()
    want = reference_step(cfg, session["inputs"])
    if window is not None:
        window.setdefault("notes", {})["reference_s"] = time.monotonic() - t0
    return compare_classes(session["first"], want, cfg["limits"])


def _half_batch_first(session):
    """A fault planted in the program's place: the compared step, compiled
    anew, over a batch whose every row is its first (a step that reads one
    row of its batch). Frees what it made before it returns."""
    import jax
    import jax.numpy as jnp
    cfg = session["cell"]["config"]
    weights, batches, words = session["inputs"]
    fresh = jax.jit(lambda lo, hi: ref.nest(weights(lo, hi)))
    ids = batches(*words)["input_ids"][0]
    batch = {"input_ids": jnp.broadcast_to(ids[:1], ids.shape)}
    return _first_step(_make_step(cfg, session["config"]), session["config"],
                       fresh, words, batch, cfg)[2]


def readings(session, window, faults=False):
    """Lower and upper readings for the limits (benchmark/readings.py): the
    program against the reference; the control (the reference with fp8
    matmul operands in the program's place); and, where `faults`, the
    reference with each planted fault (the rotary embedding left out, the
    values read at the keys' width, the MTP loss against x_{t+1}, the shared
    expert dropped) and two faults in the program's place: a state left
    unchanged, and a step that reads one row of its batch. Every leaf's two
    norms go to stderr beside the numbers (`leaves`), the reference's
    first: what a class's one number hides is read from them."""
    cfg = session["cell"]["config"]
    half = _half_batch_first(session) if faults else None
    want = reference_step(cfg, session["inputs"])
    leaves = lambda got: {k: got[k] for k in ("grad_norms", "change_norms")}
    print(json.dumps({"leaves": {"reference": leaves(want)}}),
          file=sys.stderr)

    def values(got, who):
        checks = compare_classes(got, want, {})
        # which leaves read worst goes to stderr, beside the numbers
        print(json.dumps({who: {c["name"]: c["worst"][:3] for c in checks
                                if "worst" in c}}), file=sys.stderr)
        print(json.dumps({"leaves": {who: leaves(got)}}), file=sys.stderr)
        out = {c["name"]: c["value"] for c in checks}
        out["mtp_mean_loss_gap"] = checks[1]["mean_gap"]
        return out

    out = {"program": values(session["first"], "program"),
           "control_fp8": values(reference_step(
               cfg, session["inputs"], precision="fp8"), "control_fp8")}
    if faults:
        for fault in ref.FAULTS:
            out["fault_" + fault] = values(
                reference_step(cfg, session["inputs"], fault=fault),
                "fault_" + fault)
        still = dict(session["first"], change_norms=dict.fromkeys(
            session["first"]["change_norms"], 0.0))
        out["fault_unchanged"] = values(still, "fault_unchanged")
        out["fault_half"] = values(half, "fault_half")
    return out
