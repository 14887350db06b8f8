"""Driver for `kind: train_hybrid_lm`: next-token training of the
`nemotron_h` backbone (Mamba-2 / sparse experts / grouped-query
attention) through the program's `hybrid_lm.make_train_step` on one chip,
which holds its share of a stated deployment: some of each expert layer's
experts, a slice of the vocabulary, the first blocks of the pattern.

Set-up builds ONE object — the compiled step — and drives it from the
seed. The learning rate is a schedule the driver hands the program: a
linear warm-up over `train.warmup_steps` to `train.learning_rate`, held
there. WHAT THE WINDOW TIMES is therefore the job's first steps, at a few
millionths of a rate, under a router that stays as the seed drew it: at a
constant 1e-4 the untrained router collapses within ten steps (nothing
balances the load; PERF.md section 6), and the step's time follows the
collapse. The cell says nothing of a trained or drifting router.

The step that is compared is one the timed job does not visit: taken
first, from the seed's state and zero moments, at the schedule's peak
(iteration `warmup_steps - 1`) — ISSUE 27's first step at 1e-4. At the
schedule's start every change of a bfloat16 parameter rounds away and
there would be nothing to compare. From it the driver keeps the loss,
each leaf's gradient norm (from Adam's first moment) and each leaf's
change in norm (the start parameters are made a second time from the seed
after the step, so that no copy of them lies beside the step's peak).
Then the job itself starts: the state is made from the seed once more,
and the same compiled step runs from iteration 0 through its warm steps
into the window. The reference (`benchmark/reference/nemotron_h.py`)
follows the compared step after the window has closed and the program's
state is freed.

`harness.run_cell` deletes the trace before a per-layer reader runs, so
in a traced run this driver stops the profile itself after its traced
steps and reduces the trace to the scope table there, after the window:
`window["scopes"]`, beside the counters' deltas over the window in
`window["counters"]` and over the traced steps in `window["traced"]`.
"""
from __future__ import annotations

import json
import sys
import time

from benchmark import harness
from benchmark.drivers.train_mlm import compare
from benchmark.reference import nemotron_h as ref

MOE_COUNTERS = ("dl4j_moe_assignments_total",
                "dl4j_moe_held_assignments_total")
EXPERT_TOKENS = "dl4j_moe_expert_tokens_total"


def _program_config(cfg):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import hybrid_lm
    d = ref.dims(cfg)
    return hybrid_lm.HybridLMConfig(
        vocab_size=d["V"], hidden_size=d["E"],
        hybrid_override_pattern=d["pattern"], norm_eps=d["eps"],
        mamba_num_heads=d["H"], mamba_head_dim=d["P"],
        ssm_state_size=d["N"], n_groups=d["G"], conv_kernel=d["K"],
        chunk_size=d["chunk"], num_attention_heads=d["heads"],
        num_key_value_heads=d["kv_heads"], head_dim=d["D"],
        n_routed_experts=d["experts"], num_experts_per_tok=d["top_k"],
        moe_intermediate_size=d["F"],
        moe_shared_expert_intermediate_size=d["Fs"],
        routed_scaling_factor=d["scale"], first_expert=d["first"],
        experts_held=d["held"], rescale_layers=d["depth"],
        # the tests' tiny rehearsal states float32 activations: at its
        # sizes one token routed otherwise is a tenth of an expert's load
        dtype=jnp.dtype(cfg["train"].get("activations", "bfloat16")))


def learning_rate(t):
    """`train`'s rate as the program takes it: the number, or with
    `warmup_steps` the schedule `iteration -> rate` that rises linearly
    to it at iteration `warmup_steps - 1` and holds it."""
    peak, warmup = t["learning_rate"], int(t.get("warmup_steps", 0))
    if not warmup:
        return peak
    import jax.numpy as jnp
    return lambda it: peak * jnp.minimum(1.0, (it + 1) * (1.0 / warmup))


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


def _leaf_norms(names):
    """jitted: a tree's leaves (in `names`' order) -> `ref.expand`-able
    norms, one per leaf and one per held expert of a stack."""
    import jax

    @jax.jit
    def norms(tree):
        return {n: ref.leaf_norm(n, x)
                for n, x in zip(names, jax.tree_util.tree_leaves(tree))}

    return norms


def _moe_counters() -> dict:
    """The expert-load counters as the program's registry has them now:
    the unlabelled totals, and `expert_tokens/<block>/<expert>`."""
    from deeplearning4j_tpu.common.metrics import registry
    out = {}
    for name in MOE_COUNTERS:
        fam = registry().get(name)
        if fam is not None:
            out[name] = float(fam.value())
    fam = registry().get(EXPERT_TOKENS)
    if fam is not None:
        for (block, expert), child in fam.children():
            out[f"expert_tokens/{block}/{expert}"] = float(child.value())
    return out


def _scope_ms(scopes, program="jit_step"):
    """The traced steps' scope table as `{scope: ms per execution}`, for
    the notes line (None where nothing was traced)."""
    prog = ((scopes or {}).get("programs") or {}).get(program)
    if not prog or not prog["executions"]:
        return None
    return {scope: 1e3 * row["device_s"] / prog["executions"]
            for scope, row in sorted(prog["scopes"].items(),
                                     key=lambda kv: -kv[1]["device_s"])}


def setup(cell, seed):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import hybrid_lm

    cfg = cell["config"]
    t = cfg["train"]
    config = _program_config(cfg)
    weights, batches, words = make_inputs(cfg, seed)
    fresh = jax.jit(lambda lo, hi: ref.nest(weights(lo, hi)))
    params = fresh(*words)
    names = ref.leaf_names(params)
    pool = batches(*words)["input_ids"]
    feed = [{"input_ids": pool[i]} for i in range(t["batches"])]
    opt = hybrid_lm.init_opt_state(params)
    warmup = int(t.get("warmup_steps", 0))
    step = hybrid_lm.make_train_step(config, None,
                                     learning_rate=learning_rate(t),
                                     remat=t["remat"])
    norms = _leaf_norms(names)
    change = jax.jit(lambda a, b: norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    tokens = t["batch"] * t["seq_len"]

    params, opt, aux = step(params, opt, feed[0], max(warmup - 1, 0))
    first = {
        "losses": [hybrid_lm.observe(aux, config, tokens)],
        # Adam's first moment after one step is (1 - beta1) * gradient
        "grad_norms": {k: v / (1.0 - ref.ADAM_B1) for k, v in ref.expand(
            jax.device_get(norms(opt[1]))).items()},
        "change_norms": ref.expand(jax.device_get(
            change(params, fresh(*words)))),
        "expert_tokens": jax.device_get(aux["expert_tokens"]).tolist(),
    }
    it = 1
    if warmup:
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
    import jax
    from deeplearning4j_tpu.models import hybrid_lm
    cell = session["cell"]
    t = cell["config"]["train"]
    traffic = cell["traffic"]
    step, feed, config = session["step"], session["feed"], session["config"]
    params, opt, it = session["params"], session["opt"], session["it"]
    ahead = int(traffic["run_ahead"])
    tokens = t["batch"] * t["seq_len"]
    pending, losses = [], []

    def one_step():
        nonlocal params, opt, it
        params, opt, aux = step(params, opt, feed[it % len(feed)], it)
        it += 1
        pending.append(aux)
        if len(pending) > ahead:
            # reading a loss two steps old is where the counters are fed
            losses.append(hybrid_lm.observe(pending.pop(0), config, tokens))

    def drain():
        jax.block_until_ready(params)
        while pending:
            losses.append(hybrid_lm.observe(pending.pop(0), config, tokens))

    before = _moe_counters()
    done = 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        one_step()
        done += 1
    drain()
    elapsed = time.monotonic() - t0
    after = _moe_counters()
    window = {"end_to_end": {"train_tokens_per_s": done * tokens / elapsed},
              "attempted": done, "failed": 0,
              "counters": {k: after[k] - before.get(k, 0.0) for k in after}}
    if profile is not None:
        # the traced steps follow the window, so that starting and
        # stopping the profiler costs the window's rate nothing
        from benchmark import scope_reduce
        profile.start()
        t1 = time.monotonic()
        steps = 0
        while time.monotonic() - t1 < traffic["trace_seconds"]:
            one_step()
            steps += 1
        drain()
        profile.stop()
        window["scopes"] = scope_reduce.scope_table(profile.dir)
        traced = _moe_counters()
        window["traced"] = {"steps": steps, "counters": {
            k: traced[k] - after.get(k, 0.0) for k in traced}}
    session.update(params=params, opt=opt, it=it)
    from deeplearning4j_tpu.kernels import dispatch_snapshot
    window["notes"] = {"steps": done, "window_s": elapsed,
                       "attention_path": dispatch_snapshot().get(
                           "attention", {}).get("path"),
                       "scope_ms": _scope_ms(window.get("scopes")),
                       "tokens_per_step": tokens,
                       "last_loss": losses[-1] if losses else None,
                       "first_losses": session["first"]["losses"],
                       "first_expert_tokens":
                           session["first"]["expert_tokens"]}
    return window


def release(session):
    """Free the program's state before the reference runs."""
    for k in ("params", "opt", "step", "feed"):
        session.pop(k, None)


def reference_step(cfg, inputs, precision="f32", fault=None):
    """What the reference's first step gives, under `compare`'s names."""
    weights, batches, words = inputs
    t = cfg["train"]
    got = ref.first_step(weights(*words), batches(*words)["input_ids"][0],
                         cfg, lr=t["learning_rate"],
                         t=max(int(t.get("warmup_steps", 0)), 1),
                         precision=precision, fault=fault)
    return {"losses": [got["loss"]], "grad_norms": got["grad_norms"],
            "change_norms": got["change_norms"]}


def compare_by_class(first, want, limits):
    """`train_mlm.compare` over two classes of leaf, each with limits of
    its own. The leaves every token reaches (embedding, head, norms,
    Mamba-2 and attention blocks, routers, shared experts): `loss_gap`,
    `grad_norm_gap`, `change_norm_gap`. Each held expert's two matrices,
    leaves of their own: `expert_grad_norm_gap`, `expert_change_norm_gap`.
    An expert's gradient is a sum over the few hundred tokens routed to
    it, their weights squared activations: a token whose sixth choice
    falls the other way under another rounding moves that norm by
    percents whatever the precision (on the chip the program, the float8
    control and a fault in the scan all read their worst there, 0.02 to
    0.15), so these leaves tell a missing expert or missing tokens and
    the others tell the precision."""
    checks = []
    for prefix, mine in (("", lambda k: not ref.per_expert_leaf(k)),
                         ("expert_", ref.per_expert_leaf)):
        pick = lambda d: {k: v for k, v in d.items() if mine(k)}
        part = compare({"losses": first["losses"],
                        "grad_norms": pick(first["grad_norms"]),
                        "change_norms": pick(first["change_norms"])},
                       {"losses": want["losses"],
                        "grad_norms": pick(want["grad_norms"]),
                        "change_norms": pick(want["change_norms"])}, limits)
        for c in part[0 if not prefix else 1:]:
            c["name"] = prefix + c["name"]
            c["limit"] = limits.get(c["name"], 0.0)
            checks.append(c)
    return checks


def check(session, window):
    """The first step against the reference's, by `compare_by_class`."""
    cfg = session["cell"]["config"]
    t0 = time.monotonic()
    want = reference_step(cfg, session["inputs"])
    if window is not None:
        window.setdefault("notes", {})["reference_s"] = time.monotonic() - t0
    return compare_by_class(session["first"], want, cfg["limits"])


def readings(session, window, faults=False):
    """Lower and upper readings for the limits (benchmark/readings.py):
    the program against the reference; the control (the reference with
    fp8 matmul operands in the program's place); and, where `faults`, the
    reference with each planted fault: one held expert's routed term left
    out, the state not carried across chunk boundaries, half the sequence
    left out."""
    cfg = session["cell"]["config"]
    want = reference_step(cfg, session["inputs"])

    def values(got, who):
        checks = compare_by_class(got, want, {})
        # which leaves read worst goes to stderr, beside the numbers
        print(json.dumps({who: {c["name"]: c["worst"][:3] for c in checks
                                if "worst" in c}}), file=sys.stderr)
        return {c["name"]: c["value"] for c in checks}

    out = {"program": values(session["first"], "program"),
           "control_fp8": values(reference_step(
               cfg, session["inputs"], precision="fp8"), "control_fp8")}
    if faults:
        for fault in ref.FAULTS:
            out["fault_" + fault] = values(
                reference_step(cfg, session["inputs"], fault=fault),
                "fault_" + fault)
    return out
