"""Driver for `kind: train_packed_lm`: next-token training on packed rows of
a dense Mamba-2 / attention hybrid (`granitemoehybrid`'s layer: a mixer and
a gated MLP) through the program's `hybrid_lm.make_train_step` on one chip,
which holds its share of a stated deployment: one period of the layer
pattern, a slice of the tied vocabulary.

Set-up builds ONE object, the compiled step, and drives it from the seed.
The rows are packed on the host from the seed (`reference.pack_rows`: the
traffic file's document lengths), so the packing counters are fed from the
batch's own lengths with no device read; the token ids are drawn on the
device. The learning rate is constant (the configuration's `assumed`): the
compared step is the job's first, from the seed's state and zero moments,
on the row of the pool that holds the most documents (a row that one
document fills has no boundary to get wrong); the job then goes on through
the pool in rotation. From that step the driver keeps the loss, each
position's loss, each leaf's gradient norm (from Adam's first moment) and
each leaf's change in norm (the start parameters are made a second time
from the seed after the step, so that no copy of them lies beside the
step's peak). The reference (`benchmark/reference/granite_hybrid.py`)
follows after the window has closed and the program's state is freed.

What is compared (`check`): `train_mlm.compare`'s three numbers, by the
worst leaf, and `boundary_loss_gap_<n>` for each `n` the configuration's
`limits` name: over the predicting positions within `n` steps after a
document's first (not a row's), the root mean square of the program's loss
less the reference's, over the mean of the reference's losses there. A
document's first steps are where a conv tap (n = 3, the taps' reach) or a
state (some tens of steps, while it decays) that crossed the boundary lands
undiluted; in a whole row's mean loss and in a leaf's norm a dozen such
positions of 16,384 are invisible.

`harness.run_cell` deletes the trace before a per-layer reader runs, so in
a traced run this driver stops the profile itself after its traced steps
and reduces the trace to the scope table there, after the window:
`window["scopes"]`, beside the counters' deltas over the window in
`window["counters"]`, and `window["packing"]` (rows, documents, attended
pairs of the window's steps) for `work_granite`'s attention count.
"""
from __future__ import annotations

import json
import math
import sys
import time

import numpy as np

from benchmark import harness
from benchmark.drivers.train_hybrid_lm import _leaf_norms, _scope_ms
from benchmark.drivers.train_mlm import compare
from benchmark.reference import granite_hybrid as ref

PACKING_COUNTERS = {"rows": "dl4j_packed_rows_total",
                    "documents": "dl4j_packed_documents_total",
                    "attended_pairs": "dl4j_packed_attended_pairs_total"}


def _program_config(cfg):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import hybrid_lm
    d = ref.dims(cfg)
    return hybrid_lm.HybridLMConfig(
        vocab_size=d["V"], hidden_size=d["E"],
        hybrid_override_pattern=ref.pattern(cfg), norm_eps=d["eps"],
        mamba_num_heads=d["H"], mamba_head_dim=d["P"],
        ssm_state_size=d["N"], n_groups=d["G"], conv_kernel=d["K"],
        chunk_size=d["chunk"], time_step_min=d["dt_min"],
        time_step_max=d["dt_max"], time_step_floor=d["dt_floor"],
        num_attention_heads=d["heads"], num_key_value_heads=d["kv_heads"],
        head_dim=d["D"], intermediate_size=d["F"],
        mlp_hidden_act=cfg["hidden_act"],
        embedding_multiplier=d["m_e"], residual_multiplier=d["m_r"],
        attention_multiplier=d["m_a"], logits_scaling=d["m_l"],
        tie_word_embeddings=True, rescale_layers=d["depth"],
        # the tests' tiny rehearsal states float32 activations
        dtype=jnp.dtype(cfg["train"].get("activations", "bfloat16")))


def make_inputs(cell, seed):
    """Weights (flat, stored types) and token ids, each made on the device
    in one jitted call from the seed; the packing, made on the host."""
    import jax
    cfg, t = cell["config"], cell["config"]["train"]
    if t["batch"] != 1:
        raise ValueError("the packed cell feeds one row a step")
    lo, hi = harness.seed_words(seed)

    @jax.jit
    def weights(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        return ref.make_flat_params(key, cfg)

    @jax.jit
    def ids(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        return ref.make_ids(key, cfg, t["batches"], t["seq_len"])

    seg, lengths = ref.pack_rows(seed, t["batches"], t["seq_len"],
                                 cell["traffic"]["packing"])
    return weights, ids, (lo, hi), seg, lengths


def _packing_counters() -> dict:
    from deeplearning4j_tpu.common.metrics import registry
    out = {}
    for short, name in PACKING_COUNTERS.items():
        fam = registry().get(name)
        out[short] = float(fam.value()) if fam is not None else 0.0
    return out


def setup(cell, seed):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import hybrid_lm

    cfg = cell["config"]
    t = cfg["train"]
    config = _program_config(cfg)
    weights, ids, words, seg, lengths = make_inputs(cell, seed)
    fresh = jax.jit(lambda lo, hi: ref.nest(weights(lo, hi)))
    params = fresh(*words)
    names = ref.leaf_names(params)
    pool, seg_dev = ids(*words), jnp.asarray(seg)
    feed = [{"input_ids": pool[i][None], "segment_ids": seg_dev[i][None]}
            for i in range(t["batches"])]
    opt = hybrid_lm.init_opt_state(params)
    step = hybrid_lm.make_train_step(config, None,
                                     learning_rate=t["learning_rate"],
                                     remat=t["remat"])
    norms = _leaf_norms(names)
    change = jax.jit(lambda a, b: norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))

    # the compared step: the job's first, on the row with the most documents
    row = max(range(len(lengths)), key=lambda i: len(lengths[i]))
    params, opt, aux = step(params, opt, feed[row], 0)
    hybrid_lm.observe_packed([lengths[row]])
    first = {
        "row": row,
        "losses": [float(aux["loss"])],
        "token_loss": np.asarray(jax.device_get(aux["token_loss"])),
        # Adam's first moment after one step is (1 - beta1) * gradient
        "grad_norms": {k: v / (1.0 - ref.ADAM_B1) for k, v in ref.expand(
            jax.device_get(norms(opt[1]))).items()},
        "change_norms": ref.expand(jax.device_get(
            change(params, fresh(*words)))),
    }
    it = 1
    for _ in range(2):       # the window's loop, warm: the host path too
        params, opt, aux = step(params, opt, feed[it % len(feed)], it)
        hybrid_lm.observe_packed([lengths[it % len(feed)]])
        float(aux["loss"])
        it += 1
    return {"cell": cell, "seed": seed, "step": step, "params": params,
            "opt": opt, "feed": feed, "lengths": lengths, "it": it,
            "first": first, "config": config,
            "inputs": (weights, ids, words, seg)}


def measure(session, seconds, profile):
    import jax
    from deeplearning4j_tpu.models import hybrid_lm
    cell = session["cell"]
    t = cell["config"]["train"]
    traffic = cell["traffic"]
    step, feed, lengths = session["step"], session["feed"], session["lengths"]
    params, opt, it = session["params"], session["opt"], session["it"]
    ahead = int(traffic["run_ahead"])
    tokens = t["batch"] * t["seq_len"]
    pending, losses = [], []

    def one_step():
        nonlocal params, opt, it
        row = it % len(feed)
        params, opt, aux = step(params, opt, feed[row], it)
        hybrid_lm.observe_packed([lengths[row]])    # host lengths, no read
        it += 1
        pending.append(aux["loss"])
        if len(pending) > ahead:
            losses.append(float(pending.pop(0)))

    def drain():
        jax.block_until_ready(params)
        while pending:
            losses.append(float(pending.pop(0)))

    before = _packing_counters()
    done = 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        one_step()
        done += 1
    drain()
    elapsed = time.monotonic() - t0
    after = _packing_counters()
    delta = {k: after[k] - before[k] for k in after}
    finite = all(math.isfinite(x) for x in losses)
    window = {"end_to_end": {"train_tokens_per_s": done * tokens / elapsed},
              "attempted": done, "failed": 0 if finite else done,
              "counters": {PACKING_COUNTERS[k]: v for k, v in delta.items()},
              "packing": dict(delta, tokens_per_row=t["seq_len"])}
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
        traced = _packing_counters()
        window["traced"] = {"steps": steps, "packing": dict(
            {k: traced[k] - after[k] for k in traced},
            tokens_per_row=t["seq_len"])}
    session.update(params=params, opt=opt, it=it)
    from deeplearning4j_tpu.kernels import dispatch_snapshot
    window["notes"] = {"steps": done, "window_s": elapsed,
                       "attention_path": dispatch_snapshot().get(
                           "attention", {}).get("path"),
                       "scope_ms": _scope_ms(window.get("scopes")),
                       "tokens_per_step": tokens,
                       "docs_per_row": delta["documents"] / max(
                           delta["rows"], 1.0),
                       "last_loss": losses[-1] if losses else None,
                       "first_losses": session["first"]["losses"],
                       "compared_row": session["first"]["row"],
                       "compared_row_documents": len(
                           lengths[session["first"]["row"]])}
    return window


def release(session):
    """Free the program's state before the reference runs."""
    for k in ("params", "opt", "step", "feed"):
        session.pop(k, None)


def reference_step(cfg, inputs, row, precision="f32", fault=None):
    """What the reference's first step gives, under `compare`'s names."""
    weights, ids, words, seg = inputs
    got = ref.first_step(weights(*words), ids(*words)[row][None],
                         seg[row][None], cfg,
                         lr=cfg["train"]["learning_rate"], t=1,
                         precision=precision, fault=fault)
    return {"losses": [got["loss"]], "token_loss": got["token_loss"],
            "grad_norms": got["grad_norms"],
            "change_norms": got["change_norms"]}


def boundary_positions(seg_row, reach):
    """The positions of a row within `reach` steps after a document's first
    step, the row's own first document left out (nothing lies before it)."""
    seg_row = np.asarray(seg_row)
    at = np.flatnonzero(np.diff(seg_row)) + 1
    near = np.zeros(seg_row.shape, bool)
    for k in range(reach):
        ok = at + k < seg_row.size
        near[at[ok] + k] |= seg_row[at[ok] + k] == seg_row[at[ok]]
    return near


def boundary_loss_gap(got, want, seg_row, reach):
    """Root mean square of (program's - reference's) loss over the
    predicting positions next to a boundary, over the mean of the
    reference's losses there; None where the row has no such position."""
    want, got = np.asarray(want)[0], np.asarray(got)[0]
    near = boundary_positions(seg_row, reach) & (want != 0.0)
    if not near.any():
        return None
    return float(np.sqrt(np.mean((got[near] - want[near]) ** 2))
                 / np.mean(want[near]))


READ_REACHES = (3, 16, 64, 256, 1024)


def reaches(limits):
    """The `n` of each `boundary_loss_gap_<n>` that `limits` names."""
    return sorted(int(k.rsplit("_", 1)[1]) for k in limits
                  if k.startswith("boundary_loss_gap_"))


def class_gap(got, want):
    """|norm over all leaves of the program's norms - the reference's| over
    the reference's (a reading beside the worst leaf's, no check)."""
    norm = lambda d: math.sqrt(sum(v * v for v in d.values()))
    return abs(norm(got) - norm(want)) / norm(want)


def compare_packed(first, want, seg_row, limits, near=None):
    """`train_mlm.compare`'s three numbers and `boundary_loss_gap_<n>` for
    each reach `n` in `near` (default: those `limits` names)."""
    checks = compare(first, want, limits)
    for n in (reaches(limits) if near is None else near):
        name = f"boundary_loss_gap_{n}"
        checks.append({"name": name,
                       "value": boundary_loss_gap(
                           first["token_loss"], want["token_loss"], seg_row,
                           n),
                       "limit": limits.get(name, 0.0)})
    return checks


def _compare(session, got, want, limits, near=None):
    row = session["first"]["row"]
    return compare_packed(got, want, session["inputs"][3][row], limits, near)


def check(session, window):
    """The first step against the reference's."""
    cfg = session["cell"]["config"]
    t0 = time.monotonic()
    want = reference_step(cfg, session["inputs"], session["first"]["row"])
    if window is not None:
        window.setdefault("notes", {})["reference_s"] = time.monotonic() - t0
    return _compare(session, session["first"], want, cfg["limits"])


def readings(session, window, faults=False):
    """Lower and upper readings for the limits (benchmark/readings.py): the
    program against the reference; the control (the reference with fp8
    matmul operands in the program's place); and, where `faults`, the
    reference with each planted fault: the state carried across document
    boundaries, the conv's taps reaching across them."""
    cfg = session["cell"]["config"]
    row = session["first"]["row"]
    want = reference_step(cfg, session["inputs"], row)

    def values(got, who):
        checks = _compare(session, got, want, {}, READ_REACHES)
        # which leaves read worst goes to stderr, beside the numbers
        print(json.dumps({who: {c["name"]: c["worst"][:3] for c in checks
                                if "worst" in c}}), file=sys.stderr)
        out = {c["name"]: c["value"] for c in checks}
        for kind in ("grad_norms", "change_norms"):
            out[kind[:-1] + "_class_gap"] = class_gap(got[kind], want[kind])
        return out

    out = {"program": values(session["first"], "program"),
           "control_fp8": values(reference_step(
               cfg, session["inputs"], row, precision="fp8"), "control_fp8")}
    if faults:
        for fault in ref.FAULTS:
            out["fault_" + fault] = values(
                reference_step(cfg, session["inputs"], row, fault=fault),
                "fault_" + fault)
    return out
