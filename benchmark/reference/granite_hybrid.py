"""Plain reference for the dense `granitemoehybrid` layer stack of
`ibm-granite/granite-4.0-h-micro` (its `config.json`) trained on packed
rows: forward, next-token loss, gradients and the first Adam step.

Written from the equations in `jax.numpy`, float32, matmuls at `highest`
precision; it imports nothing of the program (a few plain helpers come
from the other reference, `nemotron_h`). It also owns what the benchmark
feeds both sides: the weights (`make_flat_params`), the token ids
(`make_ids`) and the packing (`pack_rows`, on the host).

With m_e = embedding_multiplier, m_r = residual_multiplier, m_a =
attention_multiplier, m_l = logits_scaling, and d[t] the document of
position t of a packed row (`segment_ids`, non-decreasing along a row):

    h_0 = m_e W_emb[ids]
    per layer l (layer_types[l] in {mamba, attention}):
        h <- h + m_r Mixer_l(RMSNorm(h; w1_l))
        h <- h + m_r W_out_l (silu(a) * b),   [a | b] = RMSNorm(h; w2_l) W_in_l
    logits = (RMSNorm(h; w_f) W_emb^T) / m_l                    (tied)
    loss = mean over the positions t < T - 1 with d[t + 1] = d[t] of the
           next-token cross entropy

`mamba` (d_inner = heads x head size, G groups, state N, conv K):
    [z | xBC | dt] = u W_in
    xBC_t = silu(sum_k w[k] xBC_{t-(K-1)+k} [d[t-(K-1)+k] = d[t]] + b)
    dt = softplus(dt + dt_bias);  A = -exp(A_log);  per head
    S_t = [d[t] = d[t-1]] exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t
    y_t = S_t C_t + D x_t;   out = W_out (RMSNorm_groups(y silu(z)) w_g)
  the recurrence one time step after another (`lax.scan`), the document
  compared at every step.
`attention`: H query heads on H_kv key/value heads (query head i on KV head
    i // (H / H_kv)), no bias, no positional embedding,
    softmax over the keys j <= i with d[j] = d[i] of m_a q_i . k_j.

Layers are held as the program holds them, two blocks a layer: block 2l the
mixer with its norm, block 2l + 1 the MLP with its norm (`blocks/<i>/<name>`
in the flat dict). Parameters are *stored* as the configuration states
(bfloat16 matrices; float32 norm weights, A_log, dt_bias, D, conv) and
computed with in float32 from those values. `precision="fp8"` is the
control: both operands of every matmul rounded to an e4m3 float8 on the
forward pass (per-tensor scale), gradients straight through.

So that it fits beside nothing else on one chip at T = 16,384 (the float32
parameters alone are 3.1 GB), `first_step` backpropagates layer by layer,
recomputing each layer's forward; the recurrence is checkpointed every
`mamba_chunk_size` steps, attention goes a few query rows at a time; a
gradient leaf is reduced to its norm, and to the norm of the change Adam's
first step makes of it, as soon as it exists.

The planted faults (`fault=`) are for the readings that the limits are set
from, each the new mechanism left out in one place: "scan" carries the state
across document boundaries, "conv" lets the conv's taps reach into the
document before.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.nemotron_h import (  # plain helpers, no model
    ADAM_B1, ADAM_B2, ADAM_EPS, _draw, _mm, _rms, block_weights, expand,
    leaf_names, leaf_norm, make_batches, nest, round_to)

MAMBA, ATTENTION = "mamba", "attention"
FAULTS = ("scan", "conv")


def dims(cfg) -> dict:
    """The sizes by short names, from the published key names."""
    pub = cfg.get("published", {})
    d = dict(
        E=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        layers=list(cfg["layer_types"]),
        F=int(cfg["shared_intermediate_size"]),
        H=int(cfg["mamba_n_heads"]), P=int(cfg["mamba_d_head"]),
        N=int(cfg["mamba_d_state"]), G=int(cfg["mamba_n_groups"]),
        K=int(cfg["mamba_d_conv"]), chunk=int(cfg["mamba_chunk_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        eps=float(cfg["rms_norm_eps"]),
        m_e=float(cfg["embedding_multiplier"]),
        m_r=float(cfg["residual_multiplier"]),
        m_a=float(cfg["attention_multiplier"]),
        m_l=float(cfg["logits_scaling"]),
        depth=int(pub.get("num_hidden_layers", cfg["num_hidden_layers"])))
    d["D"] = d["E"] // d["heads"]
    d["d_inner"] = d["H"] * d["P"]
    d["conv_dim"] = d["d_inner"] + 2 * d["G"] * d["N"]
    init = cfg.get("assumed", {}).get("time_step", {})
    d["dt_min"] = float(init.get("min", 0.001))
    d["dt_max"] = float(init.get("max", 0.1))
    d["dt_floor"] = float(init.get("floor", 1e-4))
    if len(d["layers"]) != int(cfg["num_hidden_layers"]):
        raise ValueError("layer_types and num_hidden_layers disagree")
    if d["d_inner"] != int(cfg["mamba_expand"]) * d["E"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not "
                         "mamba_expand x hidden_size")
    if not cfg["tie_word_embeddings"] or int(cfg["num_local_experts"]):
        raise ValueError("this reference is the tied, dense model's")
    return d


def pattern(cfg) -> str:
    """The layers as the program's block letters, two a layer."""
    return "".join({MAMBA: "M-", ATTENTION: "*-"}[k]
                   for k in cfg["layer_types"])


def mixer_shapes(d, kind):
    """name -> (shape, how it is drawn) of a layer's mixer block."""
    E = d["E"]
    out = {"norm": ((E,), "one")}
    if kind == MAMBA:
        out.update({
            "in_proj": ((E, d["d_inner"] + d["conv_dim"] + d["H"]), "matrix"),
            "conv_w": ((d["K"], d["conv_dim"]), "conv"),
            "conv_b": ((d["conv_dim"],), "conv"),
            "dt_bias": ((d["H"],), "dt_bias"), "A_log": ((d["H"],), "A_log"),
            "D": ((d["H"],), "one"), "gate_norm": ((d["d_inner"],), "one"),
            "out_proj": ((d["d_inner"], E), "residual_out")})
    elif kind == ATTENTION:
        q, kv = d["heads"] * d["D"], d["kv_heads"] * d["D"]
        out.update({"wq": ((E, q), "matrix"), "wk": ((E, kv), "matrix"),
                    "wv": ((E, kv), "matrix"),
                    "wo": ((q, E), "residual_out")})
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    return out


def mlp_shapes(d):
    return {"norm": ((d["E"],), "one"),
            "mlp_in": ((d["E"], 2 * d["F"]), "matrix"),
            "mlp_out": ((d["F"], d["E"]), "residual_out")}


def make_flat_params(key, cfg):
    """All parameters as a flat dict `path -> array` in their stored types:
    `embed` (the head too), `blocks/<i>/<name>`, `final_norm`, drawn as the
    other hybrid's (`nemotron_h._draw`, the configuration file's `assumed`):
    N(0, 0.02) matrices in bfloat16, those that write into the residual
    stream scaled by 1/sqrt(published depth); conv weight and bias
    U(+-1/sqrt(K)); A_log = log U(1, 16); dt_bias the inverse softplus of a
    log-uniform step in [dt_min, dt_max] floored at dt_floor; D and norm
    weights 1."""
    d = dims(cfg)
    flat = {"embed": _draw(jax.random.fold_in(key, 1), (d["V"], d["E"]),
                           "matrix", d),
            "final_norm": jnp.ones((d["E"],), jnp.float32)}
    for l, kind in enumerate(d["layers"]):
        for i, shapes in ((2 * l, mixer_shapes(d, kind)),
                          (2 * l + 1, mlp_shapes(d))):
            kb = jax.random.fold_in(jax.random.fold_in(key, 3), i)
            for j, (name, (shape, how)) in enumerate(sorted(shapes.items())):
                flat[f"blocks/{i}/{name}"] = _draw(jax.random.fold_in(kb, j),
                                                   shape, how, d)
    return flat


def make_ids(key, cfg, rows, seq_len):
    """Token ids [rows, T]: Zipf(1.0) over the ids held, ranks permuted
    from the seed, as the other hybrid cell draws them."""
    return make_batches(key, cfg, rows, 1, seq_len)["input_ids"][:, 0]


def pack_rows(seed, rows, seq_len, packing):
    """The packing of `rows` rows of `seq_len` tokens, on the host:
    (segment_ids int32 [rows, T], lengths: per row the list of its
    documents' lengths). Document lengths are drawn log-normal (`median`,
    `sigma`), rounded and clipped to [`min`, `max`]; the documents are laid
    end to end in drawn order and the stream is cut every `seq_len` tokens:
    no padding, and a document cut by a row's end goes on as a new document
    at the next row's start. Ids count a row's documents from 0."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 33])
    total = rows * seq_len
    lengths, have = [], 0
    while have < total:
        n = int(np.clip(round(math.exp(rng.normal(
            math.log(packing["median"]), packing["sigma"]))),
            packing["min"], packing["max"]))
        lengths.append(n)
        have += n
    starts = np.cumsum([0] + lengths[:-1])
    first = np.zeros(total, np.int32)
    first[starts] = 1
    first = first.reshape(rows, seq_len)
    first[:, 0] = 1                     # a row's start starts a document
    seg = np.cumsum(first, axis=1, dtype=np.int32) - 1
    per_row = []
    for row in first:
        at = np.flatnonzero(row)
        per_row.append(np.diff(np.append(at, seq_len)).tolist())
    return seg, per_row


# -- the model --------------------------------------------------------------

def conv_silu(xBC, w, b, seg, fault=None):
    """silu of the causal depthwise conv (tap K - 1 on the current step)
    that stops at a document's first step. `fault == "conv"` lets the taps
    reach back into the document before."""
    K, t = w.shape[0], xBC.shape[1]
    padded = jnp.pad(xBC, [(0, 0), (K - 1, 0), (0, 0)])
    before = jnp.pad(seg, [(0, 0), (K - 1, 0)], constant_values=-1)
    out = b
    for k in range(K):
        same = (before[:, k:k + t] >= 0 if fault == "conv"
                else before[:, k:k + t] == seg)
        out = out + jnp.where(same[..., None], padded[:, k:k + t], 0.0) * w[k]
    return jax.nn.silu(out)


def recurrence(x, dt, A, Bm, Cm, seg, chunk, fault=None):
    """y [b, t, h, p] of S_t = [d_t = d_{t-1}] exp(dt_t A) S_{t-1} + dt_t
    x_t (outer) B_t, y_t = S_t C_t, one time step after another. x
    [b,t,h,p], dt [b,t,h], A [h], Bm, Cm [b,t,g,n], seg [b,t]. The steps
    are taken `chunk` at a time under `jax.checkpoint` (memory only).
    `fault == "scan"` carries the state into the next document."""
    b, t, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    r = h // g
    same = jnp.concatenate([jnp.zeros((b, 1), bool),
                            seg[:, 1:] == seg[:, :-1]], axis=1)
    if fault == "scan":
        same = jnp.ones_like(same)
    pad = -t % chunk
    if pad:         # dt = 0 inside the last document: nothing moves
        x, dt, Bm, Cm = (jnp.pad(v, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (v.ndim - 2))
                         for v in (x, dt, Bm, Cm))
        same = jnp.pad(same, [(0, 0), (0, pad)], constant_values=True)
    c = (t + pad) // chunk

    def step(S, inp):
        x_t, dt_t, B_t, C_t, same_t = inp
        B_t, C_t = (jnp.repeat(v, r, axis=1) for v in (B_t, C_t))  # [b,h,n]
        keep = jnp.where(same_t[:, None], jnp.exp(dt_t * A), 0.0)
        S = (S * keep[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.sum(S * C_t[:, :, None, :], axis=-1)

    steps = jax.checkpoint(lambda S, inp: jax.lax.scan(step, S, inp))
    time_major = lambda v: jnp.moveaxis(v, 1, 0).reshape(
        (c, chunk) + v.shape[:1] + v.shape[2:])
    _, y = jax.lax.scan(steps, jnp.zeros((b, h, p, n), jnp.float32),
                        tuple(time_major(v) for v in (x, dt, Bm, Cm, same)))
    y = jnp.moveaxis(y.reshape((c * chunk, b, h, p)), 0, 1)
    return y[:, :t] if pad else y


def mamba(u, w, seg, d, precision, fault):
    b, t, _ = u.shape
    H, P, G, N = d["H"], d["P"], d["G"], d["N"]
    zxbcdt = _mm("bte,ef->btf", u, w["in_proj"], precision)
    z, xBC, dt = jnp.split(zxbcdt, [d["d_inner"],
                                    d["d_inner"] + d["conv_dim"]], axis=-1)
    xBC = conv_silu(xBC, w["conv_w"], w["conv_b"], seg, fault)
    x, Bm, Cm = jnp.split(xBC, [d["d_inner"], d["d_inner"] + G * N], axis=-1)
    x = x.reshape(b, t, H, P)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(w["A_log"]), Bm.reshape(b, t, G, N),
                   Cm.reshape(b, t, G, N), seg, d["chunk"], fault)
    y = (y + w["D"][:, None] * x).reshape(b, t, d["d_inner"])
    y = y * jax.nn.silu(z)
    y = _rms(y.reshape(b, t, G, -1), 1.0, d["eps"]).reshape(y.shape)
    return _mm("btf,fe->bte", y * w["gate_norm"], w["out_proj"], precision)


def attention(u, w, seg, d, precision, rows):
    b, t, _ = u.shape
    H, Hkv, D = d["heads"], d["kv_heads"], d["D"]
    q = _mm("bte,ef->btf", u, w["wq"], precision).reshape(b, t, Hkv,
                                                          H // Hkv, D)
    k = _mm("bte,ef->btf", u, w["wk"], precision).reshape(b, t, Hkv, D)
    v = _mm("bte,ef->btf", u, w["wv"], precision).reshape(b, t, Hkv, D)
    rows = min(rows, t)
    pad = -t % rows
    qp = jnp.pad(q, [(0, 0), (0, pad)] + [(0, 0)] * 3) if pad else q
    segp = jnp.pad(seg, [(0, 0), (0, pad)], mode="edge") if pad else seg
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def some_rows(args):
        q_rows, seg_rows, first = args              # [b, rows, Hkv, R, D]
        s = _mm("bqgrd,bkgd->bgrqk", q_rows, k, precision) * d["m_a"]
        seen = ((key_pos[None, :] <= (first + jnp.arange(rows))[:, None])
                & (seg_rows[:, :, None] == seg[:, None, :]))   # [b, q, k]
        p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf),
                           axis=-1)
        return _mm("bgrqk,bkgd->bqgrd", p, v, precision)

    n = (t + pad) // rows
    ctx = jax.lax.map(some_rows, (
        jnp.moveaxis(qp.reshape((b, n, rows) + q.shape[2:]), 1, 0),
        jnp.moveaxis(segp.reshape(b, n, rows), 1, 0),
        jnp.arange(n) * rows))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, n * rows, H * D)[:, :t]
    return _mm("btf,fe->bte", ctx, w["wo"], precision)


def gated_mlp(u, w, precision):
    a, b = jnp.split(_mm("bte,ef->btf", u, w["mlp_in"], precision), 2, -1)
    return _mm("btf,fe->bte", jax.nn.silu(a) * b, w["mlp_out"], precision)


def layer(kind, h, mixer, mlp, seg, d, precision="f32", fault=None,
          attn_rows=256):
    """One layer over h [B, T, E]: its mixer block, then its MLP block,
    each pre-norm and weighed by m_r; `mixer`, `mlp`: name -> float32."""
    u = _rms(h, mixer["norm"], d["eps"])
    out = (mamba(u, mixer, seg, d, precision, fault) if kind == MAMBA
           else attention(u, mixer, seg, d, precision, attn_rows))
    h = h + d["m_r"] * out
    return h + d["m_r"] * gated_mlp(_rms(h, mlp["norm"], d["eps"]), mlp,
                                    precision)


def predicts(seg):
    """[B, T] bool: the positions whose next token is their document's."""
    return jnp.concatenate([seg[:, 1:] == seg[:, :-1],
                            jnp.zeros(seg.shape[:1] + (1,), bool)], axis=1)


def logits(h, final_norm, embed, d, precision="f32"):
    return _mm("bte,ve->btv", _rms(h, final_norm, d["eps"]), embed,
               precision) / d["m_l"]


def token_losses(h, final_norm, embed, ids, seg, d, precision="f32"):
    """float32 [B, T]: each predicting position's cross entropy, 0 at the
    others."""
    out = logits(h, final_norm, embed, d, precision)
    labels = jnp.roll(ids, -1, axis=1)
    picked = jnp.take_along_axis(out, labels[..., None], axis=-1)[..., 0]
    return jnp.where(predicts(seg),
                     jax.nn.logsumexp(out, axis=-1) - picked, 0.0)


def hidden(flat, ids, seg, d, precision="f32", fault=None, attn_rows=256):
    """The last layer's output [B, T, E]; `flat` in float32."""
    h = d["m_e"] * flat["embed"][ids]
    for l, kind in enumerate(d["layers"]):
        h = layer(kind, h, block_weights(flat, 2 * l),
                  block_weights(flat, 2 * l + 1), seg, d, precision, fault,
                  attn_rows)
    return h


def loss(flat, ids, seg, d, precision="f32", fault=None, attn_rows=256):
    """The whole model in one expression: (mean loss, token losses); what
    `first_step` computes layer by layer."""
    h = hidden(flat, ids, seg, d, precision, fault, attn_rows)
    per_tok = token_losses(h, flat["final_norm"], flat["embed"], ids, seg, d,
                           precision)
    return jnp.sum(per_tok) / jnp.maximum(jnp.sum(predicts(seg)), 1), per_tok


@functools.lru_cache(maxsize=8)
def _step_functions(cfg_json, lr, t, precision, fault):
    """The jitted pieces of `first_step` for one configuration and variant:
    (embed, forward by kind, backward by kind, head, embed's backward)."""
    cfg = json.loads(cfg_json)
    d = dims(cfg)
    f32 = lambda w: {k: v.astype(jnp.float32) for k, v in w.items()}
    rows = int(cfg.get("reference", {}).get("attn_rows", 256))
    # Adam from zero moments: m = (1 - b1) g, u = (1 - b2) g^2
    alpha = lr * math.sqrt(1 - ADAM_B2 ** t) / (1 - ADAM_B1 ** t)

    def norms(prefix, stored, grads):
        """{path: (gradient norm, change norm)} of some leaves."""
        out = {}
        for k, g in grads.items():
            w = stored[k].astype(jnp.float32)
            update = alpha * (1 - ADAM_B1) * g / (
                math.sqrt(1 - ADAM_B2) * jnp.abs(g) + ADAM_EPS)
            out[prefix + k] = (
                leaf_norm(prefix + k, g),
                leaf_norm(prefix + k,
                          round_to(w - update, stored[k].dtype) - w))
        return out

    def apply(kind):
        return lambda h, mixer, mlp, seg: layer(
            kind, h, f32(mixer), f32(mlp), seg, d, precision, fault, rows)

    def backward(kind):
        # leaves named "mixer/<leaf>" and "mlp/<leaf>": the caller puts the
        # blocks' paths in their place, so that layers of one kind share
        # one compiled function
        @jax.jit
        def run(h, mixer, mlp, seg, dh):
            _, vjp = jax.vjp(lambda h, a, b: layer(
                kind, h, a, b, seg, d, precision, fault, rows),
                h, f32(mixer), f32(mlp))
            dh, da, db = vjp(dh)
            return dh, {**norms("mixer/", mixer, da),
                        **norms("mlp/", mlp, db)}
        return run

    @jax.jit
    def head(h, final_norm, table, ids, seg):
        def mean_loss(h, w_f, w_e):
            per_tok = token_losses(h, w_f, w_e, ids, seg, d, precision)
            return (jnp.sum(per_tok)
                    / jnp.maximum(jnp.sum(predicts(seg)), 1), per_tok)
        (total, per_tok), (dh, d_norm, d_table) = jax.value_and_grad(
            mean_loss, argnums=(0, 1, 2), has_aux=True)(
            h, final_norm.astype(jnp.float32), table.astype(jnp.float32))
        return (total, per_tok, dh,
                norms("", {"final_norm": final_norm},
                      {"final_norm": d_norm}), d_table)

    @jax.jit
    def embed_backward(table, ids, dh, d_table):
        # the tied matrix: the head's gradient and the lookup's
        g = d_table.at[ids].add(d["m_e"] * dh)
        return norms("", {"embed": table}, {"embed": g})

    kinds = set(d["layers"])
    return (jax.jit(lambda table, ids: d["m_e"]
                    * table.astype(jnp.float32)[ids]),
            {kind: jax.jit(apply(kind)) for kind in kinds},
            {kind: backward(kind) for kind in kinds}, head, embed_backward)


def first_step(flat0, ids, seg, cfg, *, lr, t=1, precision="f32",
               fault=None):
    """The reference's first training step from `flat0` (stored types) on
    the packed rows `ids`, `seg` [B, T]: `{"loss", "token_loss" [B, T],
    "grad_norms", "change_norms"}` with one norm per leaf, the change being
    what Adam's first step (moments from zero, learning rate `lr`, bias
    corrections of step count `t`) and the rounding to the stored type make
    of each leaf.

    Backpropagation by hand over the layers, so that it fits: the forward
    pass keeps each layer's input; the backward pass takes one layer at a
    time (its float32 weights, its recomputed forward, its gradients),
    reduces each gradient leaf to its two norms at once and hands on only
    the gradient of the layer's input."""
    kinds = cfg["layer_types"]
    embed, forward, backward, head, embed_backward = _step_functions(
        json.dumps(cfg, sort_keys=True), lr, t, precision, fault)
    blocks = lambda l: (block_weights(flat0, 2 * l),
                        block_weights(flat0, 2 * l + 1))
    inputs = []
    h = embed(flat0["embed"], ids)
    for l, kind in enumerate(kinds):
        inputs.append(h)
        h = forward[kind](h, *blocks(l), seg)
    total, per_tok, dh, both, d_table = head(h, flat0["final_norm"],
                                             flat0["embed"], ids, seg)
    del h
    for l, kind in reversed(list(enumerate(kinds))):
        dh, more = backward[kind](inputs.pop(), *blocks(l), seg, dh)
        where = {"mixer": f"blocks/{2 * l}", "mlp": f"blocks/{2 * l + 1}"}
        both.update({f"{where[k.split('/')[0]]}/{k.split('/')[1]}": v
                     for k, v in more.items()})
    both.update(embed_backward(flat0["embed"], ids, dh, d_table))
    both = jax.device_get(both)
    return {"loss": float(total),
            "token_loss": np.asarray(jax.device_get(per_tok)),
            "grad_norms": expand({k: v[0] for k, v in both.items()}),
            "change_norms": expand({k: v[1] for k, v in both.items()})}


__all__ = ["FAULTS", "dims", "pattern", "make_flat_params", "make_ids",
           "pack_rows", "first_step", "loss", "hidden", "logits", "nest",
           "leaf_names", "leaf_norm", "expand", "ADAM_B1"]
