"""Plain reference for the DeepSeek-V3 family's decoder with the keys of
`jdopensource/JoyAI-LLM-Flash`'s `config.json` (`model_type`
`joyai_llm_flash`): forward, the next-token and the multi-token-prediction
loss, gradients and the first Adam step.

Written from the equations (arXiv:2412.19437 §2.1-2.2) in `jax.numpy`,
float32, matmuls at `highest` precision; it imports nothing of the program
(only plain helpers of the other reference, `nemotron_h`). It also owns what
the benchmark feeds both sides: the weights (`make_flat_params`) and the
batches (`make_batches`), each one jitted call from the seed.

Pre-norm residual layers, `h <- h + Attn(RMSNorm(h))`, then `h <- h +
FFN(RMSNorm(h))`, eps `rms_norm_eps`; `h_0 = W_emb[ids]`.

Latent attention (every layer; `u` the normed input, H heads):
    c_q  = RMSNorm(u W_qa; w_q);  q = c_q W_qb -> H x [q_nope d_n | q_rope d_r]
    [c_kv | k_rope] = u W_kva;  c_kv = RMSNorm(c_kv; w_kv)
    [k_nope | v] = c_kv W_kvb -> H x (d_n + d_v);  k_rope ONE head for all H
    q_rope, k_rope <- RoPE at positions 0..T-1: the pair (x_2i, x_2i+1)
        turned by pos * theta^(-2i/d_r)  (`rope_interleave`), no scaling
    o_h = softmax(causal(q_h . [k_nope_h | k_rope] / sqrt(d_n + d_r))) v_h
    out = concat_h(o_h) W_o
FFN of the first `first_k_dense_replace` layers: W_down (silu(W_gate u) *
    W_up u), width `intermediate_size`.
FFN of the other layers and of the MTP module: s = sigmoid(u W_r) over ALL
    the published experts; selection by s + b with b =
    `e_score_correction_bias` = 0; `n_group` = `topk_group` = 1; top k;
    g_k = scale s_k / (sum of the chosen s + 1e-20);
    out = sum_k g_k Down_e(silu(Gate_e u) * Up_e u) + Shared(u).
    Of the routed sum only the terms of the experts held on this chip are
    computed (a dense loop over them under a mask).
Multi-token prediction (one module, depth 1), h_t the main model's last
hidden state AFTER its final norm (`assumed`):
    m_t  = W_eh [ RMSNorm(Emb(x_{t+1}); w_e) ; RMSNorm(h_t; w_h) ]
    m'_t = Layer_MTP(m_t)      (latent attention + experts, causal over t)
    p_t  = Head(RMSNorm(m'_t; w_n))     embedding and head shared
    L = L_main + lambda L_mtp;  L_main: mean over t < T-1 of CE(logits_t,
    x_{t+1});  L_mtp: mean over t < T-2 of CE(p_t, x_{t+2}).

The parameters' names and layout are the program's tree (`nest`): blocks
`blocks/<i>/...` by the pattern `L-` + `LE` x (layers - 1), the module under
`mtp/...`. An expert's `w1` is [Gate ; Up] as one [2 F, E] matrix, `w2` is
Down transposed, [F, E]; `mlp_in` is [W_gate | W_up], E x 2 F.

Parameters are *stored* as the configuration states (bfloat16 matrices;
float32 norm weights and router) and computed with in float32 from those
values. `precision="fp8"` is the control: the two operands of every matmul
that the program runs in bfloat16 are rounded to an e4m3 float8 on the
forward pass (per-tensor scale), gradients straight through.

It has to fit beside nothing else on one chip at B x T = 2 x 8,192:
`first_step` backpropagates block by block, recomputing each block's
forward; attention goes a few query rows at a time, the held experts one at
a time, the two heads a few rows at a time; and a gradient leaf is reduced
to its norm, and to the norm of the change Adam's first step makes from it,
as soon as it is whole (the embedding's and the head's gradients have two
parts, which are added first).

The planted faults (`fault=`) are for the readings that the limits are set
from: "rope" leaves the rotary embedding out, "vwidth" reads the values as
if a head's were as wide as its keys (192 for 128: head h's values start
at column 192 h of the packed values, wrapped), "mtp_shift" takes the MTP
loss against x_{t+1}, "shared" drops the shared expert.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.nemotron_h import (  # plain helpers, no model
    ADAM_B1, ADAM_B2, ADAM_EPS, _draw, _mm, _rms, expand, leaf_names,
    leaf_norm, make_batches, per_expert, per_expert_leaf, round_to)

LATENT, EXPERTS, MLP = "L", "E", "-"
MTP_PATTERN = LATENT + EXPERTS
FAULTS = ("rope", "vwidth", "mtp_shift", "shared")


def dims(cfg) -> dict:
    """The sizes by short names. `n_routed_experts` in the file counts the
    experts held here; the router's width is the published count."""
    pub = cfg.get("published", {})
    dep = cfg.get("deployment", {})
    layers, dense = int(cfg["num_hidden_layers"]), int(
        cfg["first_k_dense_replace"])
    d = dict(
        E=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        pattern=(LATENT + MLP) * dense + (LATENT + EXPERTS) * (layers - dense),
        heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        dn=int(cfg["qk_nope_head_dim"]), dr=int(cfg["qk_rope_head_dim"]),
        dv=int(cfg["v_head_dim"]), theta=float(cfg["rope_theta"]),
        Fd=int(cfg["intermediate_size"]),
        held=int(cfg["n_routed_experts"]),
        experts=int(pub.get("n_routed_experts", cfg["n_routed_experts"])),
        first=int(dep.get("first_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]),
        F=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        scale=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]),
        mtp=int(cfg["num_nextn_predict_layers"]),
        lam=float(cfg["train"]["mtp_loss_weight"]),
        depth=int(pub.get("num_hidden_layers", layers)))
    if int(cfg["qk_head_dim"]) != d["dn"] + d["dr"]:
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if int(cfg["n_group"]) != 1 or int(cfg["topk_group"]) != 1:
        raise ValueError("group-limited selection is not built: n_group and "
                         "topk_group must be 1")
    if d["mtp"] not in (0, 1):
        raise ValueError("one multi-token-prediction module at most")
    return d


def block_shapes(d, kind):
    """name -> (shape, how it is drawn) of one block's leaves."""
    E = d["E"]
    out = {"norm": ((E,), "one")}
    if kind == LATENT:
        H = d["heads"]
        out.update({
            "wq_a": ((E, d["q_rank"]), "matrix"),
            "q_norm": ((d["q_rank"],), "one"),
            "wq_b": ((d["q_rank"], H * (d["dn"] + d["dr"])), "matrix"),
            "wkv_a": ((E, d["kv_rank"] + d["dr"]), "matrix"),
            "kv_norm": ((d["kv_rank"],), "one"),
            "wkv_b": ((d["kv_rank"], H * (d["dn"] + d["dv"])), "matrix"),
            "wo": ((H * d["dv"], E), "residual_out")})
    elif kind == EXPERTS:
        out.update({
            "router": ((E, d["experts"]), "router"),
            "w1": ((d["held"], 2 * d["F"], E), "matrix"),
            "w2": ((d["held"], d["F"], E), "residual_out"),
            "shared_w1": ((E, 2 * d["Fs"]), "matrix"),
            "shared_w2": ((d["Fs"], E), "residual_out")})
    elif kind == MLP:
        out.update({"mlp_in": ((E, 2 * d["Fd"]), "matrix"),
                    "mlp_out": ((d["Fd"], E), "residual_out")})
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return out


def make_flat_params(key, cfg):
    """All parameters as a flat dict `path -> array` in their stored types
    (`nemotron_h._draw`: N(0, 0.02) matrices in bfloat16, those that write
    into the residual stream scaled by 1/sqrt(published depth), the router
    the same in float32, norm weights 1): `embed`, `head`, `final_norm`,
    `blocks/<i>/<name>` and, with an MTP module, `mtp/embed_norm`,
    `mtp/hidden_norm`, `mtp/merge`, `mtp/final_norm`,
    `mtp/blocks/<0|1>/<name>`. The same for every caller."""
    d = dims(cfg)
    E = d["E"]
    one = lambda: jnp.ones((E,), jnp.float32)
    flat = {"embed": _draw(jax.random.fold_in(key, 1), (d["V"], E),
                           "matrix", d),
            "head": _draw(jax.random.fold_in(key, 2), (E, d["V"]),
                          "matrix", d),
            "final_norm": one()}

    def blocks(key, pattern, prefix):
        for i, kind in enumerate(pattern):
            kb = jax.random.fold_in(key, i)
            for j, (name, (shape, how)) in enumerate(
                    sorted(block_shapes(d, kind).items())):
                flat[f"{prefix}blocks/{i}/{name}"] = _draw(
                    jax.random.fold_in(kb, j), shape, how, d)

    blocks(jax.random.fold_in(key, 3), d["pattern"], "")
    if d["mtp"]:
        km = jax.random.fold_in(key, 4)
        flat.update({"mtp/embed_norm": one(), "mtp/hidden_norm": one(),
                     "mtp/final_norm": one(),
                     "mtp/merge": _draw(jax.random.fold_in(km, 0),
                                        (2 * E, E), "matrix", d)})
        blocks(jax.random.fold_in(km, 1), MTP_PATTERN, "mtp/")
    return flat


def nest(flat):
    """The flat dict as the tree the program holds: a path's numeric parts
    index lists (`blocks`), the others dicts."""
    tree = {}
    for path, v in flat.items():
        parts = path.split("/")
        node = tree
        for a, b in zip(parts, parts[1:]):
            node = node.setdefault(a, {})
        node[parts[-1]] = v

    def lists(node):
        if not isinstance(node, dict):
            return node
        if node and all(k.isdigit() for k in node):
            return [lists(node[str(i)]) for i in range(len(node))]
        return {k: lists(v) for k, v in node.items()}

    return lists(tree)


def block_weights(flat, prefix):
    """The leaves under `prefix` (`blocks/3/`, `mtp/blocks/0/`) by name."""
    return {k[len(prefix):]: v for k, v in flat.items()
            if k.startswith(prefix)}


# -- the model ----------------------------------------------------------

def rope(x, theta):
    """x [b, t, ..., d] with the pair (x_2i, x_2i+1) turned by the angle
    pos * theta^(-2i/d), pos = 0..t-1."""
    d, t = x.shape[-1], x.shape[1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((1, t) + (1,) * (x.ndim - 3) + (d // 2,))
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def latent_attention(u, w, d, precision, rows, fault):
    b, t, _ = u.shape
    H, dn, dr, dv, r = d["heads"], d["dn"], d["dr"], d["dv"], d["kv_rank"]
    cq = _rms(_mm("bte,ef->btf", u, w["wq_a"], precision), w["q_norm"],
              d["eps"])
    q = _mm("btf,fg->btg", cq, w["wq_b"], precision).reshape(b, t, H, dn + dr)
    kv_a = _mm("bte,ef->btf", u, w["wkv_a"], precision)
    ckv = _rms(kv_a[..., :r], w["kv_norm"], d["eps"])
    kv = _mm("btf,fg->btg", ckv, w["wkv_b"], precision).reshape(
        b, t, H, dn + dv)
    q_rope, k_rope = q[..., dn:], kv_a[..., r:]
    if fault != "rope":
        q_rope, k_rope = rope(q_rope, d["theta"]), rope(k_rope, d["theta"])
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope[:, :, None, :], (b, t, H, dr))], axis=-1)
    v = kv[..., dn:]
    if fault == "vwidth":
        cols = (jnp.arange(H)[:, None] * (dn + dr)
                + jnp.arange(dv)[None, :]) % (H * dv)
        v = v.reshape(b, t, H * dv)[..., cols]
    rows = min(rows, t)
    pad = -t % rows
    qp = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)]) if pad else q
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def some_rows(args):
        q_rows, first = args                       # [b, rows, H, dn + dr]
        s = _mm("bqhd,bkhd->bhqk", q_rows, k, precision) / math.sqrt(dn + dr)
        seen = key_pos[None, :] <= (first + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, precision)

    n = (t + pad) // rows
    ctx = jax.lax.map(some_rows, (
        jnp.moveaxis(qp.reshape((b, n, rows) + q.shape[2:]), 1, 0),
        jnp.arange(n) * rows))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, n * rows, H * dv)[:, :t]
    return _mm("btf,fe->bte", ctx, w["wo"], precision)


def _gated(u, w_in, w_out, precision, in_eq="bte,ef->btf"):
    a, b = jnp.split(_mm(in_eq, u, w_in, precision), 2, axis=-1)
    return _mm("btf,fe->bte", jax.nn.silu(a) * b, w_out, precision)


def experts(u, w, d, precision, fault):
    """(output, how many assignments each held expert received)."""
    s = jax.nn.sigmoid(jnp.einsum("bte,en->btn", u, w["router"],
                                  precision="highest"))
    chosen, idx = jax.lax.top_k(s, d["top_k"])      # by s + b, b = 0
    gates = d["scale"] * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)

    @jax.checkpoint
    def one(acc, inp):
        w1, w2, e = inp
        gate = jnp.sum(jnp.where(idx == d["first"] + e, gates, 0.0), -1)
        # an expert's [Gate ; Up] is stored [2 F, E], as a model file holds it
        return acc + gate[..., None] * _gated(u, w1, w2, precision,
                                              "bte,fe->btf"), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (w["w1"], w["w2"], jnp.arange(d["held"])))
    counts = jnp.sum(idx[..., None] == d["first"] + jnp.arange(d["held"]),
                     axis=(0, 1, 2))
    if fault != "shared":
        out = out + _gated(u, w["shared_w1"], w["shared_w2"], precision)
    return out, counts


def block(kind, h, w, d, precision="f32", fault=None, attn_rows=512):
    """One pre-norm residual block of kind `kind` over h [B, T, E] with its
    weights `w` (name -> float32 array): (h + Mixer(RMSNorm(h)), the held
    experts' assignment counts or None)."""
    u = _rms(h, w["norm"], d["eps"])
    if kind == LATENT:
        return h + latent_attention(u, w, d, precision, attn_rows,
                                    fault), None
    if kind == EXPERTS:
        out, n = experts(u, w, d, precision, fault)
        return h + out, n
    return h + _gated(u, w["mlp_in"], w["mlp_out"], precision), None


def head_loss(hn, head, ids, shift, d, precision="f32", rows=1024,
              per_position=False):
    """Sum over t < T - shift of CE(hn_t . head, ids[t + shift]), the
    logits made `rows` positions at a time and never held whole; with
    `per_position` (the sum, each position's term [B, T])."""
    b, t, E = hn.shape
    labels = jnp.roll(ids, -shift, axis=1)
    weight = (jnp.arange(t) < t - shift).astype(jnp.float32)
    rows = min(rows, t)
    pad = -t % rows
    if pad:
        hn = jnp.pad(hn, [(0, 0), (0, pad), (0, 0)])
        labels = jnp.pad(labels, [(0, 0), (0, pad)])
        weight = jnp.pad(weight, [(0, pad)])
    n = (t + pad) // rows

    @jax.checkpoint
    def some_rows(args):
        x, y, m = args                                  # [b, rows, E] ...
        logits = _mm("bte,ev->btv", x, head, precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return (lse - picked) * m

    terms = jax.lax.map(some_rows, (
        jnp.moveaxis(hn.reshape(b, n, rows, E), 1, 0),
        jnp.moveaxis(labels.reshape(b, n, rows), 1, 0),
        weight.reshape(n, rows)))                       # [n, b, rows]
    if not per_position:
        return jnp.sum(terms)
    return jnp.sum(terms), jnp.moveaxis(terms, 0, 1).reshape(b, -1)[:, :t]


def mtp_merge(ahead, hn, w, d, precision="f32"):
    """m = W_eh [RMSNorm(Emb(x_{t+1}); w_e) ; RMSNorm(h_t; w_h)]."""
    both = jnp.concatenate([_rms(ahead, w["embed_norm"], d["eps"]),
                            _rms(hn, w["hidden_norm"], d["eps"])], axis=-1)
    return _mm("btf,fe->bte", both, w["merge"], precision)


def losses(flat, ids, d, precision="f32", fault=None, attn_rows=512,
           blocks=None):
    """The whole model in one expression: (L_main, L_mtp or None, expert
    tokens [n_expert_blocks (+ 1 for the module), held]); `flat` in
    float32. What `first_step` computes block by block. `blocks` (tests)
    stops the main stack after that many blocks."""
    b, t = ids.shape
    h = flat["embed"][ids]
    counts = []
    for i, kind in enumerate(d["pattern"][:blocks]):
        h, n = block(kind, h, block_weights(flat, f"blocks/{i}/"), d,
                     precision, fault, attn_rows)
        if n is not None:
            counts.append(n)
    hn = _rms(h, flat["final_norm"], d["eps"])
    main = head_loss(hn, flat["head"], ids, 1, d, precision) / (b * (t - 1))
    mtp = None
    if d["mtp"]:
        m = mtp_merge(flat["embed"][jnp.roll(ids, -1, axis=1)], hn,
                      block_weights(flat, "mtp/"), d, precision)
        for i, kind in enumerate(MTP_PATTERN):
            m, n = block(kind, m, block_weights(flat, f"mtp/blocks/{i}/"), d,
                         precision, fault, attn_rows)
            if n is not None:
                counts.append(n)
        shift = 1 if fault == "mtp_shift" else 2
        mtp = head_loss(_rms(m, flat["mtp/final_norm"], d["eps"]),
                        flat["head"], ids, shift, d,
                        precision) / (b * (t - shift))
    return main, mtp, (jnp.stack(counts) if counts else None)


def logits(flat, ids, d, attn_rows=512):
    """Float32 next-token logits [B, T, V] of the main model (tests)."""
    h = flat["embed"][ids]
    for i, kind in enumerate(d["pattern"]):
        h, _ = block(kind, h, block_weights(flat, f"blocks/{i}/"), d,
                     attn_rows=attn_rows)
    return _mm("bte,ev->btv", _rms(h, flat["final_norm"], d["eps"]),
               flat["head"], "f32")


@functools.lru_cache(maxsize=8)
def _step_functions(cfg_json, shape, lr, t, precision, fault):
    """The jitted pieces of `first_step` for one configuration, batch shape
    and variant. Kept, so that a tool that walks many seeds in one process
    compiles them once."""
    cfg = json.loads(cfg_json)
    d = dims(cfg)
    f32 = lambda w: {k: v.astype(jnp.float32) for k, v in w.items()}
    B, T = shape
    rows = int(cfg.get("reference", {}).get("attn_rows", 512))
    head_rows = int(cfg.get("reference", {}).get("head_rows", 1024))
    shift = 1 if fault == "mtp_shift" else 2
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
        return lambda h, w: block(kind, h, f32(w), d, precision, fault, rows)

    def backward(kind):
        # leaves named "/<leaf>": the caller puts the block's path in
        # front, so that blocks of one kind share one compiled function
        @jax.jit
        def run(h, w, dh):
            _, vjp, _ = jax.vjp(lambda h, w32: block(
                kind, h, w32, d, precision, fault, rows), h, f32(w),
                has_aux=True)
            dh, dw = vjp(dh)
            return dh, norms("/", w, dw)
        return run

    @jax.jit
    def final_norm(h, w):
        return _rms(h, w.astype(jnp.float32), d["eps"])

    @jax.jit
    def merge(ahead, hn, w):
        return mtp_merge(ahead, hn, f32(w), d, precision)

    @jax.jit
    def mtp_head(m, w_n, head, ids):
        """(L_mtp, its terms [B, T], lambda dL_mtp/dm, norms of w_n, lambda
        dL_mtp/dhead)."""
        def f(m, n, out):
            total, terms = head_loss(_rms(m, n, d["eps"]), out, ids, shift,
                                     d, precision, head_rows, True)
            return total / (B * (T - shift)), terms
        (loss, terms), (dm, dn, dhead) = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(
            m, w_n.astype(jnp.float32), head.astype(jnp.float32))
        lam = d["lam"]
        return (loss, terms, lam * dm,
                norms("mtp/", {"final_norm": w_n}, {"final_norm": lam * dn}),
                lam * dhead)

    @jax.jit
    def merge_backward(ahead, hn, w, dm):
        _, vjp = jax.vjp(lambda a, x, w32: mtp_merge(a, x, w32, d, precision),
                         ahead, hn, f32(w))
        da, dhn, dw = vjp(dm)
        return da, dhn, norms("mtp/", w, dw)

    @jax.jit
    def main_head(h, w_n, head, ids, dhn, dhead_mtp):
        """L_main and the gradients at the main stack's end: `dhn`, the MTP
        module's cotangent of the normed state, enters through the norm;
        the head's gradient is the sum of its two passes."""
        def f(h, n, out):
            hn = _rms(h, n, d["eps"])
            loss = head_loss(hn, out, ids, 1, d, precision,
                             head_rows) / (B * (T - 1))
            return loss + jnp.sum(hn * dhn), loss
        (_, loss), (dh, dn, dhead) = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(
            h, w_n.astype(jnp.float32), head.astype(jnp.float32))
        return loss, dh, norms("", {"final_norm": w_n, "head": head},
                               {"final_norm": dn, "head": dhead + dhead_mtp})

    @jax.jit
    def embed_backward(table, ids, dh, da):
        g = jnp.zeros(table.shape, jnp.float32).at[ids].add(dh)
        if da is not None:
            g = g.at[jnp.roll(ids, -1, axis=1)].add(da)
        return norms("", {"embed": table}, {"embed": g})

    kinds = set(d["pattern"]) | set(MTP_PATTERN if d["mtp"] else "")
    return dict(
        embed=jax.jit(lambda table, ids: table.astype(jnp.float32)[ids]),
        forward={kind: jax.jit(apply(kind)) for kind in kinds},
        backward={kind: backward(kind) for kind in kinds},
        final_norm=final_norm, merge=merge, mtp_head=mtp_head,
        merge_backward=merge_backward, main_head=main_head,
        embed_backward=embed_backward)


def first_step(flat0, ids, cfg, *, lr, t=1, precision="f32", fault=None):
    """The reference's first training step from `flat0` (stored types) on
    `ids` [B, T]: `{"loss", "main_loss", "mtp_loss", "grad_norms",
    "change_norms", "expert_tokens", "mtp_token_loss"}` (the last each
    position's term of the MTP loss, [B, T]) with one norm per leaf (per held
    expert for the expert stacks), the change being what Adam's first step
    (moments from zero, at learning rate `lr`, its bias corrections those
    of step count `t`) and the rounding to the stored type make of each
    leaf; `loss` = `main_loss` + lambda `mtp_loss`.

    Backpropagation by hand over the blocks, so that it fits: the forward
    pass keeps each block's input; the backward pass takes one block at a
    time, the MTP module's first (its head, its two blocks, its merge),
    then the main head with the module's cotangent of the normed state,
    then the main blocks; each gradient leaf is reduced to its two norms
    as soon as it is whole. One jitted function per kind of block and
    direction."""
    d = dims(cfg)
    fn = _step_functions(json.dumps(cfg, sort_keys=True), tuple(ids.shape),
                         lr, t, precision, fault)
    zero = jnp.zeros((), jnp.float32)

    def run(pattern, prefix, h):
        inputs, counts = [], []
        for i, kind in enumerate(pattern):
            inputs.append(h)
            h, n = fn["forward"][kind](
                h, block_weights(flat0, f"{prefix}blocks/{i}/"))
            if n is not None:
                counts.append(n)
        return h, inputs, counts

    def back(pattern, prefix, inputs, dh, both):
        for i, kind in reversed(list(enumerate(pattern))):
            dh, more = fn["backward"][kind](
                inputs.pop(), block_weights(flat0, f"{prefix}blocks/{i}/"),
                dh)
            both.update({f"{prefix}blocks/{i}{k}": v
                         for k, v in more.items()})
        return dh

    both = {}
    h, inputs, counts = run(d["pattern"], "", fn["embed"](flat0["embed"], ids))
    mtp_loss, mtp_terms, dhn, da, dhead_mtp = None, None, zero, None, zero
    if d["mtp"]:
        hn = fn["final_norm"](h, flat0["final_norm"])
        ahead = fn["embed"](flat0["embed"], jnp.roll(ids, -1, axis=1))
        merge_w = {k: flat0["mtp/" + k]
                   for k in ("embed_norm", "hidden_norm", "merge")}
        m, m_inputs, more = run(MTP_PATTERN, "mtp/",
                                fn["merge"](ahead, hn, merge_w))
        counts += more
        mtp_loss, mtp_terms, dm, n_norms, dhead_mtp = fn["mtp_head"](
            m, flat0["mtp/final_norm"], flat0["head"], ids)
        both.update(n_norms)
        del m
        dm = back(MTP_PATTERN, "mtp/", m_inputs, dm, both)
        da, dhn, more = fn["merge_backward"](ahead, hn, merge_w, dm)
        both.update(more)
        del ahead, hn, dm
    main, dh, more = fn["main_head"](h, flat0["final_norm"], flat0["head"],
                                     ids, dhn, dhead_mtp)
    both.update(more)
    del h, dhead_mtp
    dh = back(d["pattern"], "", inputs, dh, both)
    both.update(fn["embed_backward"](flat0["embed"], ids, dh, da))
    both = jax.device_get(both)
    main = float(main)
    mtp_loss = None if mtp_loss is None else float(mtp_loss)
    return {"loss": main + (d["lam"] * mtp_loss if d["mtp"] else 0.0),
            "main_loss": main, "mtp_loss": mtp_loss,
            "mtp_token_loss": (None if mtp_terms is None
                               else np.asarray(jax.device_get(mtp_terms))),
            "grad_norms": expand({k: v[0] for k, v in both.items()}),
            "change_norms": expand({k: v[1] for k, v in both.items()}),
            "expert_tokens": np.asarray(jax.device_get(counts)).tolist()}
