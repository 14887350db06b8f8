"""Plain reference for the window-and-full-attention, sparse-expert decoder
with the keys of `JetBrains/Mellum2-12B-A2.5B-Instruct`'s `config.json`
(`model_type` `mellum`): forward, the next-token loss, gradients and the
first Adam step.

Written from the equations in `jax.numpy`, float32, every matmul at
`highest` precision (and the step's pieces traced under
`jax.default_matmul_precision("highest")`); it imports nothing of the
program, only plain helpers of the other references. It also owns what the
benchmark feeds both sides: the weights (`make_flat_params`) and the
batches (`make_batches`, `nemotron_h`'s: Zipf(1.0) ids over the vocabulary
held, the ranks permuted from the seed), each one jitted call from the
seed.

Pre-norm residual layers, `h <- h + Attn(RMSNorm(h))`, then `h <- h +
MoE(RMSNorm(h))`, eps `rms_norm_eps`; `h_0 = W_emb[ids]`; logits =
RMSNorm(h) W_head (untied); loss = mean over t < T-1 of CE(logits_t,
x_{t+1}).

Attention (every layer; `u` the normed input): H query heads on H_kv
key/value heads of D, query head i on KV head i // (H / H_kv); q = u W_q,
k = u W_k, v = u W_v, no bias, no per-head norm;
    q, k <- RoPE at positions 0..T-1, half-split: the pair (x_i, x_{i+D/2})
        turned by pos * f_i, cos and sin times the section's attention
        factor a (so a score carries a^2)
    o_h = softmax(q_h . k_h / sqrt(D), over the visible keys) v_h;
    out = concat_h(o_h) W_o.
  `layer_types` "sliding_attention": key j is visible to query i iff
    i - W < j <= i (W = `sliding_window`, the query's own key among them),
    f_i = theta^(-2i/D) (`rope_parameters.sliding_attention`, default).
  "full_attention": j <= i; f_i by YaRN (`rope_parameters.full_attention`):
    with b_i = theta^(2i/D), s = `factor`, L = `original_max_position_
    embeddings`, low = floor(D ln(L / (beta_fast 2 pi)) / (2 ln theta)),
    high = ceil(D ln(L / (beta_slow 2 pi)) / (2 ln theta)) (18 and 35 at
    D = 128, theta = 5e5, L = 8,192, betas 32 and 1), r_i = clip((i - low)
    / (high - low), 0, 1): f_i = (1 - r_i) / b_i + r_i / (s b_i);
    a = `attention_factor`.
MoE (every layer): p = softmax(u W_r) over ALL the published experts, in
    float32; the top k of p; g_k = p_k / (sum of the chosen p)
    (`norm_topk_prob`; no `routed_scaling_factor`, so 1); out = sum_k g_k
    Down_e(silu(Gate_e u) * Up_e u); no shared expert. Of the routed sum
    only the terms of the experts held on this chip are computed (a dense
    loop over them under a mask); the others' are left out, as the
    program leaves them out.

Departures from the published model are the configuration file's
(`departures`, `assumed`): no multi-token-prediction head (`config.json`
names none), seeded initialisation, no dropout.

The parameters' names and layout are the program's tree (`joyai_flash.
nest`): blocks `blocks/<i>/...` by the pattern, `W` for a window layer's
attention and `*` for a full one's, each followed by `E`. An expert's `w1`
is [Gate ; Up] as one [2 F, E] matrix, `w2` is Down transposed, [F, E].

Parameters are *stored* as the configuration states (bfloat16 matrices;
float32 norm weights and router) and computed with in float32 from those
values. `precision="fp8"` is the control: the two operands of every matmul
that the program runs in bfloat16 are rounded to an e4m3 float8 on the
forward pass (per-tensor scale), gradients straight through.

It has to fit beside nothing else on one chip at T = 16,384: `first_step`
backpropagates block by block, recomputing each block's forward; attention
goes a few query rows at a time over every key under an explicit mask, the
held experts one at a time, the head a few rows at a time; and a gradient
leaf is reduced to its norm, and to the norm of the change Adam's first
step makes from it, as soon as it is whole.

The planted faults (`fault=`) are for the readings that the limits are set
from: "nowindow" lets a window layer see every earlier key, "window_off"
lets it see one key more (i - j <= W), "yarn" gives the full layer the
default rotary (no ramp, no attention factor), "interleave" pairs (x_2i,
x_2i+1) in place of the half-split pairs, "sigmoid" scores the router with
a sigmoid in place of the softmax.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.joyai_flash import (  # plain helpers, no model
    _gated, block_weights, head_loss, nest)
from benchmark.reference.nemotron_h import (  # plain helpers, no model
    ADAM_B1, ADAM_B2, ADAM_EPS, _draw, _mm, _rms, expand, leaf_names,
    leaf_norm, make_batches, per_expert, per_expert_leaf, round_to)

WINDOW, FULL, EXPERTS = "W", "*", "E"
LAYER_LETTER = {"sliding_attention": WINDOW, "full_attention": FULL}
SECTION = {WINDOW: "sliding_attention", FULL: "full_attention"}
FAULTS = ("nowindow", "window_off", "yarn", "interleave", "sigmoid")


def dims(cfg) -> dict:
    """The sizes by short names. `num_experts` in the file counts the
    experts held here; the router's width is the published count."""
    pub = cfg.get("published", {})
    dep = cfg.get("deployment", {})
    layers = int(cfg["num_hidden_layers"])
    types = cfg["layer_types"][:layers]
    if len(types) != layers or any(t not in LAYER_LETTER for t in types):
        raise ValueError(f"layer_types {types!r} for {layers} layers")
    if any(t != "sparse" for t in cfg["mlp_layer_types"][:layers]):
        raise ValueError("only sparse-expert layers are built")
    if not cfg.get("norm_topk_prob", False):
        raise ValueError("the top k's weights are renormalised here "
                         "(norm_topk_prob)")
    return dict(
        E=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        pattern="".join(LAYER_LETTER[t] + EXPERTS for t in types),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]), D=int(cfg["head_dim"]),
        window=int(cfg["sliding_window"]), rope=cfg["rope_parameters"],
        held=int(cfg["num_experts"]),
        experts=int(pub.get("num_experts", cfg["num_experts"])),
        first=int(dep.get("first_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]),
        F=int(cfg["moe_intermediate_size"]), eps=float(cfg["rms_norm_eps"]),
        depth=int(pub.get("num_hidden_layers", layers)))


def block_shapes(d, kind):
    """name -> (shape, how it is drawn) of one block's leaves."""
    E, D = d["E"], d["D"]
    out = {"norm": ((E,), "one")}
    if kind in (WINDOW, FULL):
        out.update({"wq": ((E, d["heads"] * D), "matrix"),
                    "wk": ((E, d["kv_heads"] * D), "matrix"),
                    "wv": ((E, d["kv_heads"] * D), "matrix"),
                    "wo": ((d["heads"] * D, E), "residual_out")})
    elif kind == EXPERTS:
        out.update({"router": ((E, d["experts"]), "router"),
                    "w1": ((d["held"], 2 * d["F"], E), "matrix"),
                    "w2": ((d["held"], d["F"], E), "residual_out")})
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return out


def make_flat_params(key, cfg):
    """All parameters as a flat dict `path -> array` in their stored types
    (`nemotron_h._draw`: N(0, 0.02) matrices in bfloat16, those that write
    into the residual stream scaled by 1/sqrt(published depth), the router
    the same in float32, norm weights 1; the embedding N(0, 1), bfloat16):
    `embed`, `head`, `final_norm`, `blocks/<i>/<name>`. The same for every
    caller.

    The embedding is drawn at unit scale (`nn.Embedding`'s own default) and
    not at 0.02: the blocks read the residual stream through RMSNorm, and
    beside a 0.02 embedding the first window layer's output, an average
    over up to 1,024 earlier positions, is most of that stream, so every
    later router sees nearly one input at every position and sends every
    token to the same experts (a layer's held share 0.04 to 1.87 of a
    quarter over eight seeds at T = 1,024; PERF.md section 6). At unit
    scale a position's own token is most of the stream and the routers
    route by content, as a working router does."""
    d = dims(cfg)
    E = d["E"]
    flat = {"embed": jax.random.normal(jax.random.fold_in(key, 1),
                                       (d["V"], E)).astype(jnp.bfloat16),
            "head": _draw(jax.random.fold_in(key, 2), (E, d["V"]),
                          "matrix", d),
            "final_norm": jnp.ones((E,), jnp.float32)}
    kblocks = jax.random.fold_in(key, 3)
    for i, kind in enumerate(d["pattern"]):
        kb = jax.random.fold_in(kblocks, i)
        for j, (name, (shape, how)) in enumerate(
                sorted(block_shapes(d, kind).items())):
            flat[f"blocks/{i}/{name}"] = _draw(jax.random.fold_in(kb, j),
                                               shape, how, d)
    return flat


# -- the model ----------------------------------------------------------

def frequencies(D, params, fault=None):
    """(f_i [D/2], attention factor a) of one `rope_parameters` section:
    the default's theta^(-2i/D) and 1, or YaRN's blend (the docstring)."""
    theta = float(params["rope_theta"])
    base = theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    if params.get("rope_type", "default") == "default" or fault == "yarn":
        return 1.0 / base, 1.0
    assert params["rope_type"] == "yarn", params
    s, L = float(params["factor"]), float(
        params["original_max_position_embeddings"])
    corr = lambda beta: D * math.log(L / (beta * 2 * math.pi)) / (
        2 * math.log(theta))
    low = max(math.floor(corr(float(params["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(params["beta_slow"]))), D - 1)
    r = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - low)
                 / (high - low), 0.0, 1.0)
    return (1.0 - r) / base + r / (s * base), float(
        params["attention_factor"])


def rope(x, f, a, interleave=False):
    """x [b, t, h, D] turned at positions 0..t-1: the half-split pair
    (x_i, x_{i+D/2}) (or, `interleave`, the pair (x_2i, x_2i+1)) by the
    angle pos * f_i, cos and sin times a."""
    b, t, h, D = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * f[None, :]
    cos, sin = (a * g(ang)[None, :, None, :] for g in (jnp.cos, jnp.sin))
    if interleave:
        pairs = x.reshape(b, t, h, D // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(kind, u, w, d, precision, rows, fault):
    b, t, _ = u.shape
    H, Hkv, D = d["heads"], d["kv_heads"], d["D"]
    q = _mm("bte,ef->btf", u, w["wq"], precision).reshape(b, t, H, D)
    k = _mm("bte,ef->btf", u, w["wk"], precision).reshape(b, t, Hkv, D)
    v = _mm("bte,ef->btf", u, w["wv"], precision).reshape(b, t, Hkv, D)
    f, a = frequencies(D, d["rope"][SECTION[kind]],
                       fault if kind == FULL else None)
    q, k = (rope(x, f, a, fault == "interleave") for x in (q, k))
    # query head i reads KV head i // (H / H_kv)
    k, v = (jnp.repeat(x, H // Hkv, axis=2) for x in (k, v))
    window = None
    if kind == WINDOW and fault != "nowindow":
        window = d["window"] + (fault == "window_off")
    rows = min(rows, t)
    pad = -t % rows
    qp = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)]) if pad else q
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def some_rows(args):
        q_rows, first = args                               # [b, rows, H, D]
        s = _mm("bqhd,bkhd->bhqk", q_rows, k, precision) / math.sqrt(D)
        q_pos = (first + jnp.arange(rows))[:, None]
        seen = key_pos[None, :] <= q_pos
        if window is not None:
            seen = seen & (key_pos[None, :] > q_pos - window)
        # a padded query row past the window's reach sees no key: a finite
        # floor keeps its (sliced away) softmax out of NaN
        p = jax.nn.softmax(jnp.where(seen, s, jnp.finfo(jnp.float32).min),
                           axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, precision)

    n = (t + pad) // rows
    ctx = jax.lax.map(some_rows, (
        jnp.moveaxis(qp.reshape((b, n, rows) + q.shape[2:]), 1, 0),
        jnp.arange(n) * rows))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, n * rows, H * D)[:, :t]
    return _mm("btf,fe->bte", ctx, w["wo"], precision)


def experts(u, w, d, precision, fault):
    """(output, how many assignments each held expert received)."""
    logits = jnp.einsum("bte,en->btn", u, w["router"], precision="highest")
    p = (jax.nn.sigmoid(logits) if fault == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    chosen, idx = jax.lax.top_k(p, d["top_k"])
    gates = chosen / jnp.sum(chosen, -1, keepdims=True)

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
    return out, counts


def block(kind, h, w, d, precision="f32", fault=None, attn_rows=512):
    """One pre-norm residual block of kind `kind` over h [B, T, E] with its
    weights `w` (name -> float32 array): (h + Mixer(RMSNorm(h)), the held
    experts' assignment counts or None)."""
    u = _rms(h, w["norm"], d["eps"])
    if kind == EXPERTS:
        out, n = experts(u, w, d, precision, fault)
        return h + out, n
    return h + attention(kind, u, w, d, precision, attn_rows, fault), None


def losses(flat, ids, d, precision="f32", fault=None, attn_rows=512):
    """The whole model in one expression: (L, expert tokens [n_expert_
    blocks, held]); `flat` in float32. What `first_step` computes block by
    block."""
    b, t = ids.shape
    h = flat["embed"][ids]
    counts = []
    for i, kind in enumerate(d["pattern"]):
        h, n = block(kind, h, block_weights(flat, f"blocks/{i}/"), d,
                     precision, fault, attn_rows)
        if n is not None:
            counts.append(n)
    hn = _rms(h, flat["final_norm"], d["eps"])
    loss = head_loss(hn, flat["head"], ids, 1, d, precision) / (b * (t - 1))
    return loss, jnp.stack(counts)


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
    def head(h, w_n, out, ids):
        """L and the gradients at the stack's end."""
        def f(h, n, out):
            hn = _rms(h, n, d["eps"])
            return head_loss(hn, out, ids, 1, d, precision,
                             head_rows) / (B * (T - 1))
        loss, (dh, dn, dhead) = jax.value_and_grad(f, argnums=(0, 1, 2))(
            h, w_n.astype(jnp.float32), out.astype(jnp.float32))
        return loss, dh, norms("", {"final_norm": w_n, "head": out},
                               {"final_norm": dn, "head": dhead})

    @jax.jit
    def embed_backward(table, ids, dh):
        g = jnp.zeros(table.shape, jnp.float32).at[ids].add(dh)
        return norms("", {"embed": table}, {"embed": g})

    kinds = set(d["pattern"])
    return dict(
        embed=jax.jit(lambda table, ids: table.astype(jnp.float32)[ids]),
        forward={kind: jax.jit(apply(kind)) for kind in kinds},
        backward={kind: backward(kind) for kind in kinds},
        head=head, embed_backward=embed_backward)


def first_step(flat0, ids, cfg, *, lr, t=1, precision="f32", fault=None):
    """The reference's first training step from `flat0` (stored types) on
    `ids` [B, T]: `{"loss", "grad_norms", "change_norms",
    "expert_tokens"}` with one norm per leaf (per held expert for the
    expert stacks), the change being what Adam's first step (moments from
    zero, at learning rate `lr`, its bias corrections those of step count
    `t`) and the rounding to the stored type make of each leaf.

    Backpropagation by hand over the blocks, so that it fits: the forward
    pass keeps each block's input; the backward pass takes the head, then
    one block at a time; each gradient leaf is reduced to its two norms as
    soon as it is whole. One jitted function per kind of block and
    direction."""
    d = dims(cfg)
    fn = _step_functions(json.dumps(cfg, sort_keys=True), tuple(ids.shape),
                         lr, t, precision, fault)
    with jax.default_matmul_precision("highest"):
        inputs, counts = [], []
        h = fn["embed"](flat0["embed"], ids)
        for i, kind in enumerate(d["pattern"]):
            inputs.append(h)
            h, n = fn["forward"][kind](h, block_weights(flat0,
                                                        f"blocks/{i}/"))
            if n is not None:
                counts.append(n)
        loss, dh, both = fn["head"](h, flat0["final_norm"], flat0["head"],
                                    ids)
        del h
        for i, kind in reversed(list(enumerate(d["pattern"]))):
            dh, more = fn["backward"][kind](
                inputs.pop(), block_weights(flat0, f"blocks/{i}/"), dh)
            both.update({f"blocks/{i}{k}": v for k, v in more.items()})
        both.update(fn["embed_backward"](flat0["embed"], ids, dh))
    both = jax.device_get(both)
    return {"loss": float(loss),
            "grad_norms": expand({k: v[0] for k, v in both.items()}),
            "change_norms": expand({k: v[1] for k, v in both.items()}),
            "expert_tokens": np.asarray(jax.device_get(counts)).tolist()}
