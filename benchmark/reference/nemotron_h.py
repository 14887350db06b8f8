"""Plain reference for the `nemotron_h` backbone (NVIDIA Nemotron-H family;
the language model of `nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16`'s
`config.json`): forward, next-token loss, gradients and the first Adam
step.

Written from the equations in `jax.numpy`, float32, matmuls at `highest`
precision; it imports nothing of the program. It also owns what the
benchmark feeds both sides: the weights (`make_flat_params`) and the
batches (`make_batches`), each one jitted call from the seed.

    h_0 = W_emb[ids];   h <- h + Mixer_c(RMSNorm(h; w, eps))  per letter c
    logits = RMSNorm(h) . W_head  (untied);  loss = mean next-token CE

`M`, Mamba-2 (d_inner = heads x head_dim, G groups, state N):
    [z | xBC | dt] = u W_in;  xBC = silu(conv1d_causal_depthwise_K(xBC) + b)
    -> x, B, C;  dt = softplus(dt + dt_bias);  A = -exp(A_log);  per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,  y_t = S_t C_t + D x_t,
    S_0 = 0, head i on group i // (H/G);  out = W_out GroupRMSNorm(y silu(z))
    (gate first, then the norm over groups of d_inner / G, learned weight).
  Here the recurrence is what it says: a `lax.scan` over the time steps.
`E`, experts: s = sigmoid(u W_r) over ALL the published experts; the top
    k of s; g_k = scale s_k / (sum of the chosen s + 1e-20);
    out = sum_k g_k W2_e relu(W1_e u)^2 + V2 relu(V1 u)^2. Of the routed
    sum only the terms of the experts held on this chip are computed (a
    dense loop over them under a mask); the others' are left out, as the
    program leaves them out. `e_score_correction_bias` is zero.
`*`, attention: H query heads on H_kv key/value heads (query head i on
    KV head i // (H/H_kv)), causal softmax(q k^T / sqrt(d)) v, no bias,
    no rotary embedding.

Departures from the published model are the configuration file's
(`departures`, `assumed`): the denoising tower and block diffusion are
not built; no dropout; seeded initialisation.

Parameters are *stored* as the configuration states (bfloat16 matrices;
float32 norm weights, router, A_log, dt_bias, D, conv) and computed with
in float32 from those values. `precision="fp8"` is the control: the two
operands of every matmul that the program runs in bfloat16 are rounded to
an e4m3 float8 on the forward pass (per-tensor scale), gradients straight
through.

It has to fit beside nothing else on one chip at T = 8,192, where the
float32 parameters and their gradients alone are 6.9 GB: `first_step`
backpropagates block by block, recomputing each block's forward; the
recurrence is checkpointed every `chunk_size` steps, attention goes a few
query rows at a time, the held experts one at a time; and a gradient leaf
is reduced to its norm, and to the norm of the change Adam's first step
makes from it, as soon as it exists (Adam's moments after step 1 are
functions of the gradient alone, so none is held).

The planted faults (`fault=`) are for the readings that the limits are
set from: "expert" leaves one held expert's routed term out, "state"
does not carry the state across chunk boundaries, "half" leaves the
second half of every sequence out.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
FAULTS = ("expert", "state", "half")
#: which held expert the "expert" fault leaves out
FAULT_EXPERT = 3


def dims(cfg) -> dict:
    """The sizes by short names. `n_routed_experts` in the file counts the
    experts held here; the router's width is the published count."""
    pub = cfg.get("published", {})
    dep = cfg.get("deployment", {})
    d = dict(
        E=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        pattern=cfg["hybrid_override_pattern"],
        H=int(cfg["mamba_num_heads"]), P=int(cfg["mamba_head_dim"]),
        N=int(cfg["ssm_state_size"]), G=int(cfg["n_groups"]),
        K=int(cfg["conv_kernel"]), chunk=int(cfg["chunk_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]), D=int(cfg["head_dim"]),
        held=int(cfg["n_routed_experts"]),
        experts=int(pub.get("n_routed_experts", cfg["n_routed_experts"])),
        first=int(dep.get("first_expert", 0)),
        top_k=int(cfg["num_experts_per_tok"]),
        F=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["moe_shared_expert_intermediate_size"]),
        scale=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["norm_eps"]),
        depth=int(pub.get("num_hidden_layers", cfg["num_hidden_layers"])),
        dt_min=float(cfg["time_step_min"]), dt_max=float(cfg["time_step_max"]),
        dt_floor=float(cfg["time_step_floor"]))
    d["d_inner"] = d["H"] * d["P"]
    d["conv_dim"] = d["d_inner"] + 2 * d["G"] * d["N"]
    if len(d["pattern"]) != int(cfg["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    return d


def block_shapes(d, kind):
    """name -> (shape, how it is drawn) of one block's leaves."""
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
    elif kind == EXPERTS:
        out.update({
            "router": ((E, d["experts"]), "router"),
            "w1": ((d["held"], d["F"], E), "matrix"),
            "w2": ((d["held"], d["F"], E), "residual_out"),
            "shared_w1": ((E, d["Fs"]), "matrix"),
            "shared_w2": ((d["Fs"], E), "residual_out")})
    elif kind == ATTENTION:
        q, kv = d["heads"] * d["D"], d["kv_heads"] * d["D"]
        out.update({"wq": ((E, q), "matrix"), "wk": ((E, kv), "matrix"),
                    "wv": ((E, kv), "matrix"),
                    "wo": ((q, E), "residual_out")})
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return out


def _draw(key, shape, how, d):
    """The assumed initialisation (the configuration file's `assumed`):
    N(0, 0.02) matrices in bfloat16, those that write into the residual
    stream scaled by 1/sqrt(published depth); the router the same in
    float32; conv weight and bias U(+-1/sqrt(K)); A_log = log U(1, 16);
    dt_bias the inverse softplus of a log-uniform step in [dt_min,
    dt_max] floored at dt_floor; D and norm weights 1."""
    f32 = jnp.float32
    if how in ("matrix", "residual_out", "router"):
        std = 0.02 / (math.sqrt(d["depth"]) if how == "residual_out" else 1.0)
        w = std * jax.random.normal(key, shape, f32)
        return w if how == "router" else w.astype(jnp.bfloat16)
    if how == "conv":
        bound = 1.0 / math.sqrt(d["K"])
        return jax.random.uniform(key, shape, f32, -bound, bound)
    if how == "dt_bias":
        lo, hi = math.log(d["dt_min"]), math.log(d["dt_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, f32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, d["dt_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    if how == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    return jnp.ones(shape, f32)


def make_flat_params(key, cfg):
    """All parameters as a flat dict `path -> array` in their stored
    types: `embed`, `blocks/<i>/<name>`, `final_norm`, `head`. The same
    for every caller."""
    d = dims(cfg)
    flat = {"embed": _draw(jax.random.fold_in(key, 1), (d["V"], d["E"]),
                           "matrix", d),
            "head": _draw(jax.random.fold_in(key, 2), (d["E"], d["V"]),
                          "matrix", d),
            "final_norm": jnp.ones((d["E"],), jnp.float32)}
    for i, kind in enumerate(d["pattern"]):
        kb = jax.random.fold_in(jax.random.fold_in(key, 3), i)
        for j, (name, (shape, how)) in enumerate(
                sorted(block_shapes(d, kind).items())):
            flat[f"blocks/{i}/{name}"] = _draw(jax.random.fold_in(kb, j),
                                               shape, how, d)
    return flat


def nest(flat):
    """The flat dict as the tree a model file would hold: `blocks` a list
    of per-block dicts."""
    n = 1 + max(int(k.split("/")[1]) for k in flat if k.startswith("blocks/"))
    tree = {"blocks": [dict() for _ in range(n)]}
    for path, v in flat.items():
        parts = path.split("/")
        if parts[0] == "blocks":
            tree["blocks"][int(parts[1])][parts[2]] = v
        else:
            tree[path] = v
    return tree


def leaf_names(tree, prefix=""):
    """`path` of every leaf of a nested tree, in `jax.tree_util` leaf
    order (dict keys sorted, lists by index)."""
    if isinstance(tree, dict):
        return [n for k in sorted(tree)
                for n in leaf_names(tree[k], f"{prefix}{k}/")]
    if isinstance(tree, (list, tuple)):
        return [n for i, v in enumerate(tree)
                for n in leaf_names(v, f"{prefix}{i}/")]
    return [prefix[:-1]]


def per_expert(path: str) -> bool:
    """Whether a leaf is a stack of the held experts' matrices, each of
    which is compared as a leaf of its own."""
    return path.endswith("/w1") or path.endswith("/w2")


def per_expert_leaf(name: str) -> bool:
    """Whether an `expand`ed name is one held expert's matrix
    (`blocks/<i>/w1/<e>`)."""
    return per_expert(name.rsplit("/", 1)[0])


def leaf_norm(path, v):
    """The leaf's norm, or one norm per held expert for a stack."""
    axes = tuple(range(1, v.ndim)) if per_expert(path) else None
    return jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)), axis=axes))


def expand(norms):
    """`blocks/1/w1 -> [held]` becomes `blocks/1/w1/<e> -> float`."""
    out = {}
    for k, v in norms.items():
        v = np.asarray(v)
        if v.ndim:
            for e, x in enumerate(v):
                out[f"{k}/{e}"] = float(x)
        else:
            out[k] = float(v)
    return out


def make_batches(key, cfg, count, batch, seq_len):
    """`count` batches of token ids [count, B, T], one document a
    sequence: ids drawn Zipf(s = 1.0) over the vocabulary held, the ranks
    permuted from the seed (which ids are frequent, and so which experts
    the bulk of the tokens are routed to, differs by seed and not by
    batch)."""
    V = int(cfg["vocab_size"])
    k1, k2 = jax.random.split(jax.random.fold_in(key, 7))
    weight = 1.0 / jnp.arange(1, V + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(weight) / jnp.sum(weight)
    u = jax.random.uniform(k1, (count, batch, seq_len))
    rank = jnp.minimum(jnp.searchsorted(cdf, u), V - 1)
    ids = jax.random.permutation(k2, V)[rank]
    return {"input_ids": ids.astype(jnp.int32)}


# -- the model ----------------------------------------------------------

def round_to(x, dtype):
    """float32 values rounded to what `dtype` can hold, still float32
    (`lax.reduce_precision`: under jit XLA removes an `astype` pair)."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _fp8(x):
    """Round to an e4m3 float8 under a per-tensor scale that puts the
    largest value at 224; gradient straight through."""
    s = 224.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = jax.lax.reduce_precision(x * s, exponent_bits=4, mantissa_bits=3) / s
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision="highest")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def recurrence(x, dt, A, Bm, Cm, chunk, carry=True):
    """y [b, t, h, p] of S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,
    y_t = S_t C_t, one time step after another. x [b,t,h,p], dt [b,t,h],
    A [h], Bm, Cm [b,t,g,n]. The steps are taken `chunk` at a time under
    `jax.checkpoint` (memory only). `carry=False` is the planted fault:
    every chunk starts from a zero state."""
    b, t, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    r = h // g
    pad = -t % chunk
    if pad:         # dt = 0: the state stays, nothing is added
        x, dt, Bm, Cm = (jnp.pad(v, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (v.ndim - 2))
                         for v in (x, dt, Bm, Cm))
    c = (t + pad) // chunk

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        B_t, C_t = (jnp.repeat(v, r, axis=1) for v in (B_t, C_t))  # [b,h,n]
        S = (S * jnp.exp(dt_t * A)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.sum(S * C_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def steps(S, inp):
        if not carry:
            S = jnp.zeros_like(S)
        return jax.lax.scan(step, S, inp)

    time_major = lambda v: jnp.moveaxis(v, 1, 0).reshape(
        (c, chunk) + v.shape[:1] + v.shape[2:])
    _, y = jax.lax.scan(steps, jnp.zeros((b, h, p, n), jnp.float32),
                        tuple(time_major(v) for v in (x, dt, Bm, Cm)))
    y = jnp.moveaxis(y.reshape((c * chunk, b, h, p)), 0, 1)
    return y[:, :t] if pad else y


def mamba(u, w, d, precision, fault):
    b, t, _ = u.shape
    H, P, G, N, K = d["H"], d["P"], d["G"], d["N"], d["K"]
    zxbcdt = _mm("bte,ef->btf", u, w["in_proj"], precision)
    z, xBC, dt = jnp.split(zxbcdt, [d["d_inner"],
                                    d["d_inner"] + d["conv_dim"]], axis=-1)
    padded = jnp.pad(xBC, [(0, 0), (K - 1, 0), (0, 0)])
    conv = sum(padded[:, k:k + t] * w["conv_w"][k] for k in range(K))
    xBC = jax.nn.silu(conv + w["conv_b"])
    x, Bm, Cm = jnp.split(xBC, [d["d_inner"], d["d_inner"] + G * N], axis=-1)
    x = x.reshape(b, t, H, P)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(w["A_log"]), Bm.reshape(b, t, G, N),
                   Cm.reshape(b, t, G, N), d["chunk"],
                   carry=fault != "state")
    y = (y + w["D"][:, None] * x).reshape(b, t, d["d_inner"])
    y = y * jax.nn.silu(z)
    y = _rms(y.reshape(b, t, G, -1), 1.0, d["eps"]).reshape(y.shape)
    return _mm("btf,fe->bte", y * w["gate_norm"], w["out_proj"], precision)


def _relu2(u, w1, w2, precision, w1_eq="bte,ef->btf"):
    h = jnp.square(jax.nn.relu(_mm(w1_eq, u, w1, precision)))
    return _mm("btf,fe->bte", h, w2, precision)


def experts(u, w, d, precision, fault):
    """(output, how many assignments each held expert received)."""
    s = jax.nn.sigmoid(jnp.einsum("bte,en->btn", u, w["router"],
                                  precision="highest"))
    chosen, idx = jax.lax.top_k(s, d["top_k"])
    gates = d["scale"] * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)

    @jax.checkpoint
    def one(acc, inp):
        w1, w2, e = inp
        gate = jnp.sum(jnp.where(idx == d["first"] + e, gates, 0.0), -1)
        if fault == "expert":
            gate = jnp.where(e == FAULT_EXPERT, 0.0, gate)
        # an expert's W1 is stored [F, E], as a model file holds it
        return acc + gate[..., None] * _relu2(u, w1, w2, precision,
                                              "bte,fe->btf"), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (w["w1"], w["w2"], jnp.arange(d["held"])))
    counts = jnp.sum(idx[..., None] == d["first"] + jnp.arange(d["held"]),
                     axis=(0, 1, 2))
    return routed + _relu2(u, w["shared_w1"], w["shared_w2"],
                           precision), counts


def attention(u, w, d, precision, rows):
    b, t, _ = u.shape
    H, Hkv, D = d["heads"], d["kv_heads"], d["D"]
    q = _mm("bte,ef->btf", u, w["wq"], precision).reshape(b, t, Hkv,
                                                          H // Hkv, D)
    k = _mm("bte,ef->btf", u, w["wk"], precision).reshape(b, t, Hkv, D)
    v = _mm("bte,ef->btf", u, w["wv"], precision).reshape(b, t, Hkv, D)
    rows = min(rows, t)
    pad = -t % rows
    qp = jnp.pad(q, [(0, 0), (0, pad)] + [(0, 0)] * 3) if pad else q
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def some_rows(args):
        q_rows, first = args                       # [b, rows, Hkv, R, D]
        s = _mm("bqgrd,bkgd->bgrqk", q_rows, k, precision) / math.sqrt(D)
        seen = key_pos[None, :] <= (first + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("bgrqk,bkgd->bqgrd", p, v, precision)

    n = (t + pad) // rows
    ctx = jax.lax.map(some_rows, (
        jnp.moveaxis(qp.reshape((b, n, rows) + q.shape[2:]), 1, 0),
        jnp.arange(n) * rows))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, n * rows, H * D)[:, :t]
    return _mm("btf,fe->bte", ctx, w["wo"], precision)


def block(kind, h, w, d, precision="f32", fault=None, attn_rows=512):
    """One pre-norm residual block of kind `kind` over h [B, T, E] with
    its weights `w` (name -> float32 array): (h + Mixer(RMSNorm(h)),
    the held experts' assignment counts or None)."""
    u = _rms(h, w["norm"], d["eps"])
    if kind == MAMBA:
        return h + mamba(u, w, d, precision, fault), None
    if kind == EXPERTS:
        out, n = experts(u, w, d, precision, fault)
        return h + out, n
    return h + attention(u, w, d, precision, attn_rows), None


def head_loss(h, final_norm, head, ids, d, precision="f32"):
    """Sum of the next-token cross entropy over the predicted positions
    (every position but the last of each sequence)."""
    logits = _mm("bte,ev->btv", _rms(h, final_norm, d["eps"]), head,
                 precision)
    lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logits[:, :-1], ids[:, 1:, None],
                                 axis=-1)[..., 0]
    return jnp.sum(lse - picked)


def block_weights(flat, i):
    return {k.split("/")[2]: v for k, v in flat.items()
            if k.startswith(f"blocks/{i}/")}


def loss_sum(flat, ids, d, precision="f32", fault=None, attn_rows=512):
    """The whole model in one expression: (sum of the next-token cross
    entropy, expert tokens [n_expert_blocks, held]); `flat` in float32.
    What `first_step` computes block by block."""
    h = flat["embed"][ids]
    counts = []
    for i, kind in enumerate(d["pattern"]):
        h, n = block(kind, h, block_weights(flat, i), d, precision, fault,
                     attn_rows)
        if n is not None:
            counts.append(n)
    return (head_loss(h, flat["final_norm"], flat["head"], ids, d, precision),
            jnp.stack(counts) if counts else None)


@functools.lru_cache(maxsize=8)
def _step_functions(cfg_json, shape, lr, t, precision, fault):
    """The jitted pieces of `first_step` for one configuration, batch
    shape and variant: (embed, forward by kind, backward by kind, head,
    embed's backward). Kept, so that a tool that walks many seeds in one
    process compiles them once."""
    cfg = json.loads(cfg_json)
    d = dims(cfg)
    f32 = lambda w: {k: v.astype(jnp.float32) for k, v in w.items()}
    count = shape[0] * (shape[1] - 1)
    rows = int(cfg.get("reference", {}).get("attn_rows", 512))
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
    def head(h, final_norm, out, ids):
        tail = {"final_norm": final_norm, "head": out}
        total, (dh, dw) = jax.value_and_grad(
            lambda h, w: head_loss(h, w["final_norm"], w["head"], ids, d,
                                   precision) / count, argnums=(0, 1))(
            h, f32(tail))
        return total, dh, norms("", tail, dw)

    @jax.jit
    def embed_backward(table, ids, dh):
        g = jnp.zeros(table.shape, jnp.float32).at[ids].add(dh)
        return norms("", {"embed": table}, {"embed": g})

    kinds = set(d["pattern"])
    return (jax.jit(lambda table, ids: table.astype(jnp.float32)[ids]),
            {kind: jax.jit(apply(kind)) for kind in kinds},
            {kind: backward(kind) for kind in kinds}, head, embed_backward)


def first_step(flat0, ids, cfg, *, lr, t=1, precision="f32", fault=None):
    """The reference's first training step from `flat0` (stored types) on
    `ids` [B, T]: `{"loss", "grad_norms", "change_norms",
    "expert_tokens"}` with one norm per leaf (per held expert for the
    expert stacks), the change being what Adam's first step (moments from
    zero, at learning rate `lr`, its bias corrections those of step count
    `t`) and the rounding to the stored type make of each leaf.

    Backpropagation by hand over the blocks, so that it fits: the forward
    pass keeps each block's input; the backward pass takes one block at a
    time (its float32 weights, its recomputed forward, its gradients),
    reduces each gradient leaf to its two norms at once and hands on only
    the gradient of the block's input. One jitted function per kind of
    block and direction."""
    pattern = cfg["hybrid_override_pattern"]
    if fault == "half":
        ids = ids[:, :ids.shape[1] // 2]
    embed, forward, backward, head, embed_backward = _step_functions(
        json.dumps(cfg, sort_keys=True), tuple(ids.shape), lr, t, precision,
        fault)
    inputs, counts = [], []
    h = embed(flat0["embed"], ids)
    for i, kind in enumerate(pattern):
        inputs.append(h)
        h, n = forward[kind](h, block_weights(flat0, i))
        if n is not None:
            counts.append(n)
    loss, dh, both = head(h, flat0["final_norm"], flat0["head"], ids)
    del h
    for i, kind in reversed(list(enumerate(pattern))):
        dh, more = backward[kind](inputs.pop(), block_weights(flat0, i), dh)
        both.update({f"blocks/{i}{k}": v for k, v in more.items()})
    both.update(embed_backward(flat0["embed"], ids, dh))
    both = jax.device_get(both)
    return {"loss": float(loss),
            "grad_norms": expand({k: v[0] for k, v in both.items()}),
            "change_norms": expand({k: v[1] for k, v in both.items()}),
            "expert_tokens": np.asarray(jax.device_get(counts)).tolist()}
