"""Plain reference for BERT masked-LM pretraining (Devlin et al. 2018).

Written from the paper's equations in `jax.numpy`, float32, matmuls at
`highest` precision; it imports nothing of the program. It also owns what
the benchmark feeds both sides: the weights (`make_params`) and the batches
(`make_batches`), each one jitted call from the seed.

Block (post-LN, as published):  a = MHA(h);  h = LN(h + a);
m = W2 gelu(W1 h + b1) + b2;  h = LN(h + m).  Head: LN(gelu(W h + b)) E^T + c
with E the word table. Loss: mean cross entropy over the labelled positions.

Departures from the published model, both the program's: no dropout (the
published 0.1 is a training-time regulariser; a benchmark needs a
deterministic step), and GELU in its tanh form (the form of the original
`google-research/bert` code; the hub's "gelu" is the erf form, which differs
by under 1e-3).

What the configuration states about precision is kept: parameters are
*stored* in bfloat16 (the reference rounds them to bfloat16 after each
update and computes in float32 from those values) and Adam's moments are
float32. `precision="fp8"` is the control: every matmul's two operands are
rounded to an e4m3 float8 (per-tensor scale to the format's range) on the
forward pass, gradients flowing straight through.

The layers are stacked on a leading axis and scanned, each under
`jax.checkpoint`, and a step is computed a few rows at a time, so the
reference fits beside nothing else on one chip and compiles in seconds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

def layer_shapes(E, H, D, F):
    """One block's leaves: matrices (drawn from the normal), biases (zero,
    stored like the matrices) and LayerNorm gains and biases (float32)."""
    matrices = {"attn/wq": (E, H, D), "attn/wk": (E, H, D),
                "attn/wv": (E, H, D), "attn/wo": (H, D, E),
                "mlp/w1": (E, F), "mlp/w2": (F, E)}
    biases = {"attn/bq": (H, D), "attn/bk": (H, D), "attn/bv": (H, D),
              "attn/bo": (E,), "mlp/b1": (F,), "mlp/b2": (E,)}
    norms = {"ln1_g": (E,), "ln1_b": (E,), "ln2_g": (E,), "ln2_b": (E,)}
    return matrices, biases, norms


ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def dims(cfg):
    E = int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    return (E, H, E // H, int(cfg["intermediate_size"]),
            int(cfg["num_hidden_layers"]), int(cfg["vocab_size"]),
            int(cfg["max_position_embeddings"]))


def _draw(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16)


def stacked_layers(key, cfg):
    """The L blocks' weights, each kind stacked on a leading layer axis:
    matrices N(0, initializer_range) in bfloat16, biases zero, LN (1, 0)
    in float32 — the published initialisation."""
    E, H, D, F, L, _, _ = dims(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    matrices, biases, norms = layer_shapes(E, H, D, F)
    out = {name: _draw(jax.random.fold_in(key, i), (L,) + shape, std)
           for i, (name, shape) in enumerate(sorted(matrices.items()))}
    out.update({name: jnp.zeros((L,) + shape, jnp.bfloat16)
                for name, shape in biases.items()})
    out.update({name: (jnp.ones if name.endswith("_g") else jnp.zeros)(
        (L,) + shape, jnp.float32) for name, shape in norms.items()})
    return out


def make_flat_params(key, cfg):
    """All parameters as a flat dict `path -> array`, layers stacked
    (`layers/attn/wq` is [L, E, H, Dh]). The same for every caller."""
    E, H, D, F, L, V, P = dims(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    k = lambda i: jax.random.fold_in(key, 1000 + i)
    flat = {f"layers/{n}": v
            for n, v in stacked_layers(jax.random.fold_in(key, 1), cfg).items()}
    flat.update({
        "embeddings/word": _draw(k(0), (V, E), std),
        "embeddings/position": _draw(k(1), (P, E), std),
        "embeddings/token_type": _draw(
            k(2), (int(cfg.get("type_vocab_size", 2)), E), std),
        "embeddings/ln_g": jnp.ones((E,), jnp.float32),
        "embeddings/ln_b": jnp.zeros((E,), jnp.float32),
        "mlm/dense": _draw(k(3), (E, E), std),
        "mlm/dense_b": jnp.zeros((E,), jnp.bfloat16),
        "mlm/ln_g": jnp.ones((E,), jnp.float32),
        "mlm/ln_b": jnp.zeros((E,), jnp.float32),
        "mlm/bias": jnp.zeros((V,), jnp.float32),
        "pooler/w": _draw(k(4), (E, E), std),
        "pooler/b": jnp.zeros((E,), jnp.bfloat16),
    })
    return flat


def nest(flat, num_layers):
    """The flat dict as the nested tree a model file would hold: `layers`
    a list of per-layer dicts (slices of the stacked arrays)."""
    tree = {"layers": [dict() for _ in range(num_layers)]}
    for path, v in flat.items():
        parts = path.split("/")
        if parts[0] == "layers":
            for i in range(num_layers):
                node = tree["layers"][i]
                for p in parts[1:-1]:
                    node = node.setdefault(p, {})
                node[parts[-1]] = v[i]
        else:
            node = tree
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = v
    return tree


def leaf_names(tree, prefix=""):
    """`path` of every leaf of a nested tree, in `jax.tree_util` leaf order
    (dict keys sorted, lists by index); a per-layer leaf is
    `layers/<i>/attn/wq`."""
    if isinstance(tree, dict):
        return [n for k in sorted(tree)
                for n in leaf_names(tree[k], f"{prefix}{k}/")]
    if isinstance(tree, (list, tuple)):
        return [n for i, v in enumerate(tree)
                for n in leaf_names(v, f"{prefix}{i}/")]
    return [prefix[:-1]]


def make_batches(key, cfg, count, batch, seq_len, masked_share=0.15):
    """`count` MLM batches [count, B, T]: ids uniform over the vocabulary
    (every row differs), `masked_share` of positions labelled with a random
    target, the rest -100; attention mask all ones (full-length rows, the
    phase-2 pretraining shape)."""
    V = int(cfg["vocab_size"])
    k1, k2, k3 = jax.random.split(jax.random.fold_in(key, 7), 3)
    shape = (count, batch, seq_len)
    ids = jax.random.randint(k1, shape, 0, V, jnp.int32)
    tgt = jax.random.randint(k2, shape, 0, V, jnp.int32)
    pick = jax.random.uniform(k3, shape) < masked_share
    return {"input_ids": ids, "labels": jnp.where(pick, tgt, -100),
            "attention_mask": jnp.ones(shape, jnp.int32)}


# -- the model ----------------------------------------------------------

def round_to(x, dtype):
    """float32 values rounded to what `dtype` can hold, still float32.
    `lax.reduce_precision`, not an `astype` pair: under jit XLA removes a
    float32 -> bfloat16 -> float32 round trip as excess precision, and the
    rounding with it."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _fp8(x):
    """Round to an e4m3 float8 (4 exponent bits, 3 mantissa bits) under a
    per-tensor scale that puts the largest value at 224; gradient straight
    through."""
    s = 224.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = jax.lax.reduce_precision(x * s, exponent_bits=4, mantissa_bits=3) / s
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision="highest")


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def block(h, w, eps, precision, causal=False):
    """One post-LN block over h [B, T, E]; `w` one layer's dict."""
    mm = functools.partial(_mm, precision=precision)
    q = mm("bte,ehd->bthd", h, w["attn/wq"]) + w["attn/bq"]
    k = mm("bte,ehd->bthd", h, w["attn/wk"]) + w["attn/bk"]
    v = mm("bte,ehd->bthd", h, w["attn/wv"]) + w["attn/bv"]
    s = mm("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        T = h.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s,
                      -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = mm("bhqk,bkhd->bqhd", p, v)
    a = mm("bqhd,hde->bqe", ctx, w["attn/wo"]) + w["attn/bo"]
    h = _ln(h + a, w["ln1_g"], w["ln1_b"], eps)
    m = mm("btf,fe->bte", _gelu(mm("bte,ef->btf", h, w["mlp/w1"])
                                + w["mlp/b1"]), w["mlp/w2"]) + w["mlp/b2"]
    return _ln(h + m, w["ln2_g"], w["ln2_b"], eps)


def blocks(h, layers, eps, precision, causal=False):
    step = jax.checkpoint(
        lambda h, w: (block(h, w, eps, precision, causal), None))
    return jax.lax.scan(step, h, layers)[0]


def split_layers(flat):
    layers = {k[len("layers/"):]: v for k, v in flat.items()
              if k.startswith("layers/")}
    rest = {k: v for k, v in flat.items() if not k.startswith("layers/")}
    return layers, rest


def mlm_loss_sum(flat, batch, eps, precision):
    """Sum of the cross entropy over the labelled positions of `batch`
    (ids, labels [B, T]); token type 0 everywhere."""
    layers, p = split_layers(flat)
    ids, labels = batch["input_ids"], batch["labels"]
    T = ids.shape[1]
    h = (p["embeddings/word"][ids] + p["embeddings/position"][None, :T]
         + p["embeddings/token_type"][0])
    h = _ln(h, p["embeddings/ln_g"], p["embeddings/ln_b"], eps)
    h = blocks(h, layers, eps, precision)
    mm = functools.partial(_mm, precision=precision)
    t = _gelu(mm("bte,ef->btf", h, p["mlm/dense"]) + p["mlm/dense_b"])
    t = _ln(t, p["mlm/ln_g"], p["mlm/ln_b"], eps)
    logits = mm("bte,ve->btv", t, p["embeddings/word"]) + p["mlm/bias"]
    valid = labels >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, lse - picked, 0.0))


def as_f32(flat):
    return {k: v.astype(jnp.float32) for k, v in flat.items()}


def train_steps(flat0, batches, steps, *, lr, eps, rows, precision="f32"):
    """`steps` reference steps from `flat0` (stored types) over
    `batches[i]`: per step the loss; after step 1 each leaf's gradient
    norm; after the last each leaf's change in norm. A step's gradient is
    accumulated `rows` rows at a time. Returns numpy values; stacked
    leaves give one norm per layer."""
    stored = {k: v.dtype for k, v in flat0.items()}
    grad_fn = jax.jit(jax.value_and_grad(
        lambda f, b: mlm_loss_sum(f, b, eps, precision)))

    @jax.jit
    def adam(flat, g, m, u, t):
        m = {k: ADAM_B1 * m[k] + (1 - ADAM_B1) * g[k] for k in g}
        u = {k: ADAM_B2 * u[k] + (1 - ADAM_B2) * jnp.square(g[k]) for k in g}
        alpha = lr * jnp.sqrt(1 - ADAM_B2 ** t) / (1 - ADAM_B1 ** t)
        new = {k: round_to(flat[k] - alpha * m[k]
                           / (jnp.sqrt(u[k]) + ADAM_EPS), stored[k])
               for k in g}
        return new, m, u

    @jax.jit
    def norms(tree):
        def n(k, v):
            axes = tuple(range(1, v.ndim)) if k.startswith("layers/") \
                else None
            return jnp.sqrt(jnp.sum(jnp.square(v), axis=axes))
        return {k: n(k, v) for k, v in tree.items()}

    flat = as_f32(flat0)
    start = flat
    m = {k: jnp.zeros_like(v) for k, v in flat.items()}
    u = {k: jnp.zeros_like(v) for k, v in flat.items()}
    losses, grad_norms = [], None
    B = batches["input_ids"].shape[1]
    for i in range(steps):
        total, g = 0.0, None
        count = jnp.maximum(jnp.sum(batches["labels"][i] >= 0), 1)
        for r in range(0, B, rows):
            piece = {k: v[i, r:r + rows] for k, v in batches.items()}
            part, gp = grad_fn(flat, piece)
            total = total + part
            g = gp if g is None else jax.tree_util.tree_map(jnp.add, g, gp)
        g = {k: v / count for k, v in g.items()}
        losses.append(float(total / count))
        if i == 0:
            grad_norms = expand(jax.device_get(norms(g)))
        flat, m, u = adam(flat, g, m, u, jnp.float32(i + 1))
    change = expand(jax.device_get(norms(
        {k: flat[k] - start[k] for k in flat})))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def expand(norm_tree):
    """`layers/attn/wq -> [L]` becomes `layers/<i>/attn/wq -> float`."""
    out = {}
    for k, v in norm_tree.items():
        v = np.asarray(v)
        if k.startswith("layers/"):
            for i, x in enumerate(v):
                out[f"layers/{i}/{k[len('layers/'):]}"] = float(x)
        else:
            out[k] = float(v)
    return out
