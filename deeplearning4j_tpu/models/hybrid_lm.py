"""Hybrid state-space / sparse-expert / grouped-query language model: the
``nemotron_h`` backbone (NVIDIA Nemotron-H family; here with the keys of
``nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16``'s ``config.json``),
on the training path.

A stack of pre-norm residual blocks whose mixers follow a pattern string,
one letter a block: ``M`` Mamba-2, ``E`` sparse experts beside a shared
expert, ``*`` causal grouped-query attention.

    h_0 = W_emb[ids]
    h  <- h + Mixer_c(RMSNorm(h; w, eps))        for each letter c
    logits = RMSNorm(h; w, eps) . W_head          (head not tied)
    loss = mean over positions of the next-token cross entropy

``M`` (d_inner = heads x head_dim, G groups, state N):
    [z | xBC | dt] = u . W_in
    xBC = silu(causal depthwise conv_K(xBC) + b_conv);  xBC -> x, B, C
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    per head:  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,  S_0 = 0
               y_t = S_t . C_t + D x_t              (head i, group i // (H/G))
    out = W_out . GroupRMSNorm(y * silu(z))         (gate first, then norm
                                                     over d_inner / G, weight)
  the scan in the chunked (SSD) form, `ops.ssm_scan.ssd_chunked_scan`;
  what lies between it and the two projections (conv, silu, skip, gate,
  group norm) in `kernels.ssm_fused.mamba_chain`'s two fused operations.
``E``:
    s = sigmoid(u_f32 . W_r);  top-k of s;  g_k = scale * s_k / (sum + 1e-20)
    out = sum_k g_k W2_{e_k} relu(W1_{e_k} u)^2  +  V2 relu(V1 u)^2
  with only the terms of the experts HELD here computed (`ops.moe`): the
  config says which contiguous range that is; the router keeps all its
  outputs. ``e_score_correction_bias`` (a buffer the published training
  updates for load balance, never a parameter) is zero and not carried.
``*``:
    q = u W_q (H heads), k, v = u W_k, u W_v (H_kv heads), causal
    softmax(q k^T / sqrt(d)) v with query head i on KV head i // (H/H_kv),
    then W_o. No bias, no rotary embedding (the family's modelling code
    applies none). The core goes where `kernels.attention_dispatch` says.

Stored types as `models.bert`: bfloat16 matrices, float32 norm weights,
router, ``A_log``, ``dt_bias``, ``D`` and conv; float32 Adam moments.
bfloat16 matmuls with float32 accumulation; float32 for RMSNorm
statistics, the router, softmax, the scan's decays and state, softplus
and the loss.

Not built (the published model has them, its ``config.json`` has no key
for them): the second, denoising tower and block-diffusion decoding.
Training only: no cache, no recurrent state for `DecodeEngine`, no
sharding rules (``make_train_step`` takes ``mesh=None``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..common.tracing import model_scope
from ..kernels import attention, attention_dispatch
from ..kernels.ssm_fused import mamba_chain
from ..ops import moe
from ..ops.ssm_scan import ssd_chunked_scan
from . import _optim

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclasses.dataclass
class HybridLMConfig:
    """Key names as ``nemotron_h``'s ``config.json`` where it has them."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    norm_eps: float = 1e-5
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # experts
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    # this chip's share of each expert layer: experts
    # [first_expert, first_expert + experts_held); None holds them all
    first_expert: int = 0
    experts_held: Optional[int] = None
    # depth of the whole model, for the residual-output scaling of the
    # initialisation (``rescale_prenorm_residual``); None: the pattern's
    rescale_layers: Optional[int] = None
    dtype: Any = jnp.bfloat16

    @property
    def pattern(self) -> str:
        return self.hybrid_override_pattern

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held)

    @staticmethod
    def tiny(**kw) -> "HybridLMConfig":
        """For tests: every kind of block, 8 of 16 experts held, top 2."""
        base = dict(
            vocab_size=96, hidden_size=32, hybrid_override_pattern="MEM*E",
            mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
            n_groups=2, chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, n_routed_experts=16,
            num_experts_per_tok=2, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=48, experts_held=8)
        base.update(kw)
        return HybridLMConfig(**base)


# -- parameters ------------------------------------------------------------

def _mixer_shapes(c: HybridLMConfig, kind: str) -> Dict[str, Tuple]:
    """name -> (shape, "matrix" | "residual_out" | other) of one block's
    mixer leaves; "residual_out" marks the matrices that write into the
    residual stream."""
    E = c.hidden_size
    if kind == MAMBA:
        H = c.mamba_num_heads
        return {"in_proj": ((E, c.d_inner + c.conv_dim + H), "matrix"),
                "conv_w": ((c.conv_kernel, c.conv_dim), "conv"),
                "conv_b": ((c.conv_dim,), "conv"),
                "dt_bias": ((H,), "dt_bias"), "A_log": ((H,), "A_log"),
                "D": ((H,), "one"), "gate_norm": ((c.d_inner,), "one"),
                "out_proj": ((c.d_inner, E), "residual_out")}
    if kind == EXPERTS:
        F, Fs = c.moe_intermediate_size, c.moe_shared_expert_intermediate_size
        return {"router": ((E, c.n_routed_experts), "router"),
                "w1": ((c.held, F, E), "matrix"),
                "w2": ((c.held, F, E), "residual_out"),
                "shared_w1": ((E, Fs), "matrix"),
                "shared_w2": ((Fs, E), "residual_out")}
    if kind == ATTENTION:
        q = c.num_attention_heads * c.head_dim
        kv = c.num_key_value_heads * c.head_dim
        return {"wq": ((E, q), "matrix"), "wk": ((E, kv), "matrix"),
                "wv": ((E, kv), "matrix"), "wo": ((q, E), "residual_out")}
    raise ValueError(f"unknown block kind {kind!r} in the layer pattern")


def _draw(key, shape, how: str, c: HybridLMConfig):
    f32 = jnp.float32
    if how in ("matrix", "residual_out", "router"):
        std = 0.02
        if how == "residual_out":
            std /= math.sqrt(c.rescale_layers or len(c.pattern))
        w = std * jax.random.normal(key, shape, f32)
        return w if how == "router" else w.astype(c.dtype)
    if how == "conv":
        bound = 1.0 / math.sqrt(c.conv_kernel)
        return jax.random.uniform(key, shape, f32, -bound, bound)
    if how == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32)
                     * (math.log(c.time_step_max) - math.log(c.time_step_min))
                     + math.log(c.time_step_min))
        dt = jnp.maximum(dt, c.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))        # inverse of softplus
    if how == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    return jnp.ones(shape, f32)


def init_params(key, config: HybridLMConfig) -> Dict:
    """Seeded initialisation: N(0, 0.02) matrices (those writing into the
    residual stream scaled by 1/sqrt(depth)), ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of a log-uniform step in
    [time_step_min, time_step_max] floored at ``time_step_floor``,
    ``D = 1``, norm weights 1, the conv's weight and bias
    U(+-1/sqrt(conv_kernel))."""
    c = config
    k_emb, k_head, k_blocks = jax.random.split(key, 3)
    blocks = []
    for i, kind in enumerate(c.pattern):
        block = {"norm": jnp.ones((c.hidden_size,), jnp.float32)}
        shapes = _mixer_shapes(c, kind)
        for j, (name, (shape, how)) in enumerate(sorted(shapes.items())):
            block[name] = _draw(
                jax.random.fold_in(jax.random.fold_in(k_blocks, i), j),
                shape, how, c)
        blocks.append(block)
    return {
        "embed": _draw(k_emb, (c.vocab_size, c.hidden_size), "matrix", c),
        "blocks": blocks,
        "final_norm": jnp.ones((c.hidden_size,), jnp.float32),
        "head": _draw(k_head, (c.hidden_size, c.vocab_size), "matrix", c),
    }


def init_opt_state(params):
    return _optim.adam_init(params)


# -- the mixers -------------------------------------------------------------

def _rms_norm(x, w, eps, groups: int = 1):
    """RMSNorm over the last axis, or over ``groups`` equal parts of it;
    float32 statistics."""
    with model_scope("ln"):
        x32 = x.astype(jnp.float32)
        shape = x32.shape
        if groups > 1:
            x32 = x32.reshape(shape[:-1] + (groups, shape[-1] // groups))
        x32 = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                              + eps)
        return (x32.reshape(shape) * w).astype(x.dtype)


def _mamba(p, u, c: HybridLMConfig):
    B, T, _ = u.shape
    H, G = c.mamba_num_heads, c.n_groups
    # the fused chain holds time as the minor axis, [B, channels, T]
    steps_major = lambda a: jnp.swapaxes(a, 1, 2)

    def scan(x, Bm, Cm, dt):
        dt = jax.nn.softplus(steps_major(dt).astype(jnp.float32)
                             + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        x, Bm, Cm = (steps_major(a).reshape(B, T, n, -1)
                     for a, n in ((x, H), (Bm, G), (Cm, G)))
        with model_scope("ssm_scan"):
            y = ssd_chunked_scan(x, dt, A, Bm, Cm, c.chunk_size)
        return steps_major(y.reshape(B, T, c.d_inner))

    with model_scope("ssm"):
        zxbcdt = jnp.einsum("bte,ef->bft", u, p["in_proj"])
        # conv + silu, the scan, then (y + D x) silu(z) and its group norm
        y = mamba_chain(zxbcdt, p["conv_w"], p["conv_b"], p["D"],
                        p["gate_norm"], c.norm_eps, G, scan)
        return jnp.einsum("bft,fe->bte", y, p["out_proj"])


def _relu2_mlp(u, w1, w2):
    h = jnp.einsum("te,ef->tf", u, w1, preferred_element_type=jnp.float32)
    h = jnp.square(jax.nn.relu(h)).astype(u.dtype)
    return jnp.einsum("tf,fe->te", h, w2, preferred_element_type=jnp.float32)


def _experts(p, u, c: HybridLMConfig):
    """(output [B, T, E], expert_tokens [held] int32)."""
    B, T, E = u.shape
    u = u.reshape(B * T, E)
    with model_scope("moe_route"):
        idx, gates = moe.route(u, p["router"], c.num_experts_per_tok,
                               c.routed_scaling_factor)
    routed, counts = moe.routed_experts(
        u, p["w1"], p["w2"], idx, gates, c.first_expert, c.n_routed_experts)
    with model_scope("moe_shared"):
        shared = _relu2_mlp(u, p["shared_w1"], p["shared_w2"])
        out = (routed + shared).astype(u.dtype)
    return out.reshape(B, T, E), counts


def _attention(p, u, c: HybridLMConfig, path: str):
    with model_scope("attn"):
        q = jnp.einsum("bte,ef->btf", u, p["wq"])
        k = jnp.einsum("bte,ef->btf", u, p["wk"])
        v = jnp.einsum("bte,ef->btf", u, p["wv"])
        with model_scope("attn_core"):
            ctx = attention(q, k, v, path=path, head_dim=c.head_dim,
                            causal=True)
        return jnp.einsum("btf,fe->bte", ctx, p["wo"])


def _block(p, h, kind: str, c: HybridLMConfig, path: str):
    """One pre-norm residual block: (h, expert_tokens or None)."""
    u = _rms_norm(h, p["norm"], c.norm_eps)
    counts = None
    if kind == MAMBA:
        out = _mamba(p, u, c)
    elif kind == EXPERTS:
        out, counts = _experts(p, u, c)
    else:
        out = _attention(p, u, c, path)
    return h + out, counts


# -- forward, loss, step ------------------------------------------------------

def hidden_states(params, input_ids, config: HybridLMConfig,
                  remat: bool = False):
    """(final hidden states [B, T, E] before the last norm, expert_tokens
    int32 [n_expert_blocks, held])."""
    c = config
    if len(params["blocks"]) != len(c.pattern):
        raise ValueError(f"{len(params['blocks'])} blocks of parameters for "
                         f"the pattern {c.pattern!r}")
    with model_scope("embed"):
        h = jnp.take(params["embed"], input_ids, axis=0).astype(c.dtype)
    # asked once per trace, and only by a model that has attention blocks
    path = (attention_dispatch(input_ids.shape[1], head_dim=c.head_dim)
            if ATTENTION in c.pattern else None)
    counts = []
    for p, kind in zip(params["blocks"], c.pattern):
        block = lambda p, h, kind=kind: _block(p, h, kind, c, path)
        if remat:
            block = jax.checkpoint(block)
        h, n = block(p, h)
        if n is not None:
            counts.append(n)
    return h, (jnp.stack(counts) if counts
               else jnp.zeros((0, c.held), jnp.int32))


def forward(params, input_ids, config: HybridLMConfig, remat: bool = False):
    """Token ids [B, T] -> float32 logits [B, T, V] over the rows of the
    vocabulary held."""
    h, _ = hidden_states(params, input_ids, config, remat)
    return _logits(params, h, config)


def _logits(params, h, c: HybridLMConfig):
    h = _rms_norm(h, params["final_norm"], c.norm_eps)
    with model_scope("head"):
        return jnp.einsum("bte,ev->btv", h, params["head"],
                          preferred_element_type=jnp.float32)


def lm_loss(params, batch, config: HybridLMConfig, remat: bool = False):
    """(mean next-token cross entropy over the B x (T - 1) predicted
    positions, expert_tokens). batch: ``input_ids`` [B, T]."""
    ids = batch["input_ids"]
    h, counts = hidden_states(params, ids, config, remat)
    logits = _logits(params, h, config)
    with model_scope("loss"):
        B, T = ids.shape
        # position t predicts ids[t + 1]; the last position predicts
        # nothing and is weighted 0, so the logits are never sliced
        labels = jnp.roll(ids, -1, axis=1)
        lsm = jax.nn.log_softmax(logits, axis=-1)
        per_tok = -jnp.take_along_axis(lsm, labels[..., None], axis=-1)[..., 0]
        per_tok = jnp.where(jnp.arange(T) < T - 1, per_tok, 0.0)
        return jnp.sum(per_tok) / (B * (T - 1)), counts


def make_train_step(config: HybridLMConfig, mesh=None,
                    learning_rate=1e-4, remat: bool = True):
    """Single jitted train step, built as `bert.make_train_step`:
    ``(params, opt_state, batch, iteration) -> (params, opt_state, aux)``
    with params and state donated, ``aux = {"loss", "expert_tokens":
    int32 [n_expert_blocks, held]}``. ``remat`` recomputes each block in
    the backward pass (`jax.checkpoint` around one block).
    ``learning_rate`` is a number or a schedule ``iteration -> rate`` (as
    `learning.Schedule`), traced into the step. Nothing here balances the
    experts' load (no update of the router's bias, no auxiliary loss): an
    untrained router under Adam's first, sign-like steps at a constant
    1e-4 sends every token to the same few experts within ten steps
    (measured on the chip, PERF.md). No sharded layout is built yet:
    ``mesh`` must be None."""
    if mesh is not None:
        raise NotImplementedError(
            "hybrid_lm has no sharding rules yet (no expert exchange "
            "across chips): mesh must be None")
    from ..runtime.inference import counted_jit

    def loss_fn(params, batch):
        return lm_loss(params, batch, config, remat)

    def step(params, opt_state, batch, iteration):
        (loss, counts), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        rate = (learning_rate(iteration) if callable(learning_rate)
                else learning_rate)
        new_params, opt_state = _optim.adam_apply(
            params, grads, opt_state, rate, iteration)
        return new_params, opt_state, {"loss": loss, "expert_tokens": counts}

    return counted_jit(step, tag=f"hybrid_lm_train:{id(step)}",
                       donate_argnums=(0, 1))


# -- counters -----------------------------------------------------------------

def observe(aux, config: HybridLMConfig, tokens: int) -> float:
    """Read a step's loss on the host and feed the expert-load counters
    from its ``aux`` (blocks until that step is done; ``tokens`` is the
    step's B x T):
    ``dl4j_moe_assignments_total`` (tokens x top-k routed, over all
    experts), ``dl4j_moe_held_assignments_total`` (those that fell on
    experts held here), ``dl4j_moe_expert_tokens_total{block,expert}`` and
    the gauge ``dl4j_moe_max_expert_tokens`` (the fullest held expert of
    the step). Returns the loss."""
    import numpy as np
    from ..common.environment import environment
    loss, counts = jax.device_get((aux["loss"], aux["expert_tokens"]))
    counts = np.asarray(counts)
    if counts.size:
        reg = environment().metrics()
        held = int(counts.sum())
        reg.counter("dl4j_moe_assignments_total",
                    "Token-to-expert assignments routed (tokens x top-k "
                    "x expert blocks)").inc(
                        tokens * config.num_experts_per_tok * counts.shape[0])
        reg.counter("dl4j_moe_held_assignments_total",
                    "Assignments that fell on experts held on this "
                    "chip").inc(held)
        family = reg.counter(
            "dl4j_moe_expert_tokens_total",
            "Assignments received per held expert", labels=("block", "expert"))
        for b, row in enumerate(counts):
            for e, n in enumerate(row):
                family.labels(block=str(b),
                              expert=str(config.first_expert + e)).inc(int(n))
        reg.gauge("dl4j_moe_max_expert_tokens",
                  "Assignments of the fullest held expert in the last "
                  "observed step").set(int(counts.max()))
    return float(loss)
