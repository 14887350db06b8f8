"""Hybrid state-space / sparse-expert / grouped-query / latent-attention
language model: the ``nemotron_h`` backbone (NVIDIA Nemotron-H family; here
with the keys of ``nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16``'s
``config.json``), by the same blocks the dense ``granitemoehybrid`` layer
(``ibm-granite/granite-4.0-h-micro``), and the DeepSeek-V3 family's decoder
(``jdopensource/JoyAI-LLM-Flash``: latent attention with a rotary part,
gated-SiLU experts, one multi-token-prediction module), on the training
path.

A stack of pre-norm residual blocks whose mixers follow a pattern string,
one letter a block: ``M`` Mamba-2, ``E`` sparse experts beside a shared
expert, ``*`` causal grouped-query attention, ``L`` causal latent
attention, ``-`` a dense MLP. Four
scalar multipliers (each 1 unless the configuration says otherwise: ``m_e``
``embedding_multiplier``, ``m_r`` ``residual_multiplier``, ``m_a``
``attention_multiplier``, ``m_l`` ``logits_scaling``):

    h_0 = m_e * W_emb[ids]
    h  <- h + m_r * Mixer_c(RMSNorm(h; w, eps))  for each letter c
    logits = (RMSNorm(h; w, eps) . W_head) / m_l  (W_head = W_emb^T where
                                                   ``tie_word_embeddings``)
    loss = mean of the next-token cross entropy over the predicting
           positions: t < T - 1 and, in a packed row, d[t + 1] = d[t]

A granite layer (a mixer and a gated MLP, each with its norm) is two
letters, ``M-`` or ``*-``; granite-4.0-h-micro's period of ten layers is
``M-M-M-M-M-*-M-M-M-M-`` with m_e 12, m_r 0.22, m_a 1/64, m_l 8.

**Packed rows.** ``batch["segment_ids"]`` (``[B, T]`` int32, non-decreasing
along a row; absent: one document a row) is ``d``, the document a position
belongs to. A document then computes what it would compute alone: the
scan's state is reset at its first step (``S_t = [d_t = d_{t-1}] exp(dt_t
A) S_{t-1} + ...``), the conv's tap ``x_{t-k}`` counts only where ``d[t-k]
= d[t]``, key ``j`` is visible to query ``i`` iff ``j <= i`` and ``d[j] =
d[i]``, and a document's last token predicts nothing.

``M`` (d_inner = heads x head_dim, G groups, state N):
    [z | xBC | dt] = u . W_in
    xBC = silu(causal depthwise conv_K(xBC) + b_conv);  xBC -> x, B, C
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    per head:  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,  S_0 = 0
               y_t = S_t . C_t + D x_t              (head i, group i // (H/G))
    out = W_out . GroupRMSNorm(y * silu(z))         (gate first, then norm
                                                     over d_inner / G, weight)
  the scan in the chunked (SSD) form, `ops.ssm_scan.ssd_chunked_scan`: a
  Pallas kernel pair (`kernels.ssd_scan`) that keeps a chunk's decay and
  weight tiles and the carried state in VMEM; what lies between it and
  the two projections (conv, silu, skip, gate, group norm) in
  `kernels.ssm_fused.mamba_chain`'s two fused operations. All three hold
  time as the minor axis, ``[B, channels, T]``, from the input projection's
  result to the output projection's operand.
``E``:
    s = sigmoid(u_f32 . W_r);  top-k of s;  g_k = scale * s_k / (sum + 1e-20)
    ``moe_hidden_act`` "relu2" (nemotron_h):
        out = sum_k g_k W2_{e_k} relu(W1_{e_k} u)^2  +  V2 relu(V1 u)^2
    ``moe_hidden_act`` "silu" (DeepSeek-V3 family), routed and shared alike:
        out = sum_k g_k Down_e(silu(Gate_e u) * Up_e u) + Shared(u)
        (``w1`` holds [Gate_e ; Up_e] as one [2 F, E] matrix an expert)
  with only the terms of the experts HELD here computed (`ops.moe`): the
  config says which contiguous range that is; the router keeps all its
  outputs. ``e_score_correction_bias`` (a buffer the published training
  updates for load balance, never a parameter; selection is by ``s + b``)
  is zero, never updated and not carried; ``n_group`` = ``topk_group`` = 1,
  so group-limited selection is the identity.
``L`` (latent attention, H heads; DeepSeek-V3, arXiv:2412.19437 §2.1; the
  training, non-absorbed form: no latent cache, no absorbed decode):
    c_q  = RMSNorm(u W_qa; w_q)                  W_qa: E x q_lora_rank
    q    = c_q W_qb -> H heads of [q_nope d_n | q_rope d_r]
    [c_kv | k_rope] = u W_kva                    W_kva: E x (kv_lora_rank + d_r)
    c_kv = RMSNorm(c_kv; w_kv)                   k_rope ONE head, shared by all H
    [k_nope | v] = c_kv W_kvb -> H heads of (d_n + d_v)
    q_rope, k_rope <- RoPE at positions 0..T-1: the pair (x_2i, x_2i+1)
        turned by pos * theta^(-2i/d_r) (``rope_interleave``: the stored
        order is interleaved), float32 angles, no scaling factor
    k_h = [k_nope_h | k_rope];  o_h = softmax(causal(q_h . k_h / sqrt(d_n +
    d_r))) v_h  (192-wide scores, 128-wide values);  out = concat_h(o_h) W_o
  no bias. The core is `kernels.attention` with ``v_head_dim``; ``k_rope``
  is repeated for the H heads and concatenated here.
``*``:
    q = u W_q (H heads), k, v = u W_k, u W_v (H_kv heads), causal
    softmax(m_a q k^T) v with query head i on KV head i // (H/H_kv), then
    W_o; m_a = 1 / sqrt(d) unless ``attention_multiplier`` is given. No
    bias, no rotary or other positional embedding (neither family's
    modelling code applies one here). The core goes where
    `kernels.attention_dispatch` says.
``-``:
    ``mlp_hidden_act`` "silu":  [a | b] = u W_in (E -> 2 F);
                                out = W_out (silu(a) * b)      (granite)
    ``mlp_hidden_act`` "relu2": out = W_out relu(u W_in)^2     (nemotron_h)
  no bias, F = ``intermediate_size``.

Stored types as `models.bert`: bfloat16 matrices, float32 norm weights,
router, ``A_log``, ``dt_bias``, ``D`` and conv; float32 Adam moments.
bfloat16 matmuls with float32 accumulation; float32 for RMSNorm
statistics, the router, softmax, the scan's decays and state, softplus
and the loss.

**Multi-token prediction** (``num_nextn_predict_layers`` 1; arXiv:2412.19437
§2.2, eq. 21-25; one module of depth 1, parameters under ``params["mtp"]``).
With ``h_t`` the main model's last hidden state at position t AFTER its
final norm:

    m_t  = W_eh [ RMSNorm(Emb(x_{t+1}); w_e) ; RMSNorm(h_t; w_h) ]  W_eh: 2E x E
    m'_t = Layer_MTP(m_t)      one more ``L`` block and one more ``E`` block
    p_t  = Head(RMSNorm(m'_t; w_n))   the main model's embedding and head
    L    = L_main + lambda L_mtp;  L_mtp: mean over t < T-2 of CE(p_t, x_{t+2})

``lambda`` is ``mtp_loss_weight``. 0 modules trace nothing.

Not built (the published models have them): nemotron's second, denoising
tower and block-diffusion decoding (its ``config.json`` has no key for
them); the latent paged cache and the absorbed decode form of ``L``; MTP in
the decode scheduler; the update of the router's bias.
Training only: no cache, no recurrent state for `DecodeEngine`, no
sharding rules (``make_train_step`` takes ``mesh=None``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common.tracing import model_scope
from ..kernels import attention, attention_dispatch
from ..kernels.flash_attention import SAVED_LSE, SAVED_OUT, streams
from ..kernels.ssm_fused import mamba_chain
from ..ops import moe
from ..ops.ssm_scan import ssd_chunked_scan
from . import _optim

MAMBA, EXPERTS, ATTENTION, MLP, LATENT = "M", "E", "*", "-", "L"
#: the one layer of a multi-token-prediction module, in pattern letters
MTP_PATTERN = LATENT + EXPERTS


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
    # the experts' activation, routed and shared alike: "relu2" (two
    # matrices an expert) or "silu" (gated: [Gate ; Up] and Down)
    moe_hidden_act: str = "relu2"
    # this chip's share of each expert layer: experts
    # [first_expert, first_expert + experts_held); None holds them all
    first_expert: int = 0
    experts_held: Optional[int] = None
    # the dense MLP of a ``-`` block: "relu2" (up, relu^2, down) or
    # "silu" (gated: silu of one half of the up projection times the other)
    intermediate_size: int = 1856
    mlp_hidden_act: str = "relu2"
    # scalar multipliers (``granitemoehybrid``'s names); 1 and None trace
    # nothing. ``attention_multiplier`` None: head_dim ** -0.5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    tie_word_embeddings: bool = False
    # latent attention (``L``), the DeepSeek-V3 family's key names
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    # multi-token prediction: modules (0 or 1) and the weight of their loss
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
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

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @staticmethod
    def tiny(granite: bool = False, latent: bool = False,
             **kw) -> "HybridLMConfig":
        """For tests: every kind of ``nemotron_h`` block, 8 of 16 experts
        held, top 2; or, ``granite``, the dense granite layer's shape: a
        mixer and a gated-SiLU MLP a layer, one group, the four
        multipliers, the tied head; or, ``latent``, the DeepSeek-V3
        family's shape: a dense latent-attention layer, two with
        gated-SiLU experts (4 of 16 held, top 4), one MTP module."""
        base = dict(
            vocab_size=96, hidden_size=32, hybrid_override_pattern="MEM*E",
            mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
            n_groups=2, chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, n_routed_experts=16,
            num_experts_per_tok=2, moe_intermediate_size=24,
            moe_shared_expert_intermediate_size=48, experts_held=8)
        if granite:
            base.update(
                hybrid_override_pattern="M-*-M-", n_groups=1,
                intermediate_size=48, mlp_hidden_act="silu",
                embedding_multiplier=12.0, residual_multiplier=0.22,
                attention_multiplier=0.125, logits_scaling=8.0,
                tie_word_embeddings=True)
        if latent:
            base.update(
                hybrid_override_pattern="L-LELE", norm_eps=1e-6,
                num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
                qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                rope_theta=3.2e7, intermediate_size=48,
                mlp_hidden_act="silu", moe_hidden_act="silu",
                num_experts_per_tok=4, experts_held=4,
                moe_intermediate_size=24,
                moe_shared_expert_intermediate_size=24,
                num_nextn_predict_layers=1)
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
        if c.moe_hidden_act not in ("silu", "relu2"):
            raise ValueError(f"unknown moe_hidden_act {c.moe_hidden_act!r}")
        F, Fs = c.moe_intermediate_size, c.moe_shared_expert_intermediate_size
        up = 2 if c.moe_hidden_act == "silu" else 1     # [Gate ; Up]
        return {"router": ((E, c.n_routed_experts), "router"),
                "w1": ((c.held, up * F, E), "matrix"),
                "w2": ((c.held, F, E), "residual_out"),
                "shared_w1": ((E, up * Fs), "matrix"),
                "shared_w2": ((Fs, E), "residual_out")}
    if kind == LATENT:
        H, r = c.num_attention_heads, c.kv_lora_rank
        return {"wq_a": ((E, c.q_lora_rank), "matrix"),
                "q_norm": ((c.q_lora_rank,), "one"),
                "wq_b": ((c.q_lora_rank, H * c.qk_head_dim), "matrix"),
                "wkv_a": ((E, r + c.qk_rope_head_dim), "matrix"),
                "kv_norm": ((r,), "one"),
                "wkv_b": ((r, H * (c.qk_nope_head_dim + c.v_head_dim)),
                          "matrix"),
                "wo": ((H * c.v_head_dim, E), "residual_out")}
    if kind == ATTENTION:
        q = c.num_attention_heads * c.head_dim
        kv = c.num_key_value_heads * c.head_dim
        return {"wq": ((E, q), "matrix"), "wk": ((E, kv), "matrix"),
                "wv": ((E, kv), "matrix"), "wo": ((q, E), "residual_out")}
    if kind == MLP:
        if c.mlp_hidden_act not in ("silu", "relu2"):
            raise ValueError(f"unknown mlp_hidden_act {c.mlp_hidden_act!r}")
        F = c.intermediate_size
        wide = 2 * F if c.mlp_hidden_act == "silu" else F
        return {"mlp_in": ((E, wide), "matrix"),
                "mlp_out": ((F, E), "residual_out")}
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
    ones = lambda: jnp.ones((c.hidden_size,), jnp.float32)

    def draw_blocks(key, pattern):
        blocks = []
        for i, kind in enumerate(pattern):
            block = {"norm": ones()}
            shapes = _mixer_shapes(c, kind)
            for j, (name, (shape, how)) in enumerate(sorted(shapes.items())):
                block[name] = _draw(
                    jax.random.fold_in(jax.random.fold_in(key, i), j),
                    shape, how, c)
            blocks.append(block)
        return blocks

    params = {
        "embed": _draw(k_emb, (c.vocab_size, c.hidden_size), "matrix", c),
        "blocks": draw_blocks(k_blocks, c.pattern),
        "final_norm": ones(),
    }
    if not c.tie_word_embeddings:
        params["head"] = _draw(k_head, (c.hidden_size, c.vocab_size),
                               "matrix", c)
    if c.num_nextn_predict_layers:
        if c.num_nextn_predict_layers != 1:
            raise ValueError("one multi-token-prediction module at most")
        k_mtp = jax.random.fold_in(key, 3)
        params["mtp"] = {
            "embed_norm": ones(), "hidden_norm": ones(),
            "merge": _draw(jax.random.fold_in(k_mtp, 0),
                           (2 * c.hidden_size, c.hidden_size), "matrix", c),
            "blocks": draw_blocks(jax.random.fold_in(k_mtp, 1), MTP_PATTERN),
            "final_norm": ones()}
    return params


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


def _mamba(p, u, c: HybridLMConfig, segment_ids=None):
    B, T, _ = u.shape
    H, G = c.mamba_num_heads, c.n_groups

    # the fused chain and the scan hold time as the minor axis, [B,
    # channels, T]: the scan takes the chain's operands as they come
    def scan(x, Bm, Cm, dt):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][:, None])
        A = -jnp.exp(p["A_log"])
        x, Bm, Cm = (a.reshape(B, n, -1, T)
                     for a, n in ((x, H), (Bm, G), (Cm, G)))
        with model_scope("ssm_scan"):
            y = ssd_chunked_scan(x, dt, A, Bm, Cm, c.chunk_size,
                                 segment_ids)
        return y.reshape(B, c.d_inner, T)

    with model_scope("ssm"):
        zxbcdt = jnp.einsum("bte,ef->bft", u, p["in_proj"])
        # conv + silu, the scan, then (y + D x) silu(z) and its group norm;
        # a packed row's ids go to the conv and (above) to the scan
        y = mamba_chain(zxbcdt, p["conv_w"], p["conv_b"], p["D"],
                        p["gate_norm"], c.norm_eps, G, scan, segment_ids)
        return jnp.einsum("bft,fe->bte", y, p["out_proj"])


def _relu2_mlp(u, w1, w2):
    h = jnp.einsum("te,ef->tf", u, w1, preferred_element_type=jnp.float32)
    h = jnp.square(jax.nn.relu(h)).astype(u.dtype)
    return jnp.einsum("tf,fe->te", h, w2, preferred_element_type=jnp.float32)


def _gated_mlp(u, w1, w2):
    h = jnp.einsum("te,ef->tf", u, w1, preferred_element_type=jnp.float32)
    a, b = jnp.split(h, 2, axis=-1)
    h = (jax.nn.silu(a) * b).astype(u.dtype)
    return jnp.einsum("tf,fe->te", h, w2, preferred_element_type=jnp.float32)


def _experts(p, u, c: HybridLMConfig):
    """(output [B, T, E], expert_tokens [held] int32)."""
    B, T, E = u.shape
    u = u.reshape(B * T, E)
    with model_scope("moe_route"):
        idx, gates = moe.route(u, p["router"], c.num_experts_per_tok,
                               c.routed_scaling_factor)
    routed, counts = moe.routed_experts(
        u, p["w1"], p["w2"], idx, gates, c.first_expert, c.n_routed_experts,
        act=c.moe_hidden_act)
    shared_mlp = _gated_mlp if c.moe_hidden_act == "silu" else _relu2_mlp
    with model_scope("moe_shared"):
        shared = shared_mlp(u, p["shared_w1"], p["shared_w2"])
        out = (routed + shared).astype(u.dtype)
    return out.reshape(B, T, E), counts


def rotary(x, theta: float):
    """Interleaved rotary embedding of ``x`` [B, T, ..., d] at positions
    0..T-1: the pair ``(x_2i, x_2i+1)`` turned by ``pos * theta^(-2i/d)``,
    float32 angles and arithmetic, the result in ``x``'s dtype. The pair's
    partner is fetched by a product with the ``d x d`` signed permutation
    (exact in any dtype: one +-1 a column), which keeps the lanes where
    they are."""
    with model_scope("rope"):
        d, T = x.shape[-1], x.shape[1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
        shape = (1, T) + (1,) * (x.ndim - 3) + (d,)
        cos, sin = (jnp.repeat(f(ang), 2, axis=-1).reshape(shape)
                    for f in (jnp.cos, jnp.sin))
        # (x P)_2i = -x_2i+1, (x P)_2i+1 = x_2i
        swap = np.zeros((d, d), np.float32)
        i = np.arange(0, d, 2)
        swap[i + 1, i], swap[i, i + 1] = -1.0, 1.0
        partner = jnp.einsum("...d,de->...e", x, jnp.asarray(swap, x.dtype))
        return (x.astype(jnp.float32) * cos
                + partner.astype(jnp.float32) * sin).astype(x.dtype)


def _latent_attention(p, u, c: HybridLMConfig, path: str):
    B, T, _ = u.shape
    H, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                     c.qk_rope_head_dim, c.v_head_dim)
    r = c.kv_lora_rank
    with model_scope("attn"):
        cq = _rms_norm(jnp.einsum("bte,ef->btf", u, p["wq_a"]),
                       p["q_norm"], c.norm_eps)
        q = jnp.einsum("btf,fg->btg", cq, p["wq_b"]).reshape(B, T, H, dn + dr)
        kv_a = jnp.einsum("bte,ef->btf", u, p["wkv_a"])
        ckv = _rms_norm(kv_a[..., :r], p["kv_norm"], c.norm_eps)
        kv = jnp.einsum("btf,fg->btg", ckv, p["wkv_b"]).reshape(
            B, T, H, dn + dv)
        q_rope = rotary(q[..., dn:], c.rope_theta)
        k_rope = rotary(kv_a[..., r:], c.rope_theta)           # [B, T, dr]
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn],
             jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, dr))], axis=-1)
        with model_scope("attn_core"):
            ctx = attention(q, k, kv[..., dn:], path=path, head_dim=dn + dr,
                            v_head_dim=dv, causal=True)
        return jnp.einsum("btf,fe->bte", ctx.reshape(B, T, H * dv), p["wo"])


def _attention(p, u, c: HybridLMConfig, path: str, segment_ids=None):
    with model_scope("attn"):
        q = jnp.einsum("bte,ef->btf", u, p["wq"])
        k = jnp.einsum("bte,ef->btf", u, p["wk"])
        v = jnp.einsum("bte,ef->btf", u, p["wv"])
        with model_scope("attn_core"):
            ctx = attention(q, k, v, path=path, head_dim=c.head_dim,
                            causal=True, scale=c.attention_multiplier,
                            segment_ids=segment_ids)
        return jnp.einsum("btf,fe->bte", ctx, p["wo"])


def _mlp(p, u, c: HybridLMConfig):
    with model_scope("mlp"):
        h = jnp.einsum("bte,ef->btf", u, p["mlp_in"]).astype(jnp.float32)
        if c.mlp_hidden_act == "silu":
            a, b = jnp.split(h, 2, axis=-1)
            h = jax.nn.silu(a) * b
        else:
            h = jnp.square(jax.nn.relu(h))
        return jnp.einsum("btf,fe->bte", h.astype(u.dtype), p["mlp_out"])


def _block(p, h, kind: str, c: HybridLMConfig, path: str, segment_ids=None):
    """One pre-norm residual block: (h, expert_tokens or None)."""
    u = _rms_norm(h, p["norm"], c.norm_eps)
    counts = None
    if kind == MAMBA:
        out = _mamba(p, u, c, segment_ids)
    elif kind == EXPERTS:
        out, counts = _experts(p, u, c)
    elif kind == MLP:
        out = _mlp(p, u, c)
    elif kind == LATENT:
        if segment_ids is not None:
            raise NotImplementedError(
                "latent attention takes one document a row: rotary "
                "positions are a row's 0..T-1")
        out = _latent_attention(p, u, c, path)
    else:
        out = _attention(p, u, c, path, segment_ids)
    if c.residual_multiplier != 1.0:
        out = out * jnp.asarray(c.residual_multiplier, out.dtype)
    return h + out, counts


# -- forward, loss, step ------------------------------------------------------

def hidden_states(params, input_ids, config: HybridLMConfig,
                  remat: bool = False, segment_ids=None):
    """(final hidden states [B, T, E] before the last norm, expert_tokens
    int32 [n_expert_blocks, held]). ``segment_ids`` [B, T] int32: the
    documents of packed rows (the module's docstring)."""
    c = config
    if len(params["blocks"]) != len(c.pattern):
        raise ValueError(f"{len(params['blocks'])} blocks of parameters for "
                         f"the pattern {c.pattern!r}")
    h, counts, _ = _trunk(params, input_ids, c, remat, segment_ids)
    return h, _stacked(counts, c)


def _trunk(params, input_ids, c: HybridLMConfig, remat, segment_ids):
    """(h after the pattern's blocks, [expert_tokens of each ``E`` block],
    the attention path the trace was given)."""
    path = _attention_path(c, input_ids.shape[1])
    h, counts = _blocks(params["blocks"], c.pattern,
                        _embed(params, input_ids, c), c, path, remat,
                        segment_ids)
    return h, counts, path


def _stacked(counts, c: HybridLMConfig):
    return (jnp.stack(counts) if counts
            else jnp.zeros((0, c.held), jnp.int32))


def _embed(params, input_ids, c: HybridLMConfig):
    with model_scope("embed"):
        h = jnp.take(params["embed"], input_ids, axis=0).astype(c.dtype)
        if c.embedding_multiplier != 1.0:
            h = h * jnp.asarray(c.embedding_multiplier, h.dtype)
        return h


def _attention_path(c: HybridLMConfig, seq_len: int):
    """Asked once per trace, and only by a model that has attention
    blocks; a model has ``*`` blocks or ``L`` blocks, not both."""
    latent = LATENT in c.pattern or c.num_nextn_predict_layers
    if latent and ATTENTION in c.pattern:
        raise ValueError("a pattern with both `*` and `L` blocks would ask "
                         "for the attention path twice")
    if latent:
        return attention_dispatch(seq_len, head_dim=c.qk_head_dim)
    if ATTENTION in c.pattern:
        return attention_dispatch(seq_len, head_dim=c.head_dim)
    return None


#: what a rematerialised block keeps from its first forward: the streaming
#: flash core's output and log-sum-exp, and nothing else
_SAVE_CORES = jax.checkpoint_policies.save_only_these_names(SAVED_OUT,
                                                            SAVED_LSE)


def _blocks(blocks, pattern, h, c: HybridLMConfig, path, remat, segment_ids):
    """(h after the blocks, [expert_tokens of each ``E`` block]).

    Under ``remat`` each block is one `jax.checkpoint` whose policy keeps
    the output and log-sum-exp of a streaming flash core (the two names
    `kernels.flash_attention` gives them): the backward recomputes the
    block's forward up to q, k and v for the backward kernels, reads the
    core's results from the first forward and does not launch the forward
    kernel again. A block with no such core (``M``, ``E``, ``-``, or a core
    on XLA's path or the one-tile kernel) holds neither name and saves
    nothing, as a bare checkpoint does."""
    counts = []
    for p, kind in zip(blocks, pattern):
        block = lambda p, h, kind=kind: _block(p, h, kind, c, path,
                                               segment_ids)
        if remat:
            block = jax.checkpoint(block, policy=_SAVE_CORES)
            if _saves_core(kind, c, path, h.shape[1]):
                _count_saved_core(kind)
        h, n = block(p, h)
        if n is not None:
            counts.append(n)
    return h, counts


def _saves_core(kind, c: HybridLMConfig, path, seq_len) -> bool:
    """Whether a block of ``kind`` holds a streaming flash core, whose
    output and log-sum-exp `_SAVE_CORES` keeps."""
    if path != "flash" or kind not in (ATTENTION, LATENT):
        return False
    if kind == LATENT:
        return streams(seq_len, c.qk_head_dim, c.v_head_dim)
    return streams(seq_len, c.head_dim)


def _count_saved_core(kind) -> None:
    """Tick ``dl4j_remat_saved_cores_total{kind}`` (``kind`` the block's
    letter) at trace time: one rematerialised block whose core's results
    the policy keeps."""
    try:
        from ..common.environment import environment
        environment().metrics().counter(
            "dl4j_remat_saved_cores_total",
            "Rematerialised blocks whose streaming flash core's output and "
            "log-sum-exp the checkpoint policy keeps, counted at trace time",
            labels=("kind",)).labels(kind=kind).inc()
    except Exception:
        pass  # observability must never break a trace


def forward(params, input_ids, config: HybridLMConfig, remat: bool = False,
            segment_ids=None):
    """Token ids [B, T] -> float32 logits [B, T, V] over the rows of the
    vocabulary held."""
    h, _ = hidden_states(params, input_ids, config, remat, segment_ids)
    return _logits(params, h, config)


def _logits(params, h, c: HybridLMConfig):
    return _head(params, _rms_norm(h, params["final_norm"], c.norm_eps), c)


def _head(params, h, c: HybridLMConfig):
    """Float32 logits of normed hidden states."""
    with model_scope("head"):
        if c.tie_word_embeddings:
            logits = jnp.einsum("bte,ve->btv", h, params["embed"],
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bte,ev->btv", h, params["head"],
                                preferred_element_type=jnp.float32)
        if c.logits_scaling != 1.0:
            logits = logits * (1.0 / c.logits_scaling)
        return logits


def lm_loss(params, batch, config: HybridLMConfig, remat: bool = False):
    """(mean next-token cross entropy over the predicting positions,
    expert_tokens). batch: ``input_ids`` [B, T] and, for packed rows,
    ``segment_ids`` [B, T] int32 (non-decreasing along a row). Without
    them the B x (T - 1) positions before a row's last predict; with them
    a document's last token predicts nothing either, and the mean is over
    the positions that are left."""
    loss, aux = _loss_terms(params, batch, config, remat)
    return loss, aux[0]


def _token_ce(logits, ids, ahead: int):
    """Each position's cross entropy against the id ``ahead`` positions on
    (the ids rolled: a row's last ``ahead`` positions read its first ids
    and are for the caller to weight 0)."""
    labels = jnp.roll(ids, -ahead, axis=1)
    lsm = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(lsm, labels[..., None], axis=-1)[..., 0]


def _mtp_loss(params, hn, ids, c: HybridLMConfig, path, remat: bool):
    """The multi-token-prediction module over the main model's normed last
    hidden states ``hn``: (mean over t < T - 2 of CE(p_t, x_{t+2}), each
    position's term [B, T] (0 from T - 2 on), [expert_tokens of its ``E``
    block]). Position t takes the embedding of
    x_{t+1} (the ids rolled by one: what the rows' last position takes is
    seen by no position that is counted)."""
    p = params["mtp"]
    B, T = ids.shape
    ahead = _embed(params, jnp.roll(ids, -1, axis=1), c)
    with model_scope("mtp"):
        both = jnp.concatenate(
            [_rms_norm(ahead, p["embed_norm"], c.norm_eps),
             _rms_norm(hn, p["hidden_norm"], c.norm_eps)], axis=-1)
        m = jnp.einsum("btf,fe->bte", both, p["merge"])
    m, counts = _blocks(p["blocks"], MTP_PATTERN, m, c, path, remat, None)
    logits = _head(params, _rms_norm(m, p["final_norm"], c.norm_eps), c)
    with model_scope("loss"):
        per_tok = jnp.where(jnp.arange(T) < T - 2,
                            _token_ce(logits, ids, 2), 0.0)
        return jnp.sum(per_tok) / (B * (T - 2)), per_tok, counts


def _loss_terms(params, batch, config: HybridLMConfig, remat: bool):
    """`lm_loss` with each position's term beside it: (loss,
    (expert_tokens, float32 [B, T] cross entropy of each predicting
    position, 0 elsewhere)). With a multi-token-prediction module the loss
    is ``L_main + mtp_loss_weight * L_mtp``, ``expert_tokens`` has the
    module's row last, and the auxiliary tuple two more members: ``L_mtp``
    and each position's term of it, float32 [B, T]."""
    c = config
    ids = batch["input_ids"]
    seg = batch.get("segment_ids")
    if c.num_nextn_predict_layers and seg is not None:
        raise NotImplementedError("multi-token prediction over packed rows")
    h, counts, path = _trunk(params, ids, c, remat, seg)
    hn = _rms_norm(h, params["final_norm"], c.norm_eps)
    logits = _head(params, hn, c)
    with model_scope("loss"):
        B, T = ids.shape
        # position t predicts ids[t + 1]; the last position predicts
        # nothing and is weighted 0, so the logits are never sliced
        per_tok = _token_ce(logits, ids, 1)
        predicts = jnp.arange(T) < T - 1
        if seg is None:
            per_tok = jnp.where(predicts, per_tok, 0.0)
            loss = jnp.sum(per_tok) / (B * (T - 1))
        else:
            predicts = predicts & (jnp.roll(seg, -1, axis=1) == seg)
            per_tok = jnp.where(predicts, per_tok, 0.0)
            loss = jnp.sum(per_tok) / jnp.maximum(jnp.sum(predicts), 1)
    if not c.num_nextn_predict_layers:
        return loss, (_stacked(counts, c), per_tok)
    mtp, mtp_per_tok, more = _mtp_loss(params, hn, ids, c, path, remat)
    return (loss + c.mtp_loss_weight * mtp,
            (_stacked(counts + more, c), per_tok, mtp, mtp_per_tok))


def make_train_step(config: HybridLMConfig, mesh=None,
                    learning_rate=1e-4, remat: bool = True):
    """Single jitted train step, built as `bert.make_train_step`:
    ``(params, opt_state, batch, iteration) -> (params, opt_state, aux)``
    with params and state donated, ``aux = {"loss", "expert_tokens":
    int32 [n_expert_blocks, held]}`` and, for a batch of packed rows
    (``segment_ids``), ``"token_loss"``: float32 [B, T], each predicting
    position's cross entropy (what a job logs by document). With a
    multi-token-prediction module ``loss`` is ``L_main + mtp_loss_weight *
    L_mtp``, ``aux["mtp_loss"]`` is ``L_mtp``, ``aux["mtp_token_loss"]``
    each position's term of it (float32 [B, T], 0 from T - 2 on) and
    ``expert_tokens`` has the module's ``E`` block as its last row. ``remat`` recomputes each block in
    the backward pass (`jax.checkpoint` around one block), all but a
    streaming flash core's output and log-sum-exp, which it keeps from the
    first forward so that the forward kernel runs once (`_blocks`).
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
        return _loss_terms(params, batch, config, remat)

    def step(params, opt_state, batch, iteration):
        (loss, terms), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        rate = (learning_rate(iteration) if callable(learning_rate)
                else learning_rate)
        new_params, opt_state = _optim.adam_apply(
            params, grads, opt_state, rate, iteration)
        aux = {"loss": loss, "expert_tokens": terms[0]}
        if "segment_ids" in batch:
            aux["token_loss"] = terms[1]
        if config.num_nextn_predict_layers:
            aux["mtp_loss"], aux["mtp_token_loss"] = terms[2], terms[3]
        return new_params, opt_state, aux

    return counted_jit(step, tag=f"hybrid_lm_train:{id(step)}",
                       donate_argnums=(0, 1))


# -- counters -----------------------------------------------------------------

def observe(aux, config: HybridLMConfig, tokens: int) -> float:
    """Read a step's loss on the host and feed the expert-load counters
    from its ``aux`` (blocks until that step is done; ``tokens`` is the
    step's B x T; a step with a multi-token-prediction module also feeds
    ``dl4j_mtp_positions_total``, the B x (T - 2) positions that loss was
    taken over, from the shape of ``aux["mtp_token_loss"]``):
    ``dl4j_moe_assignments_total`` (tokens x top-k routed, over all
    experts), ``dl4j_moe_held_assignments_total`` (those that fell on
    experts held here), ``dl4j_moe_expert_tokens_total{block,expert}`` and
    the gauge ``dl4j_moe_max_expert_tokens`` (the fullest held expert of
    the step). Returns the loss."""
    from ..common.environment import environment
    loss, counts = jax.device_get((aux["loss"], aux["expert_tokens"]))
    counts = np.asarray(counts)
    if "mtp_token_loss" in aux:
        B, T = aux["mtp_token_loss"].shape
        environment().metrics().counter(
            "dl4j_mtp_positions_total",
            "Positions the multi-token-prediction loss was taken over "
            "(B x (T - 2) a step)").inc(B * max(T - 2, 0))
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


def observe_packed(lengths) -> None:
    """Feed the packing counters from a step's own rows, on the host, with
    no device read: ``lengths`` is one sequence of document lengths a row
    (what the batch's ``segment_ids`` were made from).
    ``dl4j_packed_rows_total``, ``dl4j_packed_documents_total`` and
    ``dl4j_packed_attended_pairs_total``: the (query, key) pairs of one
    head's causal attention inside documents, a row's sum of
    ``len (len + 1) / 2``. ``dl4j_flash_doc_tiles_total{kind}``: one
    head's causal grid of the streaming flash kernels at the row's length,
    ``skipped`` (no pair of one document), ``whole`` (inside one) or
    ``boundary`` (`flash_attention.document_tiles`)."""
    from ..common.environment import environment
    from ..kernels.flash_attention import document_tiles
    reg = environment().metrics()
    tiles = reg.counter("dl4j_flash_doc_tiles_total",
                        "Tiles of one head's causal flash-attention grid "
                        "over the packed rows trained on: skipped (no pair "
                        "of one document), whole (inside one document) or "
                        "boundary", labels=("kind",))
    for row in lengths:
        for kind, n in document_tiles(row).items():
            tiles.labels(kind=kind).inc(n)
    reg.counter("dl4j_packed_rows_total",
                "Packed rows trained on").inc(len(lengths))
    reg.counter("dl4j_packed_documents_total",
                "Documents (and the pieces a row's end cut them into) in "
                "the packed rows trained on").inc(
                    sum(len(row) for row in lengths))
    reg.counter("dl4j_packed_attended_pairs_total",
                "Same-document causal (query, key) pairs of one attention "
                "head over the packed rows trained on").inc(
                    sum(int(n) * (int(n) + 1) // 2
                        for row in lengths for n in row))
