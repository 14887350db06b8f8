"""Decoder-only causal language model: the generative serving workload.

Reference context: the serving stack built through PR 6 only does one-shot
``predict`` — the reference ecosystem has no autoregressive serving path at
all. This model is the minimal decoder-only transformer that exercises the
generative fast path (``runtime.generation.DecodeEngine``): it reuses the
BERT block layout (post-LN residual blocks, f32 layernorm/softmax
accumulation, tied word-embedding head) with a causal mask and a
*cache-aware* attention so the same parameters serve three call shapes:

- ``forward``   — full-sequence causal forward ``[B, T] -> [B, T, V]``
  (training/eval, and the honest "recompute the whole prefix every token"
  reference the ``generative_decode`` bench measures against);
- ``prefill``   — fill one slot of a preallocated KV cache from a padded
  prompt in one fixed-shape dispatch and return the next-token logits;
- ``decode``    — one token per active slot against the cache (the O(1)
  per-token step; ``kernels.attention_dispatch`` routes this seq-len-1
  shape to the XLA attention path unconditionally).

KV cache layouts. The *paged* layout (PagedAttention, Kwon et al. 2023)
is what ``DecodeEngine`` serves from::

    {"k": [num_blocks, layers, block_size, heads, head_dim],
     "v": [num_blocks, layers, block_size, heads, head_dim]}

plus a per-slot **block table** ``[slots, max_blocks]`` of pool indices:
a sequence at length L only holds ``ceil(L/block_size)`` blocks, so long
and short requests share one memory budget instead of each reserving
``max_ctx`` rows. Block 0 is a scratch block: table entries past a
slot's allocated count point at it, so fixed-shape writes of padding
rows land somewhere harmless (every read of scratch content is masked
by the per-slot length). The legacy slab layout
``{"k"/"v": [slots, layers, max_ctx, heads, head_dim]}`` is kept as the
single-slot reference path — and is exactly the paged layout with
``block_size == max_ctx`` and one block per slot.

Rows at positions ``> lengths[slot]`` are masked out of every attention —
stale rows left by a previous occupant of the slot (or a freshly
re-allocated block) can never leak into a new request (the poison-value
test in tests/test_generation.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..common.tracing import model_scope
from ..kernels import attention, attention_dispatch, paged_flash_decode
from ..quant.transforms import (dequant_matmul, dequantize, take_rows,
                                tied_logits)
from .bert import _ln


@dataclasses.dataclass
class CausalLMConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def tiny() -> "CausalLMConfig":
        """For tests/dryruns: f32 so the cached decode path is numerically
        interchangeable with the full-recompute forward (token-identical
        greedy continuations)."""
        return CausalLMConfig(vocab_size=97, hidden_size=64, num_layers=2,
                              num_heads=4, intermediate_size=128,
                              max_position_embeddings=256,
                              dtype=jnp.float32)


# -- parameters ----------------------------------------------------------

def init_params(key, config: CausalLMConfig) -> Dict:
    c = config
    dt = c.dtype
    std = 0.02

    def dense(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dt)

    keys = iter(jax.random.split(key, 2 + 8 * c.num_layers))
    params = {
        "embeddings": {
            "word": dense(next(keys), (c.vocab_size, c.hidden_size)),
            "position": dense(next(keys), (c.max_position_embeddings,
                                           c.hidden_size)),
            "ln_g": jnp.ones((c.hidden_size,), jnp.float32),
            "ln_b": jnp.zeros((c.hidden_size,), jnp.float32),
        },
        "layers": [],
    }
    H, Dh, E, F = c.num_heads, c.head_dim, c.hidden_size, c.intermediate_size
    for _ in range(c.num_layers):
        params["layers"].append({
            "attn": {
                "wq": dense(next(keys), (E, H, Dh)),
                "wk": dense(next(keys), (E, H, Dh)),
                "wv": dense(next(keys), (E, H, Dh)),
                "wo": dense(next(keys), (H, Dh, E)),
                "bq": jnp.zeros((H, Dh), dt), "bk": jnp.zeros((H, Dh), dt),
                "bv": jnp.zeros((H, Dh), dt), "bo": jnp.zeros((E,), dt),
            },
            "mlp": {
                "w1": dense(next(keys), (E, F)), "b1": jnp.zeros((F,), dt),
                "w2": dense(next(keys), (F, E)), "b2": jnp.zeros((E,), dt),
            },
            "ln1_g": jnp.ones((E,), jnp.float32),
            "ln1_b": jnp.zeros((E,), jnp.float32),
            "ln2_g": jnp.ones((E,), jnp.float32),
            "ln2_b": jnp.zeros((E,), jnp.float32),
        })
    return params


def init_kv_cache(config: CausalLMConfig, slots: int, max_ctx: int) -> Dict:
    """Preallocated per-slot KV cache (see module docstring for layout)."""
    c = config
    if max_ctx > c.max_position_embeddings:
        raise ValueError(
            f"max_ctx {max_ctx} exceeds max_position_embeddings "
            f"{c.max_position_embeddings}")
    shape = (int(slots), c.num_layers, int(max_ctx), c.num_heads, c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


# -- shared block pieces -------------------------------------------------

def _mlp_ln(layer, h, attn_out, c: CausalLMConfig):
    """The post-attention half of a block: residual+LN, MLP, residual+LN."""
    h = _ln(h + attn_out, layer["ln1_g"], layer["ln1_b"], c.layer_norm_eps)
    mlp = layer["mlp"]
    # dequant_matmul == einsum("...e,ef->...f") for plain weights, and the
    # int8/fp8-at-rest contraction for a quantized twin
    with model_scope("mlp"):
        inter = jax.nn.gelu(dequant_matmul(h, mlp["w1"]) + mlp["b1"])
        mlp_out = dequant_matmul(inter, mlp["w2"]) + mlp["b2"]
    return _ln(h + mlp_out, layer["ln2_g"], layer["ln2_b"], c.layer_norm_eps)


def _embed(params, input_ids, positions, c: CausalLMConfig):
    e = params["embeddings"]
    with model_scope("embed"):
        h = take_rows(e["word"], input_ids, dtype=c.dtype)
        h = h + jnp.take(e["position"], positions, axis=0)
    return _ln(h, e["ln_g"], e["ln_b"], c.layer_norm_eps)


def _lm_logits(params, h):
    """Tied word-embedding head, f32 logits (per-row scales of a
    quantized word table multiply the logits)."""
    with model_scope("head"):
        return tied_logits(h, params["embeddings"]["word"])


_BIG_NEG = jnp.finfo(jnp.float32).min


def _causal_block(layer, h, c: CausalLMConfig, path: str = "xla"):
    """Full-sequence causal attention block on the core ``path`` names
    (``kernels.attention``). Returns (h, (k, v)) with k/v [B, T, H, Dh] so
    prefill can bulk-write them into the cache."""
    a = layer["attn"]
    with model_scope("attn"):
        q = jnp.einsum("bte,ehd->bthd", h,
                       dequantize(a["wq"], h.dtype)) + a["bq"]
        k = jnp.einsum("bte,ehd->bthd", h,
                       dequantize(a["wk"], h.dtype)) + a["bk"]
        v = jnp.einsum("bte,ehd->bthd", h,
                       dequantize(a["wv"], h.dtype)) + a["bv"]
        with model_scope("attn_core"):
            ctx = attention(q, k, v, path=path, head_dim=c.head_dim,
                            causal=True)
        out = jnp.einsum("bqhd,hde->bqe", ctx,
                         dequantize(a["wo"], h.dtype)) + a["bo"]
    return _mlp_ln(layer, h, out, c), (k, v)


# -- the three call shapes -----------------------------------------------

def forward(params, input_ids, config: CausalLMConfig,
            use_flash: bool = False):
    """Full causal forward: token ids [B, T] -> next-token logits
    [B, T, V] (f32). This is the recompute path — O(T²) work per generated
    token when used for decoding, which is exactly what the KV-cached
    ``prefill``/``decode`` pair exists to avoid."""
    B, T = input_ids.shape
    h = _embed(params, input_ids, jnp.arange(T)[None, :], config)
    path = (attention_dispatch(T, head_dim=config.head_dim) if use_flash
            else "xla")
    for layer in params["layers"]:
        h, _ = _causal_block(layer, h, config, path)
    return _lm_logits(params, h)


def prefill(params, cache, input_ids, slot, length, config: CausalLMConfig):
    """Fill ``slot`` of the KV cache from a padded prompt in ONE dispatch.

    ``input_ids`` [1, T] is the prompt zero-padded to its bucket; ``length``
    (traced scalar) is the real prompt length. All T rows of the slot are
    written — rows >= length hold padding garbage that the decode masks
    out (and overwrites as generation proceeds). Returns
    ``(cache, logits[V])`` with the logits taken at position length-1,
    i.e. the distribution of the first generated token.
    """
    c = config
    h = _embed(params, input_ids, jnp.arange(input_ids.shape[1])[None, :], c)
    ks, vs = [], []
    for layer in params["layers"]:
        h, (k, v) = _causal_block(layer, h, c)
        ks.append(k[0])            # [T, H, Dh]
        vs.append(v[0])
    upd_k = jnp.stack(ks)[None].astype(cache["k"].dtype)  # [1, L, T, H, Dh]
    upd_v = jnp.stack(vs)[None].astype(cache["v"].dtype)
    start = (slot, 0, 0, 0, 0)
    with model_scope("kv_write"):
        cache = {"k": lax.dynamic_update_slice(cache["k"], upd_k, start),
                 "v": lax.dynamic_update_slice(cache["v"], upd_v, start)}
    last = lax.dynamic_index_in_dim(h[0], length - 1, axis=0,
                                    keepdims=False)
    return cache, _lm_logits(params, last)


def decode(params, cache, tokens, lengths, config: CausalLMConfig):
    """One KV-cached decode step over every slot.

    ``tokens`` [S] is each slot's current token (position ``lengths[s]``),
    ``lengths`` [S] how many tokens the slot's cache already holds. The
    step writes each token's K/V at its position and attends over
    positions ``0..lengths[s]`` — O(max_ctx) work per token instead of a
    full-prefix recompute. Returns ``(cache, logits[S, V])``.

    The query is seq-len-1, so ``kernels.attention_dispatch`` pins this
    step to the XLA attention path whatever its rule says (a 1-row query
    can never amortize the Pallas kernel's blocking).
    """
    c = config
    S = tokens.shape[0]
    C = cache["k"].shape[2]
    positions = jnp.clip(lengths, 0, c.max_position_embeddings - 1)
    h = _embed(params, tokens, positions, c)            # [S, E]
    assert attention_dispatch(1) == "xla"
    key_mask = jnp.arange(C)[None, :] <= lengths[:, None]   # [S, C]
    scale = c.head_dim ** -0.5
    rows = jnp.arange(S)
    cache_k, cache_v = cache["k"], cache["v"]
    for i, layer in enumerate(params["layers"]):
        a = layer["attn"]
        with model_scope("attn"):
            q = jnp.einsum("se,ehd->shd", h,
                           dequantize(a["wq"], h.dtype)) + a["bq"]
            k = jnp.einsum("se,ehd->shd", h,
                           dequantize(a["wk"], h.dtype)) + a["bk"]
            v = jnp.einsum("se,ehd->shd", h,
                           dequantize(a["wv"], h.dtype)) + a["bv"]
            with model_scope("kv_write"):
                cache_k = cache_k.at[rows, i, lengths].set(
                    k.astype(cache_k.dtype), mode="drop")
                cache_v = cache_v.at[rows, i, lengths].set(
                    v.astype(cache_v.dtype), mode="drop")
            with model_scope("kv_read"):
                ks, vs = cache_k[:, i], cache_v[:, i]
            with model_scope("attn_core"):
                att = jnp.einsum("shd,schd->shc", q, ks,
                                 preferred_element_type=jnp.float32) * scale
                att = jnp.where(key_mask[:, None, :], att, _BIG_NEG)
                probs = jax.nn.softmax(att, axis=-1).astype(h.dtype)
                ctx = jnp.einsum("shc,schd->shd", probs, vs)
            out = jnp.einsum("shd,hde->se", ctx,
                             dequantize(a["wo"], h.dtype)) + a["bo"]
        h = _mlp_ln(layer, h, out, c)
    return {"k": cache_k, "v": cache_v}, _lm_logits(params, h)


# -- paged (block-granular) KV cache -------------------------------------

def init_paged_kv_cache(config: CausalLMConfig, num_blocks: int,
                        block_size: int) -> Dict:
    """Block pool ``[num_blocks, layers, block_size, heads, head_dim]``
    (see module docstring). Block 0 is the scratch block the engine's
    allocator never hands out."""
    c = config
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    if num_blocks < 2:
        raise ValueError(
            f"num_blocks must be >= 2 (block 0 is scratch), got "
            f"{num_blocks}")
    shape = (int(num_blocks), c.num_layers, int(block_size), c.num_heads,
             c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def _block_coords(tables, positions, block_size):
    """(block ids, in-block offsets) for token ``positions`` under the
    per-row block ``tables`` — both [R, T] for tables [R, MB]. Positions
    whose block-table column exceeds MB clip to the last column; the
    engine never lets a live position get there (max_ctx <= MB*Bs)."""
    mb = tables.shape[1]
    col = jnp.clip(positions // block_size, 0, mb - 1)
    blk = jnp.take_along_axis(tables, col, axis=1)
    return blk, positions % block_size


def paged_prefill(params, cache, input_ids, tables, lengths,
                  config: CausalLMConfig, start_pos=None):
    """Batched (optionally partial) prefill into the paged cache: fill
    each row's uncached tail in ONE dispatch.

    ``input_ids`` [B, T] are the *tail* tokens zero-padded to the bucket
    (for a cold prefill the tail is the whole prompt), ``tables`` [B, MB]
    each row's block table (unallocated columns -> scratch 0), ``lengths``
    [B] the real total prompt lengths, and ``start_pos`` [B] how many
    leading rows are already committed in the row's blocks (0 = cold; a
    prefix-cache hit attaches those blocks and prefills only positions
    ``start_pos[b]..lengths[b]-1``). Tail row ``j`` sits at absolute
    position ``start_pos[b]+j``; rows past the real tail
    (``j >= lengths[b]-start_pos[b]``) are redirected to the scratch
    block so fixed-shape padding writes can never clobber a committed —
    possibly *shared* — block. Attention runs over the gathered block
    view (cached prefix rows + the tail written this dispatch), masked
    causally at each tail row's absolute position, so a warm tail is
    numerically the same computation the cold prefill performs at those
    positions. Returns ``(cache, logits[B, V])`` with each row's logits
    taken at tail index ``lengths[b]-start_pos[b]-1``: the distribution
    of the row's first generated token."""
    c = config
    B, T = input_ids.shape
    MB = tables.shape[1]
    Bs = cache["k"].shape[2]
    C = MB * Bs
    if start_pos is None:
        start_pos = jnp.zeros((B,), jnp.int32)
    pos = start_pos[:, None] + jnp.arange(T)[None, :]           # [B, T]
    h = _embed(params, input_ids,
               jnp.clip(pos, 0, c.max_position_embeddings - 1), c)
    assert attention_dispatch(T, paged=True) == "paged"
    valid = jnp.arange(T)[None, :] < (lengths - start_pos)[:, None]
    blk, off = _block_coords(tables, pos, Bs)
    blk = jnp.where(valid, blk, 0)          # padding rows -> scratch block
    key_mask = jnp.arange(C)[None, None, :] <= pos[:, :, None]  # [B, T, C]
    scale = c.head_dim ** -0.5
    cache_k, cache_v = cache["k"], cache["v"]
    for i, layer in enumerate(params["layers"]):
        a = layer["attn"]
        with model_scope("attn"):
            q = jnp.einsum("bte,ehd->bthd", h,
                           dequantize(a["wq"], h.dtype)) + a["bq"]
            k = jnp.einsum("bte,ehd->bthd", h,
                           dequantize(a["wk"], h.dtype)) + a["bk"]
            v = jnp.einsum("bte,ehd->bthd", h,
                           dequantize(a["wv"], h.dtype)) + a["bv"]
            with model_scope("kv_write"):
                cache_k = cache_k.at[blk, i, off].set(
                    k.astype(cache_k.dtype), mode="drop")
                cache_v = cache_v.at[blk, i, off].set(
                    v.astype(cache_v.dtype), mode="drop")
            # gather each row's blocks into its contiguous [C] key view:
            # the cached prefix rows plus the tail rows written just above
            with model_scope("kv_read"):
                ks = jnp.take(cache_k[:, i], tables, axis=0).reshape(
                    B, C, c.num_heads, c.head_dim)
                vs = jnp.take(cache_v[:, i], tables, axis=0).reshape(
                    B, C, c.num_heads, c.head_dim)
            with model_scope("attn_core"):
                att = jnp.einsum("bqhd,bchd->bhqc", q, ks,
                                 preferred_element_type=jnp.float32) * scale
                att = jnp.where(key_mask[:, None], att, _BIG_NEG)
                probs = jax.nn.softmax(att, axis=-1).astype(h.dtype)
                ctx = jnp.einsum("bhqc,bchd->bqhd", probs, vs)
            out = jnp.einsum("bqhd,hde->bqe", ctx,
                             dequantize(a["wo"], h.dtype)) + a["bo"]
        h = _mlp_ln(layer, h, out, c)
    last = jnp.take_along_axis(
        h, jnp.clip(lengths - start_pos - 1, 0, T - 1)[:, None, None],
        axis=1)[:, 0]
    return {"k": cache_k, "v": cache_v}, _lm_logits(params, last)


def paged_decode(params, cache, tables, tokens, lengths,
                 config: CausalLMConfig):
    """Cache-aware step over every slot against the paged pool: ``Q=1``
    is the classic single-token decode, ``Q=k+1`` is the speculative
    verify pass (score a drafted continuation in one dispatch).

    ``tokens`` [S, Q] are each slot's next Q tokens (position
    ``lengths[s]+q``), ``lengths`` [S] how many committed rows each
    slot's blocks hold. Writes each token's K/V through the block table,
    then attends over the block pool — either through the Pallas
    paged-flash kernel (``kernels.paged_flash_decode``: the block table
    rides into the kernel as a scalar-prefetch operand and KV blocks
    stream HBM→VMEM with online-softmax accumulation) or the XLA
    block-table gather fallback; both live inside the jitted step, and
    the path is decided at trace time, so the executable set stays fixed
    (zero steady-state recompiles). Returns ``(cache, logits[S, Q, V])``.

    ``kernels.attention_dispatch(Q, paged=True, head_dim=, block_size=)``
    picks the path (``DL4J_TPU_PAGED_KERNEL``: auto routes to the kernel
    on accelerator backends when the pool layout tiles, on/off force);
    the decision ignores ``Q`` by contract so the decode step and the
    ``Q=k+1`` speculative verify always share a path. Both compute the
    same masked softmax over the same rows — greedy decode is
    token-identical across them (regression-gated)."""
    c = config
    S, Q = tokens.shape
    MB = tables.shape[1]
    Bs = cache["k"].shape[2]
    C = MB * Bs
    pos = lengths[:, None] + jnp.arange(Q)[None, :]            # [S, Q]
    h = _embed(params, tokens,
               jnp.clip(pos, 0, c.max_position_embeddings - 1), c)
    path = attention_dispatch(Q, paged=True, head_dim=c.head_dim,
                              block_size=Bs)
    assert path in ("paged", "paged_flash")
    blk, off = _block_coords(tables, pos, Bs)
    key_mask = jnp.arange(C)[None, None, :] <= pos[:, :, None]  # [S, Q, C]
    scale = c.head_dim ** -0.5
    cache_k, cache_v = cache["k"], cache["v"]
    for i, layer in enumerate(params["layers"]):
        a = layer["attn"]
        with model_scope("attn"):
            q = jnp.einsum("sqe,ehd->sqhd", h,
                           dequantize(a["wq"], h.dtype)) + a["bq"]
            k = jnp.einsum("sqe,ehd->sqhd", h,
                           dequantize(a["wk"], h.dtype)) + a["bk"]
            v = jnp.einsum("sqe,ehd->sqhd", h,
                           dequantize(a["wv"], h.dtype)) + a["bv"]
            with model_scope("kv_write"):
                cache_k = cache_k.at[blk, i, off].set(
                    k.astype(cache_k.dtype), mode="drop")
                cache_v = cache_v.at[blk, i, off].set(
                    v.astype(cache_v.dtype), mode="drop")
            if path == "paged_flash":
                # walk the block table in-kernel: each pool block is
                # DMA'd once, straight from its pool position — no
                # gathered copy
                with model_scope("kv_read"):
                    pool_k, pool_v = cache_k[:, i], cache_v[:, i]
                with model_scope("attn_core"):
                    ctx = paged_flash_decode(q, pool_k, pool_v, tables,
                                             lengths, scale=scale)
            else:
                # gather each slot's blocks into its contiguous [C] key
                # view
                with model_scope("kv_read"):
                    ks = jnp.take(cache_k[:, i], tables, axis=0).reshape(
                        S, C, c.num_heads, c.head_dim)
                    vs = jnp.take(cache_v[:, i], tables, axis=0).reshape(
                        S, C, c.num_heads, c.head_dim)
                with model_scope("attn_core"):
                    att = jnp.einsum(
                        "sqhd,schd->shqc", q, ks,
                        preferred_element_type=jnp.float32) * scale
                    att = jnp.where(key_mask[:, None], att, _BIG_NEG)
                    probs = jax.nn.softmax(att, axis=-1).astype(h.dtype)
                    ctx = jnp.einsum("shqc,schd->sqhd", probs, vs)
            out = jnp.einsum("sqhd,hde->sqe", ctx,
                             dequantize(a["wo"], h.dtype)) + a["bo"]
        h = _mlp_ln(layer, h, out, c)
    return {"k": cache_k, "v": cache_v}, _lm_logits(params, h)


def count_params(params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


class CausalLM:
    """Config + params bundled behind the generative-model protocol the
    serving registry and ``DecodeEngine`` duck-type on: ``init_kv_cache``,
    ``prefill``, ``decode`` (and ``forward`` for the recompute path)."""

    def __init__(self, config: Optional[CausalLMConfig] = None,
                 params: Optional[Dict] = None, seed: int = 0):
        self.config = config or CausalLMConfig.tiny()
        self.params = (params if params is not None
                       else init_params(jax.random.key(seed), self.config))

    def init_kv_cache(self, slots: int, max_ctx: int) -> Dict:
        return init_kv_cache(self.config, slots, max_ctx)

    def prefill(self, params, cache, input_ids, slot, length):
        return prefill(params, cache, input_ids, slot, length, self.config)

    def decode(self, params, cache, tokens, lengths):
        return decode(params, cache, tokens, lengths, self.config)

    # paged protocol (what DecodeEngine actually serves from)
    def init_paged_kv_cache(self, num_blocks: int, block_size: int) -> Dict:
        return init_paged_kv_cache(self.config, num_blocks, block_size)

    def paged_prefill(self, params, cache, input_ids, tables, lengths,
                      start_pos=None):
        return paged_prefill(params, cache, input_ids, tables, lengths,
                             self.config, start_pos)

    def paged_decode(self, params, cache, tables, tokens, lengths):
        return paged_decode(params, cache, tables, tokens, lengths,
                            self.config)

    def forward(self, input_ids):
        return forward(self.params, input_ids, self.config)
