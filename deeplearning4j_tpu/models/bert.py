"""BERT: the flagship transformer, TPU-first.

Reference context: the reference runs BERT only via TF-frozen-graph import
(`samediff-import`, BASELINE.md config 3). Here BERT is a native model with
first-class sharding — the component the reference never had (SURVEY.md §2.4:
TP/SP/PP absent) and the north-star benchmark target (≥35% MFU).

Design:
- Pure-functional params pytree; bfloat16 activations/weights, f32 layernorm
  and softmax accumulation (MXU-native mixed precision).
- Megatron-style tensor parallelism via sharding annotations: attention
  heads and MLP hidden sharded over `tensor`; XLA/GSPMD inserts the
  all-reduces. No hand-written collectives in the model body.
- Sequence parallelism: attention dispatches to ring attention (shard_map
  over `seq`) when the mesh has a seq axis > 1.
- One jitted train step: fwd + masked-LM loss + bwd + Adam, params donated.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.tracing import model_scope
from ..kernels import attention, attention_dispatch
from ..parallel.mesh import DATA, FSDP, PIPE, SEQ, TENSOR
from ..quant.transforms import (dequant_matmul, dequantize, take_rows,
                                tied_logits)
from . import _optim
from ..parallel.ring_attention import blockwise_attention, ring_attention


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @staticmethod
    def base() -> "BertConfig":
        return BertConfig()

    @staticmethod
    def large() -> "BertConfig":
        return BertConfig(hidden_size=1024, num_layers=24, num_heads=16,
                          intermediate_size=4096)

    @staticmethod
    def tiny() -> "BertConfig":
        """For tests/dryruns."""
        return BertConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                          num_heads=4, intermediate_size=128,
                          max_position_embeddings=128)


# -- parameter init -----------------------------------------------------

def init_params(key, config: BertConfig) -> Dict:
    c = config
    dt = c.dtype
    std = 0.02

    def dense(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dt)

    keys = iter(jax.random.split(key, 8 + 8 * c.num_layers))
    params = {
        "embeddings": {
            "word": dense(next(keys), (c.vocab_size, c.hidden_size)),
            "position": dense(next(keys), (c.max_position_embeddings,
                                           c.hidden_size)),
            "token_type": dense(next(keys), (c.type_vocab_size, c.hidden_size)),
            "ln_g": jnp.ones((c.hidden_size,), jnp.float32),
            "ln_b": jnp.zeros((c.hidden_size,), jnp.float32),
        },
        "layers": [],
        "mlm": {
            "dense": dense(next(keys), (c.hidden_size, c.hidden_size)),
            "dense_b": jnp.zeros((c.hidden_size,), dt),
            "ln_g": jnp.ones((c.hidden_size,), jnp.float32),
            "ln_b": jnp.zeros((c.hidden_size,), jnp.float32),
            "bias": jnp.zeros((c.vocab_size,), jnp.float32),
        },
        "pooler": {
            "w": dense(next(keys), (c.hidden_size, c.hidden_size)),
            "b": jnp.zeros((c.hidden_size,), dt),
        },
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


# -- sharding rules (Megatron TP + optional FSDP) ------------------------

def param_specs(config: BertConfig) -> Dict:
    """PartitionSpec tree matching init_params' structure."""
    layer = {
        "attn": {
            "wq": P(FSDP, TENSOR, None), "wk": P(FSDP, TENSOR, None),
            "wv": P(FSDP, TENSOR, None), "wo": P(TENSOR, None, FSDP),
            "bq": P(TENSOR, None), "bk": P(TENSOR, None),
            "bv": P(TENSOR, None), "bo": P(),
        },
        "mlp": {
            "w1": P(FSDP, TENSOR), "b1": P(TENSOR),
            "w2": P(TENSOR, FSDP), "b2": P(),
        },
        "ln1_g": P(), "ln1_b": P(), "ln2_g": P(), "ln2_b": P(),
    }
    return {
        "embeddings": {"word": P(FSDP, None), "position": P(),
                       "token_type": P(), "ln_g": P(), "ln_b": P()},
        "layers": [layer] * config.num_layers,
        "mlm": {"dense": P(FSDP, None), "dense_b": P(), "ln_g": P(),
                "ln_b": P(), "bias": P()},
        "pooler": {"w": P(FSDP, None), "b": P()},
    }


def _ln(x, g, b, eps):
    with model_scope("ln"):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        return ((x32 - mean) * lax.rsqrt(var + eps) * g + b).astype(x.dtype)


# -- forward ------------------------------------------------------------

def _attention(layer_params, h, attention_mask, config: BertConfig,
               mesh: Optional[Mesh], seq_parallel: bool,
               path: str, tp_axis: Optional[str] = None):
    """Multi-head attention. tp_axis: when running INSIDE a shard_map with
    head-sharded weights (the pipeline's Megatron-TP stages), names the
    mesh axis for the explicit f/g collectives (tp_copy before QKV,
    tp_reduce after the output projection); None means replicated weights
    or GSPMD-annotated sharding (XLA inserts the collectives).
    path: the core ``kernels.attention`` runs, as ``encode`` asked (in
    the sequence-parallel ring: whether each K/V block takes the Pallas
    kernel)."""
    a = layer_params["attn"]
    with model_scope("attn"):
        if tp_axis is not None:
            from ..parallel.pipeline import tp_copy
            h_in = tp_copy(h, tp_axis)
        else:
            h_in = h

        # heads stay packed along the last axis, [B, T, H*D], from the
        # projections through the core to the output projection: the
        # kernel takes its blocks straight from that layout, and XLA has
        # no [.., H, 64] minor dimensions to lay out around it
        def proj(w, b):
            w = dequantize(w, h_in.dtype)
            return jnp.einsum("bte,ef->btf", h_in,
                              w.reshape(w.shape[0], -1)) + b.reshape(-1)
        q, k, v = (proj(a["w" + n], a["b" + n]) for n in "qkv")
        with model_scope("attn_core"):
            if seq_parallel and mesh is not None:
                # flash composes with SP: the Pallas kernel computes each
                # K/V block inside the ring (VERDICT r4 #4 / SURVEY §5)
                heads = q.shape[:2] + (-1, config.head_dim)
                ctx = ring_attention(
                    q.reshape(heads), k.reshape(heads), v.reshape(heads),
                    mesh, mask=attention_mask, causal=False,
                    use_flash=path == "flash").reshape(q.shape)
            else:
                ctx = attention(q, k, v, path=path,
                                head_dim=config.head_dim,
                                mask=attention_mask)
        wo = dequantize(a["wo"], ctx.dtype)
        out = jnp.einsum("bqf,fe->bqe", ctx, wo.reshape(-1, wo.shape[-1]))
        if tp_axis is not None:
            from ..parallel.pipeline import tp_reduce
            out = tp_reduce(out, tp_axis)
        return out + a["bo"]


def encode(params, input_ids, token_type_ids=None, attention_mask=None, *,
           config: BertConfig, mesh: Optional[Mesh] = None,
           seq_parallel: bool = False,
           use_flash: Optional[bool] = None):
    """Token ids [B, T] → contextual encodings [B, T, E].

    use_flash None (the default) lets ``kernels.attention_dispatch``
    choose between the Pallas flash kernel and XLA from the shape and the
    backend, once per trace; True asks for the kernel wherever the
    dispatcher grants it; False is the plain XLA reference and never
    asks."""
    c = config
    e = params["embeddings"]
    B, T = input_ids.shape
    with model_scope("embed"):
        h = take_rows(e["word"], input_ids, dtype=c.dtype)
        h = h + e["position"][None, :T]
        if token_type_ids is not None:
            h = h + jnp.take(e["token_type"], token_type_ids, axis=0)
        else:
            h = h + e["token_type"][0]
    h = _ln(h, e["ln_g"], e["ln_b"], c.layer_norm_eps)
    if mesh is not None:
        h = lax.with_sharding_constraint(
            h, NamedSharding(mesh, P((DATA, FSDP), SEQ if seq_parallel else None,
                                     None)))

    if seq_parallel and mesh is not None:
        # the ring is not a core: per K/V block, the kernel only on request
        path = "flash" if use_flash else "xla"
    elif use_flash is False or (use_flash is None and mesh is not None):
        # under GSPMD a ``pallas_call`` has no partitioning rule
        path = "xla"
    else:
        path = attention_dispatch(T, head_dim=c.head_dim)
    for layer in params["layers"]:
        attn_out = _attention(layer, h, attention_mask, c, mesh, seq_parallel,
                              path)
        h = _ln(h + attn_out, layer["ln1_g"], layer["ln1_b"], c.layer_norm_eps)
        mlp = layer["mlp"]
        with model_scope("mlp"):
            inter = jax.nn.gelu(dequant_matmul(h, mlp["w1"]) + mlp["b1"])
            if mesh is not None:
                inter = lax.with_sharding_constraint(
                    inter, NamedSharding(
                        mesh, P((DATA, FSDP), SEQ if seq_parallel else None,
                                TENSOR)))
            mlp_out = dequant_matmul(inter, mlp["w2"]) + mlp["b2"]
        h = _ln(h + mlp_out, layer["ln2_g"], layer["ln2_b"], c.layer_norm_eps)
        if mesh is not None:
            h = lax.with_sharding_constraint(
                h, NamedSharding(mesh, P((DATA, FSDP),
                                         SEQ if seq_parallel else None, None)))
    return h


def mlm_logits(params, encodings, config: BertConfig):
    """Masked-LM head with tied decoder weights."""
    m = params["mlm"]
    with model_scope("head"):
        h = jax.nn.gelu(dequant_matmul(encodings, m["dense"])
                        + m["dense_b"])
        h = _ln(h, m["ln_g"], m["ln_b"], config.layer_norm_eps)
        # tied decoder: per-row scales of a quantized word table fold into
        # the f32 logits
        return tied_logits(h, params["embeddings"]["word"]) + m["bias"]


def pooled(params, encodings):
    return jnp.tanh(dequant_matmul(encodings[:, 0], params["pooler"]["w"])
                    + params["pooler"]["b"])


def mlm_loss(params, batch, config: BertConfig, mesh=None,
             seq_parallel=False, use_flash=None):
    """Masked-LM cross entropy. batch: input_ids, labels (-100 = unmasked),
    attention_mask.

    The vocab softmax-xent stays on XLA's fusion deliberately: a Pallas
    vocab-tiled kernel was measured 0.93x/0.61x (fwd/train) against it at
    the headline shape and deleted (kernels/__init__.py has the numbers)."""
    enc = encode(params, batch["input_ids"],
                 batch.get("token_type_ids"), batch.get("attention_mask"),
                 config=config, mesh=mesh, seq_parallel=seq_parallel,
                 use_flash=use_flash)
    logits = mlm_logits(params, enc, config)
    with model_scope("loss"):
        labels = batch["labels"]
        valid = labels >= 0
        safe_labels = jnp.where(valid, labels, 0)
        lsm = jax.nn.log_softmax(logits, axis=-1)
        per_tok = -jnp.take_along_axis(lsm, safe_labels[..., None],
                                       axis=-1)[..., 0]
        per_tok = jnp.where(valid, per_tok, 0.0)
        return jnp.sum(per_tok) / jnp.maximum(jnp.sum(valid), 1)


# -- training step ------------------------------------------------------

def _make_loss_fn(config, mesh, seq_parallel, remat, use_flash):
    loss_fn = functools.partial(mlm_loss, config=config, mesh=mesh,
                                seq_parallel=seq_parallel,
                                use_flash=use_flash)
    if remat:
        # rematerialize the encoder to trade FLOPs for HBM (checkpointing)
        loss_fn = jax.checkpoint(loss_fn)
    return loss_fn


def _jit_step(fn, config, mesh, seq_parallel):
    """jit a ``(params, opt_state, batch, scalar) -> (params, opt_state,
    aux)`` step with donated params/state and, when a mesh is given, the
    TP/FSDP/SP shardings from param_specs. Routed through ``counted_jit``
    (DL101) so BERT training shares the recompile counters and — for the
    unsharded step — the persistent executable store."""
    from ..runtime.inference import counted_jit

    donate = (0, 1)
    if mesh is None:
        return counted_jit(fn, tag=f"bert_train:{id(fn)}",
                           donate_argnums=donate)
    specs = param_specs(config)
    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    flat_specs = [NamedSharding(mesh, s) for s in
                  jax.tree_util.tree_leaves(
                      specs, is_leaf=lambda x: isinstance(x, P))]
    opt_sh = (flat_specs, flat_specs)
    batch_sh = NamedSharding(mesh, P((DATA, FSDP),
                                     SEQ if seq_parallel else None))
    # batch_sh is a pytree *prefix*: it applies to every entry of the batch
    # dict, whatever keys the caller provides (token_type_ids included)
    return counted_jit(
        fn, tag=f"bert_train:{id(fn)}", donate_argnums=donate,
        in_shardings=(param_sh, opt_sh, batch_sh, None),
        out_shardings=(param_sh, opt_sh, None))


def make_train_step(config: BertConfig, mesh: Optional[Mesh] = None,
                    learning_rate: float = 1e-4, seq_parallel: bool = False,
                    remat: bool = True, use_flash: Optional[bool] = None):
    """Single jitted train step: fwd+bwd+Adam, donated params/state.

    With a mesh: params placed per param_specs (TP/FSDP), batch sharded over
    (data, fsdp), sequence over seq when seq_parallel — XLA emits all ICI
    collectives (the entire reference PS stack, §2.5).
    use_flash: see ``encode`` (None: ``attention_dispatch`` decides).
    """
    loss_fn = _make_loss_fn(config, mesh, seq_parallel, remat, use_flash)

    def step(params, opt_state, batch, iteration):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, opt_state = _optim.adam_apply(
            params, grads, opt_state, learning_rate, iteration)
        return new_params, opt_state, loss

    return _jit_step(step, config, mesh, seq_parallel)


def make_scanned_train_step(config: BertConfig, n_steps: int,
                            mesh: Optional[Mesh] = None,
                            learning_rate: float = 1e-4,
                            seq_parallel: bool = False, remat: bool = True,
                            use_flash: Optional[bool] = None):
    """``n_steps`` chained train steps in ONE dispatch (jitted lax.scan).

    Benchmarks time this rather than N separate calls of make_train_step's
    output: one scan is one execute whose wall time necessarily covers all
    ``n_steps`` of device work, with no host dispatch between steps; the
    returned loss trajectory lets the caller verify that training actually
    stepped (losses must change step to step).

    Signature: ``(params, opt_state, batch, start_iteration) ->
    (params, opt_state, losses[n_steps])`` with params/opt donated.
    """
    loss_fn = _make_loss_fn(config, mesh, seq_parallel, remat, use_flash)

    def scanned(params, opt_state, batch, start_iteration):
        def body(carry, it):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            params, opt_state = _optim.adam_apply(
                params, grads, opt_state, learning_rate, it)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state),
            start_iteration + jnp.arange(n_steps, dtype=jnp.int32))
        return params, opt_state, losses

    return _jit_step(scanned, config, mesh, seq_parallel)


# -- SQuAD-style QA fine-tune head (BASELINE config 3) -------------------

def init_qa_params(key, config: BertConfig) -> Dict:
    """Span-extraction head: start/end logits per token (BERT-for-QA)."""
    w = 0.02 * jax.random.normal(key, (config.hidden_size, 2), jnp.float32)
    return {"w": w.astype(config.dtype),
            "b": jnp.zeros((2,), jnp.float32)}


def qa_logits(params, qa_params, batch, config: BertConfig, mesh=None):
    enc = encode(params, batch["input_ids"], batch.get("token_type_ids"),
                 batch.get("attention_mask"), config=config, mesh=mesh)
    logits = jnp.einsum("bte,ek->btk", enc, qa_params["w"]) \
        .astype(jnp.float32) + qa_params["b"]
    return logits[..., 0], logits[..., 1]      # start, end [B, T]


def qa_loss(params, qa_params, batch, config: BertConfig, mesh=None):
    """Cross entropy over start/end positions (SQuAD objective)."""
    start_logits, end_logits = qa_logits(params, qa_params, batch, config,
                                         mesh)
    mask = batch.get("attention_mask")
    if mask is not None:
        big_neg = jnp.finfo(jnp.float32).min
        start_logits = jnp.where(mask.astype(bool), start_logits, big_neg)
        end_logits = jnp.where(mask.astype(bool), end_logits, big_neg)

    def ce(logits, positions):
        lsm = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(lsm, positions[:, None],
                                             axis=-1)[:, 0])

    return 0.5 * (ce(start_logits, batch["start_positions"]) +
                  ce(end_logits, batch["end_positions"]))


def make_qa_train_step(config: BertConfig, mesh: Optional[Mesh] = None,
                       learning_rate: float = 3e-5):
    """Fine-tune step: encoder + QA head trained jointly (the BASELINE
    config-3 workload: BERT-base SQuAD fine-tune)."""


    def loss_fn(all_params, batch):
        return qa_loss(all_params["bert"], all_params["qa"], batch, config,
                       mesh)

    def step(all_params, opt_state, batch, iteration):
        loss, grads = jax.value_and_grad(loss_fn)(all_params, batch)
        new_params, opt_state = _optim.adam_apply(
            all_params, grads, opt_state, learning_rate, iteration)
        return new_params, opt_state, loss

    from ..runtime.inference import counted_jit
    return counted_jit(step, tag=f"bert_qa:{id(step)}",
                       donate_argnums=(0, 1))


# -- pipeline parallelism (dp x pp) --------------------------------------

def to_pipeline_params(params, n_stages: int):
    """Restructure flat params for the pipeline: encoder layers grouped
    into stages and stacked (leading stage dim); embed/head unchanged."""
    from ..parallel.pipeline import split_stages, stack_stage_params
    groups = split_stages(params["layers"], n_stages)
    return {
        "embeddings": params["embeddings"],
        "stages": stack_stage_params(groups),
        "mlm": params["mlm"],
        "pooler": params["pooler"],
    }


def from_pipeline_params(pp_params):
    """Inverse of to_pipeline_params: unstack stages back to a flat layer
    list (for checkpoint interchange with the non-pipelined layout)."""
    stages = pp_params["stages"]
    n_stages = jax.tree_util.tree_leaves(stages)[0].shape[0]
    layers = []
    for s in range(n_stages):
        layers.extend(jax.tree_util.tree_map(lambda p: p[s], stages))
    return {
        "embeddings": pp_params["embeddings"],
        "layers": layers,
        "mlm": pp_params["mlm"],
        "pooler": pp_params["pooler"],
    }


def pipeline_stage_specs(stages, tensor_parallel: bool = False):
    """Per-leaf PartitionSpecs for stage-stacked params: every leaf sharded
    over `pipe` on the stage dim; with tensor_parallel, attention heads and
    MLP intermediate additionally sharded over `tensor` (Megatron layout,
    the dp x tp x pp 3-axis composition)."""
    if not tensor_parallel:
        return jax.tree_util.tree_map(lambda _: P(PIPE), stages)
    attn = {"wq": P(PIPE, None, TENSOR, None),
            "wk": P(PIPE, None, TENSOR, None),
            "wv": P(PIPE, None, TENSOR, None),
            "bq": P(PIPE, TENSOR, None),
            "bk": P(PIPE, TENSOR, None),
            "bv": P(PIPE, TENSOR, None),
            "wo": P(PIPE, TENSOR, None, None),
            "bo": P(PIPE)}
    mlp = {"w1": P(PIPE, None, TENSOR), "b1": P(PIPE, TENSOR),
           "w2": P(PIPE, TENSOR, None), "b2": P(PIPE)}
    layer = {"attn": attn, "mlp": mlp, "ln1_g": P(PIPE), "ln1_b": P(PIPE),
             "ln2_g": P(PIPE), "ln2_b": P(PIPE)}
    return [layer for _ in stages]


def make_pipeline_train_step(config: BertConfig, mesh: Mesh,
                             n_microbatches: int,
                             learning_rate: float = 1e-4,
                             remat: bool = True,
                             schedule: str = "1f1b",
                             tensor_parallel: bool = False):
    """BERT training with pipeline parallelism over the `pipe` mesh axis,
    composed with data parallelism over (data, fsdp) and, with
    tensor_parallel=True, Megatron TP over `tensor` inside each stage
    (heads/intermediate sharded; psum after the row-parallel matmuls,
    tp_copy marking the activation fan-out) — the full dp x tp x pp
    3-axis composition.

    The reference has no PP at all (SURVEY §2.4) — this is the TPU-first
    differentiator: embed/head are the heterogeneous ends outside the loop,
    the repeated encoder block is the uniform pipelined stage, loss is
    scored on the last stage (scalar psum — no activation broadcast), and
    per-microbatch remat gives the 1F1B memory profile under jax.grad.

    schedule: "1f1b" (default — hand-scheduled interleaved backward,
    activation memory bounded by n_stages) or "gpipe" (autodiff through the
    scan; memory grows with n_microbatches).

    Use with `to_pipeline_params(init_params(...), n_stages)`.
    """

    from ..parallel.pipeline import (make_pipeline_loss,
                                     make_pipeline_loss_1f1b, tp_copy,
                                     tp_reduce)
    c = config
    tp = mesh.shape.get(TENSOR, 1) if tensor_parallel else 1

    tp_axis = TENSOR if tp > 1 else None

    def stage_fn(stage_layers, h):
        # stage_layers: list of layer dicts (this stage's slice); with
        # tp > 1 the attn/mlp leaves are the local TENSOR shard and the
        # math is Megatron column->row parallel per block (explicit f/g
        # collectives via tp_copy/tp_reduce)
        path = attention_dispatch(h.shape[1], head_dim=c.head_dim)
        for layer in stage_layers:
            attn_out = _attention(layer, h, None, c, None, False, path,
                                  tp_axis=tp_axis)
            h = _ln(h + attn_out, layer["ln1_g"], layer["ln1_b"],
                    c.layer_norm_eps)
            mlp = layer["mlp"]
            with model_scope("mlp"):
                hin = tp_copy(h, TENSOR) if tp > 1 else h
                inter = jax.nn.gelu(
                    jnp.einsum("bte,ef->btf", hin, mlp["w1"]) + mlp["b1"])
                part = jnp.einsum("btf,fe->bte", inter, mlp["w2"])
                if tp > 1:
                    part = tp_reduce(part, TENSOR)
                mlp_out = part + mlp["b2"]
            h = _ln(h + mlp_out, layer["ln2_g"], layer["ln2_b"],
                    c.layer_norm_eps)
        return h

    def head_fn(head_params, y, aux):
        m = head_params["mlm"]
        with model_scope("head"):
            h = jax.nn.gelu(jnp.einsum("bte,ef->btf", y, m["dense"])
                            + m["dense_b"])
            h = _ln(h, m["ln_g"], m["ln_b"], c.layer_norm_eps)
            logits = jnp.einsum("bte,ve->btv", h, head_params["word"])
            logits = logits.astype(jnp.float32) + m["bias"]
        with model_scope("loss"):
            labels = aux["labels"]
            valid = labels >= 0
            safe = jnp.where(valid, labels, 0)
            lsm = jax.nn.log_softmax(logits, axis=-1)
            per_tok = -jnp.take_along_axis(lsm, safe[..., None],
                                           axis=-1)[..., 0]
            per_tok = jnp.where(valid, per_tok, 0.0)
            return jnp.sum(per_tok), jnp.sum(valid).astype(jnp.float32)

    # per-leaf specs only needed for tp; the default P(pipe) blanket
    # otherwise (spec trees act as pytree prefixes of the stage params)
    n_stages = max(mesh.shape.get(PIPE, 1), 1)
    per_stage = max(c.num_layers // n_stages, 1)
    specs = (pipeline_stage_specs(range(per_stage), tensor_parallel=True)
             if tp > 1 else None)

    if schedule == "1f1b":
        pipe_loss = make_pipeline_loss_1f1b(stage_fn, head_fn, mesh,
                                            n_microbatches,
                                            param_specs=specs)
    elif schedule == "gpipe":
        pipe_loss = make_pipeline_loss(stage_fn, head_fn, mesh,
                                       n_microbatches, remat=remat,
                                       param_specs=specs)
    else:
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         "(expected '1f1b' or 'gpipe')")

    def loss_fn(params, batch):
        e = params["embeddings"]
        ids = batch["input_ids"]
        B, T = ids.shape
        with model_scope("embed"):
            h = jnp.take(e["word"], ids, axis=0) + e["position"][None, :T]
            tt = batch.get("token_type_ids")
            h = h + (jnp.take(e["token_type"], tt, axis=0)
                     if tt is not None else e["token_type"][0])
        h = _ln(h, e["ln_g"], e["ln_b"], c.layer_norm_eps)
        head_params = {"mlm": params["mlm"], "word": e["word"]}
        aux = {"labels": batch["labels"]}
        loss_sum, wsum = pipe_loss(params["stages"], head_params, h, aux)
        return loss_sum / jnp.maximum(wsum, 1.0)

    def step(params, opt_state, batch, iteration):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, opt_state = _optim.adam_apply(
            params, grads, opt_state, learning_rate, iteration)
        return new_params, opt_state, loss

    from ..runtime.inference import counted_jit
    step = counted_jit(step, tag=f"bert_pipeline:{id(loss_fn)}",
                       donate_argnums=(0, 1))
    step.loss_fn = loss_fn  # exposed for grad-level parity tests
    return step


def place_pipeline_params(pipe_params, mesh: Mesh,
                          tensor_parallel: bool = False):
    """Stage-stacked leaves sharded over pipe (and tensor when
    tensor_parallel); embed/head replicated."""
    def repl(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P())), tree)

    stage_specs = pipeline_stage_specs(pipe_params["stages"],
                                       tensor_parallel)
    stages = jax.tree_util.tree_map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        pipe_params["stages"], stage_specs,
        is_leaf=lambda x: isinstance(x, P) or isinstance(x, jax.Array))

    return {
        "embeddings": repl(pipe_params["embeddings"]),
        "stages": stages,
        "mlm": repl(pipe_params["mlm"]),
        "pooler": repl(pipe_params["pooler"]),
    }


def init_opt_state(params):
    flat = jax.tree_util.tree_leaves(params)
    zeros = [jnp.zeros(p.shape, jnp.float32) for p in flat]
    return (zeros, [jnp.zeros(p.shape, jnp.float32) for p in flat])


def place_opt_state(opt_state, config: BertConfig, mesh: Mesh):
    """Shard an Adam state (u_list, m_list) onto the mesh with the same
    per-param specs the train step pins (needed when restoring committed
    arrays, e.g. an orbax checkpoint, into the jitted step)."""
    specs = param_specs(config)
    flat_specs = [NamedSharding(mesh, s) for s in
                  jax.tree_util.tree_leaves(
                      specs, is_leaf=lambda x: isinstance(x, P))]
    u, m = opt_state
    return ([jax.device_put(a, s) for a, s in zip(u, flat_specs)],
            [jax.device_put(a, s) for a, s in zip(m, flat_specs)])


def place_params(params, config: BertConfig, mesh: Mesh):
    """Shard an (host/replicated) param tree onto the mesh per param_specs."""
    specs = param_specs(config)
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs,
        is_leaf=lambda x: isinstance(x, P) or isinstance(x, jax.Array))


def flops_per_token(config: BertConfig) -> float:
    """Training FLOPs/token ≈ 6 * params_active + attention terms (for MFU)."""
    c = config
    E, F, L = c.hidden_size, c.intermediate_size, c.num_layers
    per_layer = 4 * E * E + 2 * E * F  # qkv+o projections + mlp matmuls
    embed_head = c.vocab_size * E      # tied mlm decoder matmul
    matmul_params = L * per_layer + embed_head + E * E
    return 6.0 * matmul_params


def count_params(params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
