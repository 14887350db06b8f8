"""Plain reference for the BertGeneration decoder (Rothe et al. 2020,
arXiv:1907.12461; `BertGenerationDecoder` on the hub): BERT's post-LN block
under a causal mask, learned positions, LayerNorm on the embeddings, no
token types, and the word table as the output head. float32, matmuls at
`highest`; it imports nothing of the program and shares the block with
`bert_mlm.py`.

Departures, both the program's: the head has no bias (the published head
has one, initialised to zero), and GELU is in its tanh form.

For a served request the reference runs ONCE over the prompt with the
tokens that were served (teacher-forced) and reads, at every position that
produced a token, how far the served token's logit lies below the best:
0 where the served token is the reference's own choice. The control does
the same for the token that a lower precision (`fp8` operands) puts first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import bert_mlm as base


def make_flat_params(key, cfg):
    """Parameters as a flat dict, layers stacked; published
    initialisation (N(0, initializer_range), biases 0, LN (1, 0))."""
    E, H, D, F, L, V, P = base.dims(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    flat = {f"layers/{n}": v for n, v in base.stacked_layers(
        jax.random.fold_in(key, 1), cfg).items()}
    flat.update({
        "embeddings/word": base._draw(jax.random.fold_in(key, 1000),
                                      (V, E), std),
        "embeddings/position": base._draw(jax.random.fold_in(key, 1001),
                                          (P, E), std),
        "embeddings/ln_g": jnp.ones((E,), jnp.float32),
        "embeddings/ln_b": jnp.zeros((E,), jnp.float32),
    })
    return flat


def hidden(flat, ids, eps, precision):
    """Final hidden states [B, T, E] of token ids [B, T] under the causal
    mask (right padding changes nothing to its left)."""
    layers, p = base.split_layers(flat)
    T = ids.shape[1]
    h = p["embeddings/word"][ids] + p["embeddings/position"][None, :T]
    h = base._ln(h, p["embeddings/ln_g"], p["embeddings/ln_b"], eps)
    return base.blocks(h, layers, eps, precision, causal=True)


def logits_at(flat, ids, positions, eps, precision="f32"):
    """Logits [B, G, V] at `positions` [B, G] of `ids` [B, T]."""
    h = hidden(flat, ids, eps, precision)
    picked = jnp.take_along_axis(h, positions[..., None], axis=1)
    return base._mm("bge,ve->bgv", picked, flat["embeddings/word"],
                    precision)


def served_gaps(flat32, requests, *, eps, seq_len, gen_len, rows,
                control=None):
    """For each request `(prompt ids, served tokens)`: per served token,
    reference's best logit minus the served token's logit. With
    `control="fp8"` also the same gap for the token the lower precision
    puts first at each position. Requests are padded to one shape
    [rows, seq_len] with up to `gen_len` read positions, so one program
    serves them all. Returns `{"served": [per-request arrays],
    "control": [...] or None}`."""

    @jax.jit
    def block_gaps(flat32, ids, positions, tokens):
        ref = logits_at(flat32, ids, positions, eps)
        best = jnp.max(ref, axis=-1)
        gap = best - jnp.take_along_axis(ref, tokens[..., None],
                                         axis=-1)[..., 0]
        if control is None:
            return gap, gap
        low = jnp.argmax(logits_at(flat32, ids, positions, eps, control),
                         axis=-1)
        return gap, best - jnp.take_along_axis(ref, low[..., None],
                                               axis=-1)[..., 0]

    served, ctl = [], []
    for r0 in range(0, len(requests), rows):
        chunk = requests[r0:r0 + rows]
        ids = np.zeros((rows, seq_len), np.int32)
        pos = np.zeros((rows, gen_len), np.int32)
        tok = np.zeros((rows, gen_len), np.int32)
        for r, (prompt, tokens) in enumerate(chunk):
            seq = list(prompt) + list(tokens)
            if len(seq) > seq_len or len(tokens) > gen_len:
                raise ValueError("request longer than the reference's shape")
            ids[r, :len(seq)] = seq
            # token j was decided by the logits at position P - 1 + j
            pos[r, :len(tokens)] = len(prompt) - 1 + np.arange(len(tokens))
            tok[r, :len(tokens)] = tokens
        g, c = jax.device_get(block_gaps(
            flat32, jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(tok)))
        for r, (_, tokens) in enumerate(chunk):
            served.append(np.asarray(g[r, :len(tokens)], float))
            ctl.append(np.asarray(c[r, :len(tokens)], float))
    return {"served": served, "control": ctl if control else None}
