"""Operations and bytes the *algorithm* needs, from shapes alone.

Nothing here looks at which attention path, kernel or cache layout the
program took: the same request costs the same work before and after an
optimisation, so a share of a peak computed from it can only rise when
the time falls. All counts are multiply-adds times two. Layer norms,
biases, GELU, softmax and the embedding lookups are left out (under 1% of
the matmul work at these widths).

`cfg` is a configuration file's dict with the published key names
(`hidden_size`, `num_hidden_layers`, `num_attention_heads`,
`intermediate_size`, `vocab_size`).
"""
from __future__ import annotations

BF16_BYTES = 2


def _dims(cfg):
    return (int(cfg["hidden_size"]), int(cfg["intermediate_size"]),
            int(cfg["num_hidden_layers"]), int(cfg["vocab_size"]))


def layer_matmul_params(cfg) -> int:
    """Weights of one block's six matrices: Q, K, V, O and the two of the MLP."""
    E, F, _, _ = _dims(cfg)
    return 4 * E * E + 2 * E * F


def block_flops_per_token(cfg, keys: int) -> int:
    """Forward FLOPs of one block for one token that attends `keys` keys:
    the six matmuls plus Q.K^T and P.V over `keys` rows of width E."""
    E = int(cfg["hidden_size"])
    return 2 * layer_matmul_params(cfg) + 4 * keys * E


def mlm_forward_flops_per_token(cfg, seq_len: int) -> int:
    """BERT MLM forward per token at sequence length T: L blocks with
    bidirectional attention over T keys, the head's E x E transform and the
    tied E x V decoder at every position (as the published pretraining
    code computes it)."""
    E, _, L, V = _dims(cfg)
    return L * block_flops_per_token(cfg, seq_len) + 2 * E * E + 2 * E * V


def mlm_train_flops_per_token(cfg, seq_len: int) -> int:
    """Forward + backward (2x forward); recomputation is not counted."""
    return 3 * mlm_forward_flops_per_token(cfg, seq_len)


def decoder_request_flops(cfg, prompt_tokens: int, generated: int) -> int:
    """Forward FLOPs of serving one request with a causal decoder: every
    prompt token and every generated token but the last goes through the L
    blocks once, attending the positions up to and including its own; the
    tied head runs once per generated token."""
    E, _, L, V = _dims(cfg)
    n = prompt_tokens + max(generated - 1, 0)      # tokens through the blocks
    keys_total = n * (n + 1) // 2                  # sum of (p + 1), p < n
    return (L * (n * 2 * layer_matmul_params(cfg) + 4 * keys_total * E)
            + generated * 2 * E * V)


def decoder_weight_bytes(cfg) -> int:
    """bf16 bytes a decode step has to read whatever the batch: the blocks'
    matrices and the word table (it is the tied head)."""
    E, _, L, V = _dims(cfg)
    return BF16_BYTES * (L * layer_matmul_params(cfg) + V * E)


def decode_step_min_seconds(cfg, active: int, committed_rows: int,
                            peak: dict) -> dict:
    """The least time one decode step can take on a chip with `peak`
    (`bf16_flops_per_s`, `hbm_bytes_per_s`): `active` sequences each
    advance one token, together holding `committed_rows` KV rows. Bytes:
    the weights once and every committed K and V row once (E bf16 values a
    row, a layer). FLOPs: one token per active sequence through the blocks
    over its rows, plus the head."""
    E, _, L, V = _dims(cfg)
    kv_bytes = committed_rows * 2 * E * L * BF16_BYTES
    nbytes = decoder_weight_bytes(cfg) + kv_bytes
    flops = (active * (L * 2 * layer_matmul_params(cfg) + 2 * E * V)
             + 4 * committed_rows * E * L)
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops,
            "bytes": nbytes,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}
