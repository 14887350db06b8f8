"""Operations and bytes the *algorithm* of the dense `granitemoehybrid`
layer stack needs on packed rows, from shapes and the packing alone
(`work.py`'s rule: nothing here looks at which path or kernel the program
took, and recomputation is never counted). Counts are multiply-adds times
two. Norms, activations, the conv (4 taps), softmax and the embedding
lookup are left out (under 1% of the matmul work at these widths).

Attention is counted over the (query, key) pairs a packed row really
holds: the causal pairs inside documents, `packing["attended_pairs"]` as
the driver fed them (a row's sum of len (len + 1) / 2), not T (T + 1) / 2.
A kernel that skips what lies between documents can therefore reach, and
not pass, 100%.

`cfg` is a configuration file's dict with the published key names;
`packing` is `{"rows", "documents", "attended_pairs", "tokens_per_row"}`
summed over the steps in question (`window["packing"]`).
"""
from __future__ import annotations

from benchmark.work_hybrid import BF16, F32, _least


def _d(cfg):
    H, P = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    G, N = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    E, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    D = E // heads
    return dict(E=E, V=int(cfg["vocab_size"]), H=H, P=P, G=G, N=N,
                Q=int(cfg["mamba_chunk_size"]), d_inner=H * P,
                conv_dim=H * P + 2 * G * N, q=heads * D,
                kv=int(cfg["num_key_value_heads"]) * D,
                F=int(cfg["shared_intermediate_size"]),
                mamba=cfg["layer_types"].count("mamba"),
                attention=cfg["layer_types"].count("attention"))


def pairs_per_token(packing) -> float:
    """Same-document causal pairs of one head, a token of the rows."""
    return packing["attended_pairs"] / (packing["rows"]
                                        * packing["tokens_per_row"])


def scan_flops_per_token(cfg) -> int:
    """Forward FLOPs of the chunked (SSD) recurrence for one token, chunk
    Q, as `work_hybrid` counts them: C.B^T over the chunk per group, the
    decay-weighted product with the chunk's inputs, the token's part of
    its chunk's state and the entering state's part of its output. (Chunks
    that a boundary cuts are counted whole: the chunk is the algorithm's
    tile.)"""
    d = _d(cfg)
    return (2 * d["Q"] * d["N"] * d["G"] + 2 * d["Q"] * d["P"] * d["H"]
            + 4 * d["P"] * d["N"] * d["H"])


def mamba_mixer_flops_per_token(cfg) -> int:
    d = _d(cfg)
    return 2 * (d["E"] * (d["d_inner"] + d["conv_dim"] + d["H"])
                + d["d_inner"] * d["E"]) + scan_flops_per_token(cfg)


def mlp_flops_per_token(cfg) -> int:
    """E -> 2 F (gate and value), F -> E."""
    d = _d(cfg)
    return 6 * d["E"] * d["F"]


def attention_core_flops_per_token(cfg, packing) -> float:
    """Q.K^T and P.V over the query heads' width, the pairs a token's
    document gives it."""
    return 4 * pairs_per_token(packing) * _d(cfg)["q"]


def attention_mixer_flops_per_token(cfg, packing) -> float:
    d = _d(cfg)
    return (2 * (2 * d["E"] * d["q"] + 2 * d["E"] * d["kv"])
            + attention_core_flops_per_token(cfg, packing))


def head_flops_per_token(cfg) -> int:
    d = _d(cfg)
    return 2 * d["E"] * d["V"]


def lm_forward_flops_per_token(cfg, packing) -> float:
    d = _d(cfg)
    return (d["mamba"] * mamba_mixer_flops_per_token(cfg)
            + d["attention"] * attention_mixer_flops_per_token(cfg, packing)
            + (d["mamba"] + d["attention"]) * mlp_flops_per_token(cfg)
            + head_flops_per_token(cfg))


def lm_train_flops_per_token(cfg, packing) -> float:
    """Forward + backward (2x forward); recomputation is not counted."""
    return 3 * lm_forward_flops_per_token(cfg, packing)


def scan_step_min_seconds(cfg, tokens: int, peak: dict) -> dict:
    """The least time the scans of one training step can take: forward and
    backward of every mamba layer over `tokens` tokens. Bytes, a token and
    a layer: forward reads x (d_inner), B and C (G N each) in bf16 and dt
    (H, f32) and writes y (d_inner, bf16); backward reads those and dy and
    writes dx, dB, dC and ddt."""
    d = _d(cfg)
    inputs = BF16 * (d["d_inner"] + 2 * d["G"] * d["N"]) + F32 * d["H"]
    y = BF16 * d["d_inner"]
    nbytes = d["mamba"] * tokens * ((inputs + y) + (inputs + y + inputs))
    return _least(3 * d["mamba"] * tokens * scan_flops_per_token(cfg),
                  nbytes, peak)


def attn_core_step_min_seconds(cfg, tokens: int, packing: dict,
                               peak: dict) -> dict:
    """The least time the attention cores of one training step can take:
    forward and backward of every attention layer over `tokens` tokens of
    rows packed as `packing`. Bytes, a token and a layer, bf16: forward
    reads q (H D) and k, v (H_kv D each) and writes the context (H D);
    backward reads those and the context's cotangent and writes dq, dk,
    dv."""
    d = _d(cfg)
    fwd = BF16 * (2 * d["q"] + 2 * d["kv"])
    bwd = fwd + BF16 * d["q"] + BF16 * (d["q"] + 2 * d["kv"])
    return _least(
        3 * d["attention"] * tokens
        * attention_core_flops_per_token(cfg, packing),
        d["attention"] * tokens * (fwd + bwd), peak)
