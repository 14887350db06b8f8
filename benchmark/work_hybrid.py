"""Operations and bytes the *algorithm* of a `nemotron_h` backbone needs,
from shapes alone (`work.py`'s rule: nothing here looks at which path or
kernel the program took, and recomputation is never counted). Counts are
multiply-adds times two. Norms, activations, the conv (4 taps), softmax,
the router's top-k and the embedding lookup are left out (under 1% of the
matmul work at these widths).

`cfg` is a configuration file's dict with the published key names; its
`n_routed_experts` counts the experts held on the chip and
`published.n_routed_experts` is the router's width.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _d(cfg):
    pub = cfg.get("published", {})
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return dict(
        E=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]), H=H, P=P, G=G,
        N=N, Q=int(cfg["chunk_size"]), d_inner=H * P,
        conv_dim=H * P + 2 * G * N,
        q=int(cfg["num_attention_heads"]) * int(cfg["head_dim"]),
        kv=int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
        held=int(cfg["n_routed_experts"]),
        experts=int(pub.get("n_routed_experts", cfg["n_routed_experts"])),
        top_k=int(cfg["num_experts_per_tok"]),
        F=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["moe_shared_expert_intermediate_size"]))


def scan_flops_per_token(cfg) -> int:
    """Forward FLOPs of the chunked (SSD) recurrence for one token, chunk
    Q: C.B^T over the chunk per group (2 Q N G), the decay-weighted
    product with the chunk's inputs (2 Q P H), the token's part of its
    chunk's state (2 P N H) and the entering state's part of its output
    (2 P N H)."""
    d = _d(cfg)
    return (2 * d["Q"] * d["N"] * d["G"] + 2 * d["Q"] * d["P"] * d["H"]
            + 4 * d["P"] * d["N"] * d["H"])


def mamba_block_flops_per_token(cfg) -> int:
    """The two projections (E -> d_inner + conv_dim + H, d_inner -> E)
    and the scan."""
    d = _d(cfg)
    matmuls = d["E"] * (d["d_inner"] + d["conv_dim"] + d["H"]) \
        + d["d_inner"] * d["E"]
    return 2 * matmuls + scan_flops_per_token(cfg)


def expert_visits_per_token(cfg) -> float:
    """Expected visits of one token to experts held here: top_k x held /
    experts (a uniform router)."""
    d = _d(cfg)
    return d["top_k"] * d["held"] / d["experts"]


def expert_block_flops_per_token(cfg) -> float:
    """The router over all experts, the shared expert, and the expected
    visits to held experts (two matrices each)."""
    d = _d(cfg)
    return (2 * d["E"] * d["experts"] + 4 * d["E"] * d["Fs"]
            + expert_visits_per_token(cfg) * 4 * d["E"] * d["F"])


def attention_block_flops_per_token(cfg, seq_len: int) -> int:
    """Q, K, V, O projections and the causal core over the T/2 keys a
    token sees on average (Q.K^T and P.V over the query heads' width)."""
    d = _d(cfg)
    return (2 * (2 * d["E"] * d["q"] + 2 * d["E"] * d["kv"])
            + 4 * (seq_len // 2) * d["q"])


def head_flops_per_token(cfg) -> int:
    d = _d(cfg)
    return 2 * d["E"] * d["V"]


def lm_forward_flops_per_token(cfg, seq_len: int) -> float:
    per_kind = {"M": mamba_block_flops_per_token(cfg),
                "E": expert_block_flops_per_token(cfg),
                "*": attention_block_flops_per_token(cfg, seq_len)}
    return (sum(per_kind[k] for k in cfg["hybrid_override_pattern"])
            + head_flops_per_token(cfg))


def lm_train_flops_per_token(cfg, seq_len: int) -> float:
    """Forward + backward (2x forward); recomputation is not counted."""
    return 3 * lm_forward_flops_per_token(cfg, seq_len)


def _least(flops, nbytes, peak) -> dict:
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops,
            "bytes": nbytes,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def scan_step_min_seconds(cfg, tokens: int, peak: dict) -> dict:
    """The least time the scans of one training step can take: forward
    and backward of every `M` block over `tokens` tokens. Bytes, a token
    and a block: forward reads x (d_inner), B and C (G N each) in bf16
    and dt (H, f32) and writes y (d_inner, bf16); backward reads those
    and dy and writes dx, dB, dC and ddt."""
    d = _d(cfg)
    blocks = cfg["hybrid_override_pattern"].count("M")
    inputs = BF16 * (d["d_inner"] + 2 * d["G"] * d["N"]) + F32 * d["H"]
    y = BF16 * d["d_inner"]
    nbytes = blocks * tokens * ((inputs + y) + (inputs + y + inputs))
    return _least(3 * blocks * tokens * scan_flops_per_token(cfg), nbytes,
                  peak)


def expert_mm_step_min_seconds(cfg, rows: float, peak: dict) -> dict:
    """The least time the grouped products of one training step can take,
    `rows` being the assignments to held experts summed over the expert
    blocks of the step: per row two products forward (E -> F -> E) and
    four backward. Bytes: every product reads its two operands and writes
    its result once, in bf16 — the held experts' matrices once per
    product whatever the rows, the rows' activations E and F wide."""
    d = _d(cfg)
    blocks = cfg["hybrid_override_pattern"].count("E")
    weights = BF16 * d["held"] * d["E"] * d["F"]
    acts = BF16 * rows * (d["E"] + d["F"])
    # forward: 2 products; backward: 2 for the inputs (read dY and W,
    # write dX), 2 for the weights (read X and dY, write dW)
    nbytes = 6 * (blocks * weights + acts)
    return _least(3 * rows * 4 * d["E"] * d["F"], nbytes, peak)
