"""Operations and bytes the *algorithm* of the DeepSeek-V3 family's decoder
needs (the keys of `jdopensource/JoyAI-LLM-Flash`'s `config.json`), from
shapes alone (`work.py`'s rule: nothing here looks at which path or kernel
the program took, and recomputation is never counted). Counts are
multiply-adds times two. Norms, activations, the rotary embedding, softmax,
the router's top-k and the embedding lookups are left out (under 1% of the
matmul work at these widths).

`cfg` is a configuration file's dict with the published key names; its
`n_routed_experts` counts the experts held on the chip and
`published.n_routed_experts` is the router's width; `num_hidden_layers`
counts the layers held, of which the first `first_k_dense_replace` have a
dense MLP; `num_nextn_predict_layers` multi-token-prediction modules add one
more expert layer, a 2E -> E merge and a second pass of the head each.
"""
from __future__ import annotations

BF16 = 2


def _d(cfg):
    pub = cfg.get("published", {})
    layers, dense = int(cfg["num_hidden_layers"]), int(
        cfg["first_k_dense_replace"])
    mtp = int(cfg["num_nextn_predict_layers"])
    return dict(
        E=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        H=int(cfg["num_attention_heads"]), q_rank=int(cfg["q_lora_rank"]),
        kv_rank=int(cfg["kv_lora_rank"]), dn=int(cfg["qk_nope_head_dim"]),
        dr=int(cfg["qk_rope_head_dim"]), dv=int(cfg["v_head_dim"]),
        Fd=int(cfg["intermediate_size"]), F=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        held=int(cfg["n_routed_experts"]),
        experts=int(pub.get("n_routed_experts", cfg["n_routed_experts"])),
        top_k=int(cfg["num_experts_per_tok"]), dense=dense, mtp=mtp,
        attention_layers=layers + mtp, expert_layers=layers - dense + mtp)


def latent_proj_flops_per_token(cfg) -> int:
    """One layer's five latent-attention projections: E -> q_lora_rank -> H
    (d_n + d_r); E -> kv_lora_rank + d_r; kv_lora_rank -> H (d_n + d_v);
    H d_v -> E."""
    d = _d(cfg)
    H = d["H"]
    return 2 * (d["E"] * d["q_rank"] + d["q_rank"] * H * (d["dn"] + d["dr"])
                + d["E"] * (d["kv_rank"] + d["dr"])
                + d["kv_rank"] * H * (d["dn"] + d["dv"])
                + H * d["dv"] * d["E"])


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs of one head over one row: key j <= query i."""
    return seq_len * (seq_len + 1) // 2


def core_flops_per_row(cfg, seq_len: int) -> int:
    """One layer's core over one row, forward: q . k over d_n + d_r and
    p . v over d_v for every causal pair and head."""
    d = _d(cfg)
    return 2 * causal_pairs(seq_len) * d["H"] * (d["dn"] + d["dr"] + d["dv"])


def expert_visits_per_token(cfg) -> float:
    """Expected visits of one token to experts held here: top_k x held /
    experts (a uniform router)."""
    d = _d(cfg)
    return d["top_k"] * d["held"] / d["experts"]


def expert_layer_flops_per_token(cfg) -> float:
    """The router over all experts, the shared expert (three matrices) and
    the expected visits to held experts (three matrices each)."""
    d = _d(cfg)
    return (2 * d["E"] * d["experts"] + 6 * d["E"] * d["Fs"]
            + expert_visits_per_token(cfg) * 6 * d["E"] * d["F"])


def lm_forward_flops_per_token(cfg, seq_len: int) -> float:
    """Every part of the step by name, per token, forward."""
    d = _d(cfg)
    parts = {
        "latent_proj": d["attention_layers"] * latent_proj_flops_per_token(cfg),
        "core": d["attention_layers"] * core_flops_per_row(cfg, seq_len)
        / seq_len,
        "dense_mlp": d["dense"] * 6 * d["E"] * d["Fd"],
        "expert_layers": d["expert_layers"] * expert_layer_flops_per_token(cfg),
        "head": (1 + d["mtp"]) * 2 * d["E"] * d["V"],
        "mtp_merge": d["mtp"] * 2 * 2 * d["E"] * d["E"]}
    return sum(parts.values())


def lm_train_flops_per_token(cfg, seq_len: int) -> float:
    """Forward + backward (2x forward); recomputation is not counted."""
    return 3 * lm_forward_flops_per_token(cfg, seq_len)


def _least(flops, nbytes, peak) -> dict:
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes), "flops": flops,
            "bytes": nbytes,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def core_step_min_seconds(cfg, batch: int, seq_len: int, peak: dict) -> dict:
    """The least time the attention cores of one training step can take:
    forward and backward (dP and dV over d_v, dQ and dK over d_n + d_r:
    twice the forward) of every latent-attention layer over `batch` rows.
    Bytes, a token and a layer, bf16: forward reads q (H (d_n + d_r)),
    k_nope (H d_n), k_rope (d_r, one head) and v (H d_v) and writes o (H
    d_v); backward reads those, o and do and writes dq, dk_nope, dk_rope
    and dv."""
    d = _d(cfg)
    H = d["H"]
    q, k = H * (d["dn"] + d["dr"]), H * d["dn"] + d["dr"]
    v = H * d["dv"]
    fwd = q + k + v + v
    bwd = (q + k + v + v + v) + (q + k + v)
    nbytes = BF16 * d["attention_layers"] * batch * seq_len * (fwd + bwd)
    flops = 3 * d["attention_layers"] * batch * core_flops_per_row(cfg,
                                                                   seq_len)
    return _least(flops, nbytes, peak)


def expert_mm_step_min_seconds(cfg, rows: float, peak: dict) -> dict:
    """The least time the grouped products of one training step can take,
    `rows` being the assignments to held experts summed over the expert
    layers of the step: per row three matrices forward (E -> 2 F gated, F ->
    E) and twice that backward. Bytes: every product reads its two operands
    and writes its result once, in bf16: the held experts' matrices once
    per product whatever the rows (forward, the rows' gradients, the
    weights' gradients), the rows' activations E, 2 F and F wide."""
    d = _d(cfg)
    weights = BF16 * d["held"] * 3 * d["E"] * d["F"]
    acts = BF16 * rows * (d["E"] + 3 * d["F"])
    nbytes = 3 * (d["expert_layers"] * weights + acts)
    return _least(3 * rows * 6 * d["E"] * d["F"], nbytes, peak)


# -- a run's own work (`layer_metrics/work_mfu_train`, `work_scope_roofline_pct`
# call these by name with the run's `ctx`; None where the run holds nothing
# to count) -----------------------------------------------------------------

def run_train_flops_per_token(ctx):
    cfg = ctx["cell"]["config"]
    return lm_train_flops_per_token(cfg, cfg["train"]["seq_len"])


def run_core_min_seconds(ctx):
    cfg = ctx["cell"]["config"]
    return core_step_min_seconds(cfg, cfg["train"]["batch"],
                                 cfg["train"]["seq_len"], ctx["peak"])


def run_expert_mm_min_seconds(ctx):
    """Over the rows of the steps whose time it is divided by: the held
    assignments per traced step, from the program's counters
    (`window["traced"]`)."""
    traced = ctx["window"].get("traced", {})
    held = traced.get("counters", {}).get("dl4j_moe_held_assignments_total")
    steps = traced.get("steps")
    if not held or not steps:
        return None
    return expert_mm_step_min_seconds(ctx["cell"]["config"], held / steps,
                                      ctx["peak"])
