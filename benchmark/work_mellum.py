"""Operations and bytes the *algorithm* of the window-and-full-attention,
sparse-expert decoder needs (the keys of
`JetBrains/Mellum2-12B-A2.5B-Instruct`'s `config.json`), from shapes alone
(`work.py`'s rule: nothing here looks at which path or kernel the program
took, and recomputation is never counted). Counts are multiply-adds times
two. Norms, activations, the rotary embedding, softmax, the router's top-k
and the embedding lookup are left out (under 1% of the matmul work at these
widths).

A window layer's core is counted over the (query, key) pairs its band
holds, `window_pairs`: a kernel that skips what lies outside the band can
therefore reach, and not pass, 100%; a full layer's over the causal pairs.

`cfg` is a configuration file's dict with the published key names; its
`num_experts` counts the experts held on the chip and
`published.num_experts` is the router's width; `layer_types` names each
layer held.
"""
from __future__ import annotations

from benchmark.work_hybrid import BF16, _least
from benchmark.work_joyai import causal_pairs


def _d(cfg):
    pub = cfg.get("published", {})
    layers = int(cfg["num_hidden_layers"])
    types = cfg["layer_types"][:layers]
    D = int(cfg["head_dim"])
    return dict(
        E=int(cfg["hidden_size"]), V=int(cfg["vocab_size"]),
        q=int(cfg["num_attention_heads"]) * D,
        kv=int(cfg["num_key_value_heads"]) * D, H=int(cfg["num_attention_heads"]),
        D=D, W=int(cfg["sliding_window"]), layers=layers,
        window_layers=types.count("sliding_attention"),
        full_layers=types.count("full_attention"),
        held=int(cfg["num_experts"]),
        experts=int(pub.get("num_experts", cfg["num_experts"])),
        top_k=int(cfg["num_experts_per_tok"]),
        F=int(cfg["moe_intermediate_size"]))


def window_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs of one head over one row under a window:
    i - window < j <= i, min(i + 1, window) keys for query i."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def proj_flops_per_token(cfg) -> int:
    """One layer's four projections: E -> H D (q), E -> H_kv D twice (k,
    v), H D -> E (o)."""
    d = _d(cfg)
    return 2 * d["E"] * (2 * d["q"] + 2 * d["kv"])


def core_flops_per_pair(cfg) -> int:
    """q . k and p . v over D for every head, one (query, key) pair."""
    d = _d(cfg)
    return 2 * d["H"] * (d["D"] + d["D"])


def expert_visits_per_token(cfg) -> float:
    """Expected visits of one token to experts held here: top_k x held /
    experts (a uniform router)."""
    d = _d(cfg)
    return d["top_k"] * d["held"] / d["experts"]


def expert_layer_flops_per_token(cfg) -> float:
    """The router over all experts and the expected visits to held experts
    (three matrices each); no shared expert."""
    d = _d(cfg)
    return (2 * d["E"] * d["experts"]
            + expert_visits_per_token(cfg) * 6 * d["E"] * d["F"])


def lm_forward_flops_per_token(cfg, seq_len: int) -> float:
    """Every part of the step by name, per token, forward."""
    d = _d(cfg)
    per_pair = core_flops_per_pair(cfg)
    parts = {
        "proj": d["layers"] * proj_flops_per_token(cfg),
        "window_core": d["window_layers"] * per_pair
        * window_pairs(seq_len, d["W"]) / seq_len,
        "full_core": d["full_layers"] * per_pair * causal_pairs(seq_len)
        / seq_len,
        "experts": d["layers"] * expert_layer_flops_per_token(cfg),
        "head": 2 * d["E"] * d["V"]}
    return sum(parts.values())


def lm_train_flops_per_token(cfg, seq_len: int) -> float:
    """Forward + backward (2x forward); recomputation is not counted."""
    return 3 * lm_forward_flops_per_token(cfg, seq_len)


def core_step_min_seconds(cfg, batch: int, seq_len: int, peak: dict,
                          window: bool) -> dict:
    """The least time the window layers' cores (``window``) or the full
    layers' of one training step can take: forward and backward (twice the
    forward) over `batch` rows, the pairs of each kind of layer. Bytes, a
    token and a layer, bf16: forward reads q (H D), k and v (H_kv D each)
    and writes o (H D); backward reads those, o and do and writes dq, dk
    and dv."""
    d = _d(cfg)
    layers = d["window_layers"] if window else d["full_layers"]
    pairs = (window_pairs(seq_len, d["W"]) if window
             else causal_pairs(seq_len))
    q, kv = d["q"], d["kv"]
    fwd = q + 2 * kv + q
    bwd = (q + 2 * kv + 2 * q) + (q + 2 * kv)
    nbytes = BF16 * layers * batch * seq_len * (fwd + bwd)
    flops = 3 * layers * batch * pairs * core_flops_per_pair(cfg)
    return _least(flops, nbytes, peak)


def expert_mm_step_min_seconds(cfg, rows: float, peak: dict) -> dict:
    """The least time the grouped products of one training step can take,
    `rows` being the assignments to held experts summed over the layers of
    the step: per row three matrices forward (E -> 2 F gated, F -> E) and
    twice that backward. Bytes: every product reads its two operands and
    writes its result once, in bf16: the held experts' matrices once per
    product whatever the rows (forward, the rows' gradients, the weights'
    gradients), the rows' activations E, 2 F and F wide."""
    d = _d(cfg)
    weights = BF16 * d["held"] * 3 * d["E"] * d["F"]
    acts = BF16 * rows * (d["E"] + 3 * d["F"])
    nbytes = 3 * (d["layers"] * weights + acts)
    return _least(3 * rows * 6 * d["E"] * d["F"], nbytes, peak)


# -- a run's own work (`layer_metrics/work_mfu_train`, `work_scope_roofline_pct`
# call these by name with the run's `ctx`; None where the run holds nothing
# to count) -----------------------------------------------------------------

def run_train_flops_per_token(ctx):
    cfg = ctx["cell"]["config"]
    return lm_train_flops_per_token(cfg, cfg["train"]["seq_len"])


def run_window_core_min_seconds(ctx):
    cfg = ctx["cell"]["config"]
    return core_step_min_seconds(cfg, cfg["train"]["batch"],
                                 cfg["train"]["seq_len"], ctx["peak"], True)


def run_full_core_min_seconds(ctx):
    cfg = ctx["cell"]["config"]
    return core_step_min_seconds(cfg, cfg["train"]["batch"],
                                 cfg["train"]["seq_len"], ctx["peak"], False)


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
