"""Whole-step model FLOP/s utilisation of a hybrid-model training cell:
`work_hybrid`'s forward+backward FLOPs per token (recomputation not
counted) times the run's own train_tokens_per_s, over chips times the
chip's bf16 peak."""
from benchmark import work_hybrid


def read(ctx, params):
    rate = ctx["end_to_end"].get("train_tokens_per_s")
    if not rate or ctx["peak"] is None:
        return None
    cfg = ctx["cell"]["config"]
    flops = work_hybrid.lm_train_flops_per_token(cfg, cfg["train"]["seq_len"])
    return 100.0 * flops * rate / (ctx["chips"]
                                   * ctx["peak"]["bf16_flops_per_s"])
