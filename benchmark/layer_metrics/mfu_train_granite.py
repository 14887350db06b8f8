"""Whole-step model FLOP/s utilisation of the packed granite training cell:
`work_granite`'s forward+backward FLOPs per token (recomputation not
counted, attention over the same-document pairs the window's rows held,
`window["packing"]`) times the run's own train_tokens_per_s, over chips
times the chip's bf16 peak. None where there is no peak or no packing."""
from benchmark import work_granite


def read(ctx, params):
    rate = ctx["end_to_end"].get("train_tokens_per_s")
    packing = ctx["window"].get("packing")
    if not rate or ctx["peak"] is None or not packing or not packing["rows"]:
        return None
    flops = work_granite.lm_train_flops_per_token(ctx["cell"]["config"],
                                                  packing)
    return 100.0 * flops * rate / (ctx["chips"]
                                   * ctx["peak"]["bf16_flops_per_s"])
