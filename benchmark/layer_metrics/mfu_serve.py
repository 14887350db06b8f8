"""Whole-path model FLOP/s utilisation of a serving cell: work.py's
forward FLOPs of every request completed in the window (each prompt token
and each generated token once, over the positions it attends), over the
window's seconds times chips times the chip's bf16 peak."""
from benchmark import work


def read(ctx, params):
    done = ctx["window"].get("completed")
    if not done or ctx["peak"] is None:
        return None
    cfg = ctx["cell"]["config"]
    flops = sum(work.decoder_request_flops(cfg, p, g) for p, g in done)
    return 100.0 * flops / (ctx["seconds"] * ctx["chips"]
                            * ctx["peak"]["bf16_flops_per_s"])
