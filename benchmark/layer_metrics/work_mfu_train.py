"""Whole-step model FLOP/s utilisation of a training cell whose work a
module under `benchmark/` counts: that module's forward+backward FLOPs per
token (recomputation not counted) times the run's own train_tokens_per_s,
over chips times the chip's bf16 peak.
params: {"work": the module, "flops_per_token": its function of the run's
`ctx`}. A new configuration brings its work module and a metric file, and no
reader. None where there is no rate, no peak or nothing to count."""
import importlib


def read(ctx, params):
    rate = ctx["end_to_end"].get("train_tokens_per_s")
    if not rate or ctx["peak"] is None:
        return None
    work = importlib.import_module("benchmark." + params["work"])
    flops = getattr(work, params["flops_per_token"])(ctx)
    if flops is None:
        return None
    return 100.0 * flops * rate / (ctx["chips"]
                                   * ctx["peak"]["bf16_flops_per_s"])
