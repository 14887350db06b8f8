"""A kernel's share of its roofline in a training step of the packed
granite cell: `work_granite`'s least time for the step's work in that
kernel (the larger of FLOPs over the chip's peak and bytes over its
bandwidth, forward + backward, recomputation not counted) ÷ the device time
of the kernel's scopes per step (`window_scope_time_ms`), in percent.
params: {"program", "scopes", "work": "scan" | "attn_core"}. The attention
core's pairs are those of the traced steps whose time it is divided by
(`window["traced"]["packing"]`). None where there is nothing to read."""
from benchmark import work_granite
from benchmark.layer_metrics.window_scope_time_ms import scope_seconds


def read(ctx, params):
    got = scope_seconds(ctx, params)
    if got is None or ctx["peak"] is None or not got[0]:
        return None
    cfg = ctx["cell"]["config"]
    tokens = cfg["train"]["batch"] * cfg["train"]["seq_len"]
    if params["work"] == "scan":
        least = work_granite.scan_step_min_seconds(cfg, tokens, ctx["peak"])
    else:
        packing = ctx["window"].get("traced", {}).get("packing")
        if not packing or not packing["rows"]:
            return None
        least = work_granite.attn_core_step_min_seconds(
            cfg, tokens, packing, ctx["peak"])
    return 100.0 * least["seconds"] * got[1] / got[0]
