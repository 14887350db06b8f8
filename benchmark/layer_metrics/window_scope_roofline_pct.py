"""A kernel's share of its roofline in a training step of the hybrid
model: `work_hybrid`'s least time for the step's work in that kernel (the
larger of FLOPs over the chip's peak and bytes over its bandwidth,
forward + backward, recomputation not counted) ÷ the device time of the
kernel's scopes per step (`window_scope_time_ms`), in percent.
params: {"program", "scopes", "work": "scan" | "expert_mm"}. The grouped
product's rows are those of the steps whose time it is divided by: the
held assignments per traced step, from the program's counters
(`window["traced"]`). None where there is nothing to read."""
from benchmark import work_hybrid
from benchmark.layer_metrics.window_scope_time_ms import scope_seconds


def read(ctx, params):
    got = scope_seconds(ctx, params)
    if got is None or ctx["peak"] is None or not got[0]:
        return None
    cfg = ctx["cell"]["config"]
    tokens = cfg["train"]["batch"] * cfg["train"]["seq_len"]
    if params["work"] == "scan":
        least = work_hybrid.scan_step_min_seconds(cfg, tokens, ctx["peak"])
    else:
        traced = ctx["window"].get("traced", {})
        held = traced.get("counters", {}).get(
            "dl4j_moe_held_assignments_total")
        steps = traced.get("steps")
        if not held or not steps:
            return None
        least = work_hybrid.expert_mm_step_min_seconds(cfg, held / steps,
                                                       ctx["peak"])
    return 100.0 * least["seconds"] * got[1] / got[0]
