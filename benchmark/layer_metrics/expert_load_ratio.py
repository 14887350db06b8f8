"""How uneven the held experts' load was over the window: the assignments
of the fullest (block, expert) ÷ the mean over all of them, from the
deltas of `dl4j_moe_expert_tokens_total{block,expert}` that the driver
left in `window["counters"]` (as `expert_tokens/<block>/<expert>`). 1 is
an even load. None where the program fed no such counter."""


def read(ctx, params):
    loads = [v for k, v in ctx["window"].get("counters", {}).items()
             if k.startswith("expert_tokens/")]
    if not loads or not sum(loads):
        return None
    return max(loads) * len(loads) / sum(loads)
