"""delta of `num` ÷ delta of `den` over the window, from the program's
counters (`window["counters"]`). params: {"num", "den"}. None where either
counter is missing or `den` did not move."""


def read(ctx, params):
    c = ctx["window"].get("counters", {})
    num, den = c.get(params["num"]), c.get(params["den"])
    if num is None or not den:
        return None
    return num / den
