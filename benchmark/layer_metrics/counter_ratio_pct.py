"""100 x (delta of `num` - delta of `minus`) / (delta of `den` x scale)
over the window, from the program's counters. `scale_from` names a key of
the configuration's `deploy` group (e.g. the number of slots).
params: {"num", "den", "minus"?, "scale_from"?}."""


def read(ctx, params):
    c = ctx["window"].get("counters", {})
    den = c.get(params["den"])
    num = c.get(params["num"])
    if not den or num is None:
        return None
    if params.get("minus"):
        num -= c.get(params["minus"], 0.0)
    scale = 1.0
    if params.get("scale_from"):
        scale = float(ctx["cell"]["config"]["deploy"][params["scale_from"]])
    return 100.0 * num / (den * scale)
