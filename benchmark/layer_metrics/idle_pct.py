"""1 - (union of the device's operation intervals) / traced window."""


def read(ctx, params):
    trace = ctx["trace"]
    if not trace["window_s"] or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
