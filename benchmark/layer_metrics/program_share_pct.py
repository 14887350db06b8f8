"""Share of the device's busy time that one program's executions took.
params: {"program": "<name on the XLA Modules line>"}."""


def read(ctx, params):
    trace = ctx["trace"]
    runs = trace["programs"].get(params["program"])
    if not runs or not trace["busy_s"]:
        return None
    return 100.0 * sum(runs) / (trace["busy_s"] * trace["chips"])
