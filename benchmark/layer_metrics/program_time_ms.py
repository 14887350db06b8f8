"""Device time of one program's executions in the traced window, in ms.
params: {"program": "<name on the XLA Modules line>", "stat": "p50"}."""
from benchmark.stats import stat


def read(ctx, params):
    runs = ctx["trace"]["programs"].get(params["program"])
    if not runs:
        return None
    return 1e3 * stat(runs, params.get("stat", "p50"))
