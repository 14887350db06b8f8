"""A kernel's share of its roofline in a training step: a work module's
least time for the step's work in that kernel (the larger of FLOPs over the
chip's peak and bytes over its bandwidth, forward + backward, recomputation
not counted) ÷ the device time of the kernel's scopes per step
(`window_scope_time_ms`), in percent.
params: {"program", "scopes", "work": the module under `benchmark/`,
"min_seconds": its function of the run's `ctx`, which returns {"seconds"}
or None}. A new configuration brings its work module and a metric file, and
no reader. None where there is nothing to read."""
import importlib

from benchmark.layer_metrics.window_scope_time_ms import scope_seconds


def read(ctx, params):
    got = scope_seconds(ctx, params)
    if got is None or ctx["peak"] is None or not got[0]:
        return None
    work = importlib.import_module("benchmark." + params["work"])
    least = getattr(work, params["min_seconds"])(ctx)
    if least is None:
        return None
    return 100.0 * least["seconds"] * got[1] / got[0]
