"""A statistic of one of the series the load generator's records give
(`ttft_ms`, `engine_ttft_ms`, `http_overhead_ms`, `late_ms`, `itl_ms`).
params: {"series": "...", "stat": "p95"}."""
from benchmark.stats import stat


def read(ctx, params):
    series = ctx["window"].get("client", {}).get(params["series"])
    if not series:
        return None
    return stat(series, params.get("stat", "p50"))
