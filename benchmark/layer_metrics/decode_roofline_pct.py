"""The decode step's share of its roofline: the least time the chip needs
for the ALGORITHM's step (work.decode_step_min_seconds: weights once,
every committed K/V row once, the FLOPs — whichever bounds) over the
median device time of the decode program in the traced window. The work
is taken from what the clients saw — how many sequences were between
their first and their last token, and how many rows (prompt + tokens so
far) they held, averaged over the traced window — not from what the
implementation reads, so it stays the same when a kernel replaces the
gather. params: {"program": "<decode program's name>"}."""
from benchmark import work
from benchmark.stats import stat


def mean_load(records, t_a, t_b, samples=64):
    """Mean number of decoding sequences and of their committed rows over
    [t_a, t_b], sampled evenly."""
    active = rows = 0.0
    for i in range(samples):
        t = t_a + (i + 0.5) * (t_b - t_a) / samples
        for r in records:
            st = r["stamps"]
            if st and st[0] <= t < st[-1]:
                active += 1
                rows += r["prompt_tokens"] + sum(1 for s in st if s <= t)
    return active / samples, rows / samples


def read(ctx, params):
    trace, window = ctx["trace"], ctx["window"]
    runs = trace["programs"].get(params["program"])
    if not runs or not window.get("traced") or ctx["peak"] is None:
        return None
    active, rows = mean_load(window["records"], *window["traced"])
    if active <= 0:
        return None
    least = work.decode_step_min_seconds(ctx["cell"]["config"], active,
                                         rows, ctx["peak"])
    return 100.0 * least["seconds"] / stat(runs, "p50")
