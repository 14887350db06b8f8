"""The share of jax's persistent-cache lookups inside `counted_jit`'s
builds that hit, in %, summed over every `kind`:
`dl4j_jax_cache_requests_total{kind,outcome}` from the process's metrics
registry. 100 in a warm run; less says a cache key moved. None where no
build asked the cache (a program older than PR 37, or no cache)."""


def read(ctx, params):
    from deeplearning4j_tpu.common.metrics import registry
    fam = registry().get("dl4j_jax_cache_requests_total")
    if fam is None:
        return None
    n = {"hit": 0.0, "miss": 0.0}
    for (_, outcome), c in fam.children():
        n[outcome] = n.get(outcome, 0.0) + c.value()
    asked = n["hit"] + n["miss"]
    return 100.0 * n["hit"] / asked if asked else None
