"""Device time of some model scopes per execution of one program, in ms,
from the scope table a driver left in its window (`window["scopes"]`,
`scope_reduce.scope_table` of its own traced steps): Σ self time of the
operations whose leaf scope is one of `scopes` ÷ the program's executions.
params: {"program": "<name on the XLA Modules line>", "scopes": [...]}.
Nothing to read (None) where the window carries no scope table, the
program did not run in it, or none of the scopes is in it."""


def scope_seconds(ctx, params):
    """(Σ device seconds of the scopes, executions) or None."""
    table = (ctx["window"].get("scopes") or {}).get("programs", {})
    prog = table.get(params["program"])
    if not prog or not prog["executions"]:
        return None
    rows = [prog["scopes"][s] for s in params["scopes"]
            if s in prog["scopes"]]
    if not rows:
        return None
    return sum(r["device_s"] for r in rows), prog["executions"]


def read(ctx, params):
    got = scope_seconds(ctx, params)
    if got is None:
        return None
    return 1e3 * got[0] / got[1]
