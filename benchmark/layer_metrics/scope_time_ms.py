"""Device time of some model scopes per execution of one program, in ms:
Σ self time of the operations whose leaf scope is one of `scopes` in the
traced window ÷ the program's executions there (`scope_reduce.py`).
params: {"program": "<name on the XLA Modules line>", "scopes": [...]}.
Nothing to read (None) where the reduced trace carries no scope table, the
program did not run in the window, or none of the scopes is in it."""


def read(ctx, params):
    table = (ctx["trace"].get("scopes") or {}).get("programs", {})
    prog = table.get(params["program"])
    if not prog or not prog["executions"]:
        return None
    rows = [prog["scopes"][s] for s in params["scopes"]
            if s in prog["scopes"]]
    if not rows:
        return None
    return 1e3 * sum(r["device_s"] for r in rows) / prog["executions"]
