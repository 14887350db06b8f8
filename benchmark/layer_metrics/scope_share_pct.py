"""Share of one program's operation time that some model scopes take, in
percent (`scope_reduce.py`; with `["unscoped"]` it is the guard that the
scope table means something).
params: {"program": "<name on the XLA Modules line>", "scopes": [...]}.
None where the reduced trace carries no scope table or the program did not
run in the window; 0 is a reading (no such operation ran)."""


def read(ctx, params):
    table = (ctx["trace"].get("scopes") or {}).get("programs", {})
    prog = table.get(params["program"])
    if not prog or not prog["op_s"]:
        return None
    took = sum(prog["scopes"].get(s, {}).get("device_s", 0.0)
               for s in params["scopes"])
    return 100.0 * took / prog["op_s"]
