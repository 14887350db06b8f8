"""Host time of some of the program's live spans per occurrence of another,
in ms: Σ duration of the spans named in `spans` on the profiler's host
planes in the traced window, less that of the spans in `minus` (children
that wait for the device), ÷ the number of `per` spans there.
params: {"spans": ["generation/admit", ...], "minus": [...]?,
"per": "generation/step"}.
None where the reduced trace carries no host span table or `per` never
occurred."""


def read(ctx, params):
    spans = ctx["trace"].get("host_spans")
    if not spans or not spans.get(params["per"], [0])[0]:
        return None
    total = sum(spans[s][1] for s in params["spans"] if s in spans)
    total -= sum(spans[s][1] for s in params.get("minus", ()) if s in spans)
    return 1e3 * total / spans[params["per"]][0]
