"""Mean over the measured window's engine steps of one kind: ``tokens`` fed
in a step that holds a prefill chunk, or ``seqs`` in a multi-step decode call.
``args``: ``{"steps": "prefill"|"decode", "field": "tokens"|"seqs"}``."""

from benchmark.lib import serving


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    want_prefill = ctx["args"]["steps"] == "prefill"
    values = []
    for step, _ in serving.window_steps(ctx, ctx["window"]):
        if not (serving.is_prefill(step) if want_prefill else step["kind"] == "decode"):
            continue
        values.append(sum(step["sizes"]) if ctx["args"]["field"] == "tokens" else len(step["uids"]))
    return sum(values) / len(values) if values else None
