"""Prompt tokens prefilled per second of engine time spent in the steps that
hold a prefill chunk (host clock from before ``engine.put`` to its fetched
result), over the measured window."""

from benchmark.lib import serving


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    tokens = seconds = 0.0
    for step, (n_prompt, _) in serving.window_steps(ctx, ctx["window"]):
        if serving.is_prefill(step):
            tokens += n_prompt
            seconds += step["t1"] - step["t0"]
    return tokens / seconds if seconds > 0 else None
