"""A percentile, in milliseconds, of the time of one decode step: each
multi-step ``engine.decode`` call's host time divided by its horizon, over
the measured window. ``args``: ``{"q": 50}``."""

from benchmark.lib import serving
from benchmark.lib.rates import percentile


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    values = [(step["t1"] - step["t0"]) / step["sizes"][0] * 1e3
              for step, _ in serving.window_steps(ctx, ctx["window"]) if step["kind"] == "decode"]
    return percentile(values, ctx["args"]["q"]) if values else None
