"""A percentile, in milliseconds, of the times between successive fences of
the measured window: for training, the time of a whole step from one fetched
loss to the next. ``args``: ``{"q": 50}``."""

from benchmark.lib.rates import percentile


def read(ctx):
    fences = ctx.get("fences")
    if not fences or len(fences) < 2:
        return None
    return percentile([(b - a) * 1e3 for a, b in zip(fences, fences[1:])], ctx["args"]["q"])
