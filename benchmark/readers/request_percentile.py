"""A percentile over the measured requests of one field of their records:
``queue_wait_ms`` (due -> start of the first engine step that holds the
request), ``gen_late_ms`` (due -> the generator's call of ``submit``: a
starved generator is not a fast server) or ``ttft_ms`` (due -> first token).
``args``: ``{"field": ..., "q": 95}``."""

from benchmark.lib.rates import percentile


def read(ctx):
    values = [r[ctx["args"]["field"]] for r in ctx.get("requests") or []]
    return percentile(values, ctx["args"]["q"]) if values else None
