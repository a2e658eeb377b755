"""Share of the device's busy time, in percent, spent in the operations of
the named parts of the model: those whose ``op_name`` lies under one of the
``jax.named_scope`` names ``scopes`` (the innermost word of
``deepspeed_tpu/monitor/scopes.py``'s vocabulary on the path; ``<none>`` for
an operation under no scope, ``<unmapped>`` for one whose instruction the
trace's HLO does not hold), less the operations ``except_ops``, base names
as ``breakdown.device_ops`` gives them. ``args``: ``{"scopes": [...],
"except_ops": [...]}``. None for an untraced run, a trace without HLO, and a
program from before the scopes, as ``span_gap_share`` returns None for a
program without spans."""

from benchmark.lib import op_scopes


def read(ctx):
    table = op_scopes.for_run(ctx)
    if table is None:
        return None
    return op_scopes.share(table, ctx["args"]["scopes"], ctx["args"].get("except_ops", ()))
