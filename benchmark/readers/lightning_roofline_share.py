"""Roofline share of the lightning recurrence's kernels over the traced window,
in percent: the least time the chip could take for the lightning layers of
every engine step in the window (per step span the larger of operations over
peak FLOP/s and bytes over peak bytes/s, from the span's ``state_rows`` and
``lin_tokens`` and ``lib/opcount_lightning.recurrence_cost``) over the time the
trace measured for the named kernels. The widths come from the configuration
file (``lightning_nh``, ``lightning_head_dim``), the lightning layers from its
``mixer_types`` as cut, the item size from the type the model computes in. The
work counted is the recurrent form's, the least of any correct form. A program
whose spans carry no such counts, or a configuration without lightning layers,
reads as no metric. ``args``: ``{"kernels": [...], "spans": [...]}``."""

import numpy as np

from benchmark.lib import opcount, opcount_lightning, program_spans
from benchmark.lib.xplane import kernel_seconds


def read(ctx):
    reduced, peaks = ctx.get("reduced"), ctx.get("peaks")
    trace = program_spans.for_run(ctx)
    cf = ctx["cell"]["config_file"]
    mixers = cf.get("mixer_types")
    if trace is None or peaks is None or not mixers or not cf.get("lightning_nh"):
        return None
    first = int(cf.get("first_layer", 0))
    layers = sum(1 for m in mixers[first:first + cf["num_hidden_layers"]] if m == "lightning-attn")
    itemsize = np.dtype(ctx["system"].cfg.dtype).itemsize
    heads, d = cf["lightning_nh"], cf["lightning_head_dim"]
    least, seen = 0.0, 0
    for name in ctx["args"]["spans"]:
        for span in program_spans.spans_named(trace, name):
            rows, tokens = span.args.get("state_rows"), span.args.get("lin_tokens")
            if isinstance(rows, (int, float)) and isinstance(tokens, (int, float)):
                flops, nbytes = opcount_lightning.recurrence_cost(int(rows) * layers, int(tokens), heads, d, d, itemsize)
                least += opcount.min_seconds(flops, nbytes, peaks)[0]
                seen += 1
    measured = kernel_seconds(reduced, ctx["args"]["kernels"])
    return 100.0 * least / measured if seen and measured > 0 else None
