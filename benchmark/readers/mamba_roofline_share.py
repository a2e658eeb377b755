"""Roofline share of the selective scan's kernels over the traced window, in
percent: the least time the chip could take for the state-space layers of
every engine step in the window (per step span the larger of operations over
peak FLOP/s and bytes over peak bytes/s, from the span's ``mamba_row_calls``
and ``mamba_tokens``, which the program sums over its state-space layers, and
``lib/opcount_mamba.selective_scan_cost``) over the time the trace measured
for the named kernels. The widths come from the configuration file's
published keys (``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``,
``n_groups``), the item size from the type the model computes in. The work
counted is the recurrent form's, the least of any correct form. A program
whose spans carry no such counts (the parent of the PR that added them), or a
configuration without state-space layers, reads as no metric. ``args``:
``{"kernels": [...], "spans": [...]}``."""

import numpy as np

from benchmark.lib import opcount, opcount_mamba, program_spans
from benchmark.lib.xplane import kernel_seconds


def read(ctx):
    reduced, peaks = ctx.get("reduced"), ctx.get("peaks")
    trace = program_spans.for_run(ctx)
    cf = ctx["cell"]["config_file"]
    if trace is None or peaks is None or not cf.get("mamba_num_heads"):
        return None
    itemsize = np.dtype(ctx["system"].cfg.dtype).itemsize
    widths = (cf["mamba_num_heads"], cf["mamba_head_dim"], cf["ssm_state_size"], cf["n_groups"])
    least, seen = 0.0, 0
    for name in ctx["args"]["spans"]:
        for span in program_spans.spans_named(trace, name):
            calls, tokens = span.args.get("mamba_row_calls"), span.args.get("mamba_tokens")
            if isinstance(calls, (int, float)) and isinstance(tokens, (int, float)):
                flops, nbytes = opcount_mamba.selective_scan_cost(int(calls), int(tokens), *widths, itemsize)
                least += opcount.min_seconds(flops, nbytes, peaks)[0]
                seen += 1
    measured = kernel_seconds(reduced, ctx["args"]["kernels"])
    return 100.0 * least / measured if seen and measured > 0 else None
