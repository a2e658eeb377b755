"""Roofline share of the expert layers' grouped matmuls over the traced window,
in percent: the least time the chip could take for the routed slots of every
engine step in the window (per step span the larger of operations over peak
FLOP/s and bytes over peak bytes/s, from the span's ``moe_slots`` and
``experts_hit`` and ``lib/opcount_moe.expert_ffn_cost``) over the time the trace
measured for the named kernels. Widths come from the configuration file, the
expert weights' item size from the type the model's parameters are held in
(``system.cfg.dtype``; not the KV cache's, which may be narrower). A
program whose spans carry no such counts reads as no metric. ``args``:
``{"kernels": [...], "spans": [...]}``."""

import numpy as np

from benchmark.lib import opcount, opcount_moe, program_spans
from benchmark.lib.xplane import kernel_seconds


def read(ctx):
    reduced, peaks = ctx.get("reduced"), ctx["peaks"]
    trace = program_spans.for_run(ctx)
    cf = ctx["cell"]["config_file"]
    if trace is None or peaks is None or "moe_intermediate_size" not in cf:
        return None
    itemsize = np.dtype(ctx["system"].cfg.dtype).itemsize
    least, seen = 0.0, 0
    for name in ctx["args"]["spans"]:
        for span in program_spans.spans_named(trace, name):
            slots, hit = span.args.get("moe_slots"), span.args.get("experts_hit")
            if isinstance(slots, (int, float)) and isinstance(hit, (int, float)):
                flops, nbytes = opcount_moe.expert_ffn_cost(
                    int(slots), int(hit), cf["hidden_size"], cf["moe_intermediate_size"],
                    itemsize, gated=cf.get("hidden_act", "silu") == "silu")
                least += opcount.min_seconds(flops, nbytes, peaks)[0]
                seen += 1
    measured = kernel_seconds(reduced, ctx["args"]["kernels"])
    return 100.0 * least / measured if seen and measured > 0 else None
