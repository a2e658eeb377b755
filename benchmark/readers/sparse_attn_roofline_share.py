"""Roofline share of the paged-attention kernels under a learned block
selection over the traced window, in percent: the least time the chip could
take for the SELECTED attention of every engine step in the window (per step
span the larger of operations over peak FLOP/s and bytes over peak bytes/s,
from the span's ``attn_pairs``, ``attn_ctx_tokens``, ``kv_entry_bytes`` and its
tokens, and ``lib/opcount_sparse.selected_attention_cost``) over the time the
trace measured for the named kernels. What a tile's union makes the kernel
read beyond its tokens' own blocks is the kernel's cost and lowers the share.
A program whose spans carry no ``attn_blocks_selected`` (one without a
selection; the parent of the PR that added it) reads as no metric. ``args``:
``{"kernels": [...], "spans": [...]}``."""

import numpy as np

from benchmark.lib import opcount, opcount_sparse, program_spans
from benchmark.lib.xplane import kernel_seconds


def read(ctx):
    reduced, peaks = ctx.get("reduced"), ctx.get("peaks")
    trace = program_spans.for_run(ctx)
    cf = ctx["cell"]["config_file"]
    mixers = cf.get("mixer_types")
    if trace is None or peaks is None or not mixers:
        return None
    first = int(cf.get("first_layer", 0))
    layers = sum(1 for m in mixers[first:first + cf["num_hidden_layers"]] if m == "minicpm4")
    itemsize = np.dtype(ctx["system"].cfg.dtype).itemsize
    n_q, d = cf["num_attention_heads"], cf["head_dim"]
    number = lambda x: isinstance(x, (int, float))
    least, seen = 0.0, 0
    for name in ctx["args"]["spans"]:
        for span in program_spans.spans_named(trace, name):
            a = span.args
            if not number(a.get("attn_blocks_selected")):
                continue
            pairs, ctx_tokens, entry = a.get("attn_pairs"), a.get("attn_ctx_tokens"), a.get("kv_entry_bytes")
            tokens = a.get("tokens") if number(a.get("tokens")) else 0   # a decode horizon: rows x steps
            if name == "serving/decode" and number(a.get("rows")) and number(a.get("steps")):
                tokens = a["rows"] * a["steps"]
            if all(number(x) for x in (pairs, ctx_tokens, entry)):
                flops, nbytes = opcount_sparse.selected_attention_cost(int(pairs), int(ctx_tokens), int(tokens) * layers,
                                                                       n_q, d, int(entry), itemsize)
                least += opcount.min_seconds(flops, nbytes, peaks)[0]
                seen += 1
    measured = kernel_seconds(reduced, ctx["args"]["kernels"])
    return 100.0 * least / measured if seen and measured > 0 else None
