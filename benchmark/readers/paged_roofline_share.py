"""Roofline share of the paged-attention kernels over the traced window, in
percent: the least time the chip could take for the attention of every engine
step in the window (per step and layer the larger of operations over peak
FLOP/s and bytes over peak bytes/s, from the rows the step log holds and
``lib/opcount.paged_attention_cost``) over the time the trace measured for
the named kernels. All ``paged_attn_*`` kernels and all steps are taken
together, so that work and time cover the same calls whichever kernel the
program picked for a step. ``args``: ``{"kernels": [...]}``."""

from benchmark.lib import opcount
from benchmark.lib.xplane import kernel_seconds


def read(ctx):
    reduced, peaks = ctx.get("reduced"), ctx["peaks"]
    if not reduced or peaks is None or ctx["kind"] != "serve" or not ctx.get("trace_window"):
        return None
    system, cfg = ctx["system"], ctx["system"].cfg
    t0, t1 = ctx["trace_window"]
    seen, least = {}, 0.0
    for step in system.steps:
        calls = []  # one list of rows per attention call of a layer
        if step["kind"] == "put":
            calls.append([(seen.get(u, 0), n) for u, n in zip(step["uids"], step["sizes"])])
        else:
            calls.extend([(seen.get(u, 0) + j, 1) for u in step["uids"]] for j in range(step["sizes"][0]))
        for uid, size in zip(step["uids"], step["sizes"]):
            seen[uid] = seen.get(uid, 0) + size
        if step["t0"] < t0 or step["t1"] > t1:
            continue
        for rows in calls:
            flops, nbytes = opcount.paged_attention_cost(rows, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                                                         cfg.sliding_window, system.kv_itemsize,
                                                         system.kv_itemsize)
            least += cfg.num_layers * opcount.min_seconds(flops, nbytes, peaks)[0]
    measured = kernel_seconds(reduced, ctx["args"]["kernels"])
    return 100.0 * least / measured if measured > 0 else None
