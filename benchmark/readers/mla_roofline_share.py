"""Roofline share of attention over a latent cache in the traced window, in
percent: the least time the chip could take for the attention of every engine
step in the window (per step span the larger of operations over peak FLOP/s
and bytes over peak bytes/s, from the span's ``attn_pairs``,
``attn_ctx_tokens`` and ``tokens`` and ``lib/opcount_mla.latent_attention_cost``)
over the time the trace measured for the named attention kernels. The widths
come from the configuration file (heads, score and value widths, and the
UNPADDED entry ``kv_lora_rank + qk_rope_head_dim``), the item size from the KV
cache's. The work counted is the expanded form's, the least of any correct
form, so a program in the absorbed form reads at most
``opcount_mla.absorbed_share_of_expanded`` (47%) where FLOP/s bound. A
program whose spans carry no such counts (the parent of the PR that added
them), or a configuration without a latent entry, reads as no metric.
``args``: ``{"kernels": [...], "spans": [...]}``."""

from benchmark.lib import opcount, opcount_mla, program_spans
from benchmark.lib.xplane import kernel_seconds


def read(ctx):
    reduced, peaks = ctx.get("reduced"), ctx["peaks"]
    trace = program_spans.for_run(ctx)
    cf = ctx["cell"]["config_file"]
    if trace is None or peaks is None or "kv_lora_rank" not in cf:
        return None
    itemsize = int(ctx["system"].kv_itemsize)
    layers, n_q = cf["num_hidden_layers"], cf["num_attention_heads"]
    qk_dim, v_dim = cf["qk_nope_head_dim"] + cf["qk_rope_head_dim"], cf["v_head_dim"]
    entry_bytes = (cf["kv_lora_rank"] + cf["qk_rope_head_dim"]) * itemsize
    least, seen = 0.0, 0
    for name in ctx["args"]["spans"]:
        for span in program_spans.spans_named(trace, name):
            pairs, ctx_tokens, tokens = (span.args.get(k) for k in ("attn_pairs", "attn_ctx_tokens", "tokens"))
            if all(isinstance(v, (int, float)) for v in (pairs, ctx_tokens, tokens)):
                flops, nbytes = opcount_mla.latent_attention_cost(int(pairs), int(ctx_tokens), int(tokens) * layers,
                                                                  n_q, qk_dim, v_dim, entry_bytes, itemsize)
                least += opcount.min_seconds(flops, nbytes, peaks)[0]
                seen += 1
    measured = kernel_seconds(reduced, ctx["args"]["kernels"])
    return 100.0 * least / measured if seen and measured > 0 else None
