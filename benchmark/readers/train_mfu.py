"""Model FLOP/s utilisation of a training cell, in percent: measured tokens a
second a chip, times the operations a trained token needs (6 per matmul
parameter plus causal attention, ``lib/opcount.train_flops_per_token``;
recomputed operations not counted), over the chip's bf16 peak."""

from benchmark.lib import opcount


def read(ctx):
    if ctx["peaks"] is None or "train_tokens_per_s_per_chip" not in ctx["end_to_end"]:
        return None
    cfg, seq = ctx["system"].cfg, ctx["system"].seq
    per_token = opcount.train_flops_per_token(
        cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        cfg.intermediate_size, cfg.vocab_size, cfg.mlp == "swiglu", seq, cfg.sliding_window)
    return 100.0 * opcount.mfu(ctx["end_to_end"]["train_tokens_per_s_per_chip"], per_token, ctx["peaks"])
