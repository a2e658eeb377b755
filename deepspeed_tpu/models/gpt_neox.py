"""GPT-NeoX / Pythia model family configs.

Analog of the reference ``module_inject/containers/gptneox.py``: parallel
residual with TWO pre-norms, partial rotary (rotary_pct, NeoX-half style),
GELU, biases, untied embeddings, fused per-head query_key_value in HF
checkpoints (split by the converter).

``rotary_dim`` r is ``rotary_pct`` of the head's d (a quarter in the Pythias:
16 of 64 at 410M, 32 of 128 at 1.4B). With r < d ``rope_table`` gives both
tables at the full width of the head, ``[S, d]``: the signed sine ``[-sin,
sin, 0 ... 0]`` and the cosine ``[cos, cos, 1 ... 1]`` over r/2, r/2 and d - r
lanes, and ``apply_rope`` rotates every head as a whole (``x * cos +
partner(x) * sin``, the lanes from r on taken from ``x``): no part of a head
is sliced off or concatenated back, in the forward or in the backward.
"""

from .transformer import TransformerConfig, TransformerLM


def gpt_neox_config(size: str = "20b", **overrides) -> TransformerConfig:
    presets = {
        "tiny": dict(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4, max_seq_len=512,
                     rotary_dim=8),
        "pythia-1b": dict(vocab_size=50304, hidden_size=2048, num_layers=16, num_heads=8,
                          max_seq_len=2048, rotary_dim=64),
        "20b": dict(vocab_size=50432, hidden_size=6144, num_layers=44, num_heads=64, max_seq_len=2048,
                    rotary_dim=24),
    }
    base = dict(presets[size], norm="layernorm", positions="rotary", mlp="gelu", use_bias=True,
                intermediate_size=4 * presets[size]["hidden_size"], tie_embeddings=False,
                parallel_residual=True, shared_ln=False, norm_eps=1e-5)
    base.update(overrides)
    return TransformerConfig(**base)


def gpt_neox(size: str = "20b", **overrides) -> TransformerLM:
    return TransformerLM(gpt_neox_config(size, **overrides))
