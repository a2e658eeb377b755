"""Mellum 2 model family configs (JetBrains Mellum2-12B-A2.5B).

A pre-norm RMSNorm decoder without biases and with an untied head whose
layers differ in two ways the other families do not: three sliding-window
layers (window 1,024, plain rope) to one full-attention layer (YaRN rope,
factor 16) by ``layer_types``, and in every layer 64 experts of width 896 of
which a token's top 8 by the float32 softmax over all of them run, their
probabilities renormalised, none dropped and no shared expert. Heads are 128
wide at a hidden size of 2,304 (32 query, 4 KV heads).

Served through ``InferenceEngineV2`` (``ragged_forward`` unrolls the layers,
each with its own window and rope table, experts through the grouped
matmul). The whole-sequence training forward scans one block over the
layers and refuses this family (``transformer._refuse_mixed_layers``).
Not in the published ``config.json`` and so not built: a per-head norm on q
and k, and the multi-token-prediction head its card mentions.
"""

from .transformer import TransformerConfig, TransformerLM

_PERIOD = ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention")


def _rope_parameters(theta: float, original: int, factor: float = 16.0) -> dict:
    return {"full_attention": {"rope_type": "yarn", "rope_theta": theta, "factor": factor,
                               "original_max_position_embeddings": original,
                               "beta_fast": 32, "beta_slow": 1,
                               "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default", "rope_theta": theta}}


def mellum_config(size: str = "12b-a2.5b", **overrides) -> TransformerConfig:
    presets = {
        # one period of the layer pattern, a window shorter than a test's sequence
        "tiny": dict(vocab_size=512, hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2,
                     head_size=32, intermediate_size=128, moe_intermediate_size=48,
                     moe_num_experts=8, moe_top_k=2, max_seq_len=512, sliding_window=16,
                     rope_parameters=_rope_parameters(10000.0, 32)),
        "12b-a2.5b": dict(vocab_size=98304, hidden_size=2304, num_layers=28, num_heads=32,
                          num_kv_heads=4, head_size=128, intermediate_size=7168,
                          moe_intermediate_size=896, moe_num_experts=64, moe_top_k=8,
                          max_seq_len=131072, sliding_window=1024,
                          rope_parameters=_rope_parameters(500000.0, 8192)),
    }
    base = dict(presets[size], norm="rmsnorm", positions="rotary", mlp="swiglu", use_bias=False,
                tie_embeddings=False, norm_eps=1e-6, moe_dropless=True, moe_norm_topk_prob=True)
    base.update(overrides)
    n = base["num_layers"]
    # a depth cut keeps the published list and reads its first ``num_layers`` entries
    base["layer_types"] = tuple(base.get("layer_types") or _PERIOD * (n // len(_PERIOD) + 1))[:n]
    # the plain rope's base is the window layers' (both kinds publish the same)
    window_rope = (base.get("rope_parameters") or {}).get("sliding_attention") or {}
    if "rope_theta" in window_rope:
        base.setdefault("rope_theta", float(window_rope["rope_theta"]))
    return TransformerConfig(**base)


def mellum(size: str = "12b-a2.5b", **overrides) -> TransformerLM:
    return TransformerLM(mellum_config(size, **overrides))
