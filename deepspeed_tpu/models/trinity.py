"""Trinity model family configs (arcee-ai Trinity-Large-Preview, ``model_type``
``afmoe``, 400B-A13B).

A decoder without biases, untied head, whose block differs from the other
families' in most of its parts:

* attention: 48 query / 8 KV heads of 128 (a group of 6), an RMSNorm over each
  head's 128 dimensions on q and on k (one gain vector shared by the heads),
  the output multiplied elementwise by ``sigmoid(h W_gate)`` before ``W_o``;
  three sliding-window layers (window 4,096, plain rope at theta 10,000) to
  one full-attention layer that carries NO positional encoding at all;
* four norms a layer (sandwich norm): before and after attention, before and
  after the MLP, each branch's output normalised before it is added;
* the first ``num_dense_layers`` layers have a dense SwiGLU (12,288 wide), the
  others 256 routed experts (SwiGLU 3,072 wide) of which a token's 4 are
  chosen by ``sigmoid`` score plus a per-expert bias that no gradient trains,
  weighted by the scores alone, normalised to sum to one and multiplied by
  ``route_scale`` 2.448, beside ONE shared expert that every token takes;
* the embedding is multiplied by ``sqrt(hidden_size)`` (muP).

Served through ``InferenceEngineV2`` (``ragged_forward`` unrolls the layers).
An expert layer of the published model is 14.7 GB in bf16, which no chip
holds: the model is served expert-parallel, and a chip is told which experts
it holds (``moe_experts_held`` from ``moe_first_expert``). It routes over all
256, computes its own experts' part of each token's sum, and leaves the
absent experts' terms out: the exchange between the chips of a group is not
built, so one chip's result is the partial one. The whole-sequence training
forward refuses this family (``transformer._refuse_mixed_layers``).

Not in ``config.json`` and taken from the afmoe model's published modelling
code (the benchmark's configuration file lists each under ``assumed``): the
embedding factor, the gate, the q/k norm, no rope in the full layers, the
selection bias, the two post-norms.
"""

import math

from .transformer import TransformerConfig, TransformerLM

_PERIOD = ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention")


def trinity_config(size: str = "large-preview", **overrides) -> TransformerConfig:
    presets = {
        # a group of 3, one dense layer and one whole period of expert layers,
        # a window shorter than a test's sequence
        "tiny": dict(vocab_size=512, hidden_size=64, num_layers=5, num_heads=6, num_kv_heads=2,
                     head_size=16, intermediate_size=128, moe_intermediate_size=48,
                     moe_num_experts=16, moe_top_k=2, moe_num_dense_layers=1, max_seq_len=512,
                     sliding_window=16),
        "large-preview": dict(vocab_size=200192, hidden_size=3072, num_layers=60, num_heads=48,
                              num_kv_heads=8, head_size=128, intermediate_size=12288,
                              moe_intermediate_size=3072, moe_num_experts=256, moe_top_k=4,
                              moe_num_dense_layers=6, max_seq_len=262144, sliding_window=4096),
    }
    base = dict(presets[size], norm="rmsnorm", positions="rotary", mlp="swiglu", use_bias=False,
                tie_embeddings=False, norm_eps=1e-5, rope_theta=10000.0, moe_dropless=True,
                moe_norm_topk_prob=True, moe_num_shared_experts=1, moe_score_func="sigmoid",
                moe_route_bias=True, moe_route_scale=2.448, qk_norm=True, attention_gate=True,
                post_norms=True, rope_layer_types=("sliding_attention", ))
    base.update(overrides)
    base.setdefault("embed_scale", math.sqrt(base["hidden_size"]))
    n = base["num_layers"]
    # a depth cut keeps the published list and reads its first ``num_layers`` entries
    base["layer_types"] = tuple(base.get("layer_types") or _PERIOD * (n // len(_PERIOD) + 1))[:n]
    return TransformerConfig(**base)


def trinity(size: str = "large-preview", **overrides) -> TransformerLM:
    return TransformerLM(trinity_config(size, **overrides))
