"""Solar Open 2 model family configs (upstage Solar-Open2-250B, ``model_type``
``solar_open2``, 250B-A15B).

A pre-norm decoder without biases or post-norms, untied head, in periods of
four layers:

* the first of a period (``gqa_layers``: 0, 4, 8, ...) is softmax attention,
  64 query / 8 KV heads of 128 with NO positional encoding, the output
  multiplied elementwise by ``sigmoid(h W_gate)`` before ``W_o`` (Trinity's
  full layer without the q/k norm);
* the three after it are LINEAR attention (Kimi Delta Attention): ``q, k, v =
  silu(conv4(h W_{q,k,v}))``, a causal depthwise convolution of 4 taps; per
  head of 64, ``q`` and ``k`` L2-normalised (``q`` further by ``1 /
  sqrt(128)``); a decay per key channel ``a_t = exp(-exp(A_log_h) *
  softplus(W_f2 (W_f1 h_t) + dt_bias))`` and a step size ``b_t = 2
  sigmoid(h_t W_b)``; the float32 state ``S`` (128 x 128 a head, zero at a
  sequence's start) goes ``S' = diag(a_t) S``, ``S = S' + b_t k_t (v_t - S'^T
  k_t)^T``, read as ``o_t = S^T q_t``; the output is ``W_o [rmsnorm_head(o_t)
  * sigmoid(W_g2 (W_g1 h_t))]``. Such a layer caches nothing per token: a
  sequence holds the state and the convolution's last three inputs, whatever
  its length (``TransformerConfig.state_entry``);
* every layer's MLP is 320 routed experts (SwiGLU 1,280 wide) of which a
  token's 8 are chosen by ``sigmoid`` score plus a per-expert bias that no
  gradient trains, weighted by the scores alone, normalised, beside ONE shared
  expert: GLM's router with other numbers, no leading dense layer.

Served through ``InferenceEngineV2`` alone (``ragged_forward`` unrolls the
layers, ``ops/pallas/kda.py`` holds the delta rule's two forms); a layer of
the published model is 5 GB of experts in bf16, so a chip is told which
experts it holds (``moe_experts_held``), as Trinity's is. The whole-sequence
forwards refuse this family (``TransformerConfig.unscannable``). What a
sequence's state forbids until it can be snapshot (the prefix cache, the host
tier, a rewind, the handoff, speculative decoding) refuses by name in the
engine and the state manager.

Not in ``config.json`` and taken from the Kimi Linear release (arXiv:2510.26692
and its published modelling code): the benchmark's configuration file lists
each under ``assumed``.
"""

from .transformer import TransformerConfig, TransformerLM

_PERIOD = ("full_attention", "linear_attention", "linear_attention", "linear_attention")


def solar_config(size: str = "open2-250b", **overrides) -> TransformerConfig:
    presets = {
        # one period; a group of 3, head widths that differ between the two attention kinds
        "tiny": dict(vocab_size=512, hidden_size=64, num_layers=4, num_heads=6, num_kv_heads=2, head_size=16,
                     intermediate_size=128, moe_intermediate_size=48, moe_num_experts=16, moe_top_k=2,
                     max_seq_len=2048, kda_num_heads=4, kda_head_dim=16, kda_gate_rank=8),
        "open2-250b": dict(vocab_size=196608, hidden_size=4096, num_layers=48, num_heads=64, num_kv_heads=8,
                           head_size=128, intermediate_size=10240, moe_intermediate_size=1280,
                           moe_num_experts=320, moe_top_k=8, max_seq_len=131072, kda_num_heads=64,
                           kda_head_dim=128, kda_gate_rank=128),
    }
    base = dict(presets[size], norm="rmsnorm", positions="rotary", mlp="swiglu", use_bias=False,
                tie_embeddings=False, norm_eps=1e-5, moe_dropless=True, moe_norm_topk_prob=True,
                moe_num_shared_experts=1, moe_score_func="sigmoid", moe_route_bias=True, moe_route_scale=1.0,
                attention_gate=True, kda_conv_size=4, kda_neg_eigval=True, rope_layer_types=())
    base.update(overrides)
    # the published keys that are no field of the program's own: ``linear_attn_config`` (a group) and
    # ``gqa_layers`` (the softmax layers by number; every other layer is linear)
    lin = base.pop("linear_attn_config", None) or {}
    for key, field in (("num_heads", "kda_num_heads"), ("head_dim", "kda_head_dim"),
                       ("short_conv_kernel_size", "kda_conv_size")):
        if key in lin:
            base[field] = lin[key]
    n = base["num_layers"]
    gqa = base.pop("gqa_layers", None)
    if gqa is not None:  # a depth cut keeps the published list and reads the layers it has
        base["layer_types"] = tuple("full_attention" if l in set(gqa) else "linear_attention" for l in range(n))
    base["layer_types"] = tuple(base.get("layer_types") or _PERIOD * (n // len(_PERIOD) + 1))[:n]
    return TransformerConfig(**base)


def solar(size: str = "open2-250b", **overrides) -> TransformerLM:
    return TransformerLM(solar_config(size, **overrides))
