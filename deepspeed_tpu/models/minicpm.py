"""MiniCPM-SALA model family configs (openbmb MiniCPM-SALA, ``model_type``
``minicpm_sala``, 9B dense, context 524,288).

A pre-norm decoder without biases, untied head, RMSNorm, dense SwiGLU in
every layer, on MiniCPM's scaled residual path: ``h_0 = scale_emb *
embed(ids)``; every branch's output is multiplied by ``scale_depth /
sqrt(num_hidden_layers)`` (the PUBLISHED depth, whatever a cut leaves) before
it is added; the head reads ``rmsnorm(h_L) / (hidden_size /
dim_model_base)``. ``mixer_types`` names each layer's mixer, one to three:

* ``minicpm4`` (here ``"sparse_attention"``): softmax attention, 32 query / 2
  KV heads of 128 (a group of 16), q and k RMS-normed a head, NO positional
  encoding, the output multiplied by ``sigmoid(n W_gate)`` before ``W_o``.
  Beside K and V a layer caches one MEAN-POOLED key every 16 tokens over 32
  tokens a KV head (``kbar_{g,m} = mean(k_{g, 16m .. 16m+31})``). For the query
  at position ``t`` with more than ``dense_len`` tokens of context: ``a_{h,m} =
  softmax_m(q_h . kbar_{g(h),m} / sqrt(d))`` over the ``m`` whose 32 tokens
  exist (``16m + 31 <= t``); ``A_{g,m}`` its sum over the group's heads; the
  score of 64-token block ``j`` the largest ``A_{g,m}`` of the ``m`` whose
  tokens touch it; block 0 and the blocks of the last ``window_size`` tokens
  score +inf; the ``topk`` highest-scoring blocks, one set a KV head, are what
  the query's heads attend (causal softmax over their tokens ``<= t``). At
  ``dense_len`` tokens or under, every token ``<= t`` (InfLLM v2,
  arXiv:2506.07900; ``TransformerConfig.sparse_*``).
* ``lightning-attn`` (here ``"lightning_attention"``): linear attention with a
  scalar decay a head: ``q, k, v = n W_{q,k,v}``, q and k RMS-normed a head
  and ROPED at the token's absolute position, ``q / sqrt(d)``; a float32 state
  ``S`` (128 x 128 a head, zero at a sequence's start), ``S_t = lambda_h
  S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t``, ``lambda_h = exp(-s_h)`` a
  constant of the head and the layer (``transformer.lightning_slopes``); the
  output ``W_o [rmsnorm(o_t) * sigmoid(n_t W_g)]``, the norm over all heads'
  values with one gain vector. Such a layer caches nothing per token
  (``TransformerConfig.state_entry``).

Served through ``InferenceEngineV2`` alone: ``ragged_forward`` unrolls the
layers, ``ops/pallas/lightning.py`` holds the recurrence's two forms, the
indexer is ``flat_model``'s and both paged kernels take its selection
(``ops/pallas/paged_attention.py``). The whole-sequence forwards refuse this
family (``TransformerConfig.unscannable``), and what a sequence's state and
pooled keys forbid until they can be snapshot (the prefix cache, the host
tier, a rewind, the handoff, speculative decoding, int8 KV) refuses by name
in the engine and the state manager.

Not in ``config.json``: ``sparse_config`` (MiniCPM4's published values) and the
decay's formula (Lightning Attention-2); the benchmark's configuration file
lists each under ``assumed``.
"""

import math

from .transformer import TransformerConfig, TransformerLM

_KINDS = {"minicpm4": "sparse_attention", "lightning-attn": "lightning_attention"}
_PERIOD = ("minicpm4", "lightning-attn", "lightning-attn", "lightning-attn")
_PUBLISHED_SPARSE = (0, 9, 16, 17, 22, 29, 30, 31)  # the published order is irregular; the ratio is 1 : 3
_SPARSE_KEYS = {"kernel_size": "sparse_kernel_size", "kernel_stride": "sparse_kernel_stride",
                "block_size": "sparse_block_size", "topk": "sparse_topk", "init_blocks": "sparse_init_blocks",
                "window_size": "sparse_window_size", "dense_len": "sparse_dense_len"}


def minicpm_config(size: str = "sala-9b", **overrides) -> TransformerConfig:
    presets = {
        # two periods whose sparse layers are neighbours once (3 and 4); a group of 3; a selection that a
        # context of 200 tokens already makes: 4 pooled keys a block, 2-3 window blocks + block 0 of top 6
        "tiny": dict(vocab_size=512, hidden_size=64, num_layers=8, num_layers_published=8, num_heads=6,
                     num_kv_heads=2, head_size=16, intermediate_size=128, max_seq_len=2048,
                     lightning_num_heads=4, lightning_head_dim=16, dim_model_base=16, scale_emb=12.0,
                     mixer_types=("lightning-attn", "lightning-attn", "lightning-attn", "minicpm4",
                                  "minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"),
                     sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8, topk=6, init_blocks=1,
                                        window_size=16, dense_len=48)),
        "sala-9b": dict(vocab_size=73448, hidden_size=4096, num_layers=32, num_layers_published=32, num_heads=32,
                        num_kv_heads=2, head_size=128, intermediate_size=16384, max_seq_len=524288,
                        lightning_num_heads=32, lightning_head_dim=128, dim_model_base=256, scale_emb=12.0,
                        mixer_types=tuple("minicpm4" if l in _PUBLISHED_SPARSE else "lightning-attn" for l in range(32)),
                        sparse_config=dict(kernel_size=32, kernel_stride=16, block_size=64, topk=64, init_blocks=1,
                                           window_size=2048, dense_len=8192)),
    }
    base = dict(presets[size], norm="rmsnorm", positions="rotary", mlp="swiglu", use_bias=False,
                tie_embeddings=False, norm_eps=1e-6, rope_theta=10000.0, qk_norm=True, attention_gate=True,
                scale_depth=1.4, rope_layer_types=("lightning_attention", ))
    base.update(overrides)
    # the published keys that are no field of the program's own
    n = base["num_layers"]
    published = base.pop("num_layers_published", None) or n
    first = base.pop("first_layer", 0)        # a depth cut keeps the published list and reads layers first .. first + n - 1
    mixers = tuple(base.pop("mixer_types", None) or _PERIOD * (published // len(_PERIOD) + 1))[first:first + n]
    base["layer_types"] = tuple(_KINDS[m] for m in mixers)
    base.update(lightning_layer_offset=first, lightning_layers_published=published)
    for key, field in _SPARSE_KEYS.items():
        base[field] = base["sparse_config"][key]
    del base["sparse_config"]
    base["embed_scale"] = float(base.pop("scale_emb"))
    base["residual_scale"] = float(base.pop("scale_depth")) / math.sqrt(published)
    base["logit_scale"] = base.pop("dim_model_base") / base["hidden_size"]
    if "sparse_attention" not in base["layer_types"]:
        base["sparse_topk"] = 0
    if "lightning_attention" not in base["layer_types"]:
        base["lightning_num_heads"] = 0
    return TransformerConfig(**base)


def minicpm(size: str = "sala-9b", **overrides) -> TransformerLM:
    return TransformerLM(minicpm_config(size, **overrides))
