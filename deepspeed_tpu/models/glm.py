"""GLM model family configs (zai-org GLM-4.7-Flash, ``model_type``
``glm4_moe_lite``, 30B-A3B).

A pre-norm decoder without biases, untied head, whose attention caches ONE
low-rank latent a token a layer instead of per-head keys and values (latent
attention, MLA):

* attention: ``cq = rmsnorm(x W_qa)`` (``q_lora_rank`` 768), 20 heads
  ``[q_nope | q_rope] = cq W_qb`` (192 | 64); ``[ckv | kr] = x W_kva`` (512 |
  64), ``ckv = rmsnorm(ckv)``, ``kr`` and every ``q_rope`` rotated (all 64
  dims, theta 1e6), ``kr`` ONE key part that all heads share; ``[k_nope_h |
  v_h] = ckv W_kvb`` (192 | 256); score ``(q_nope_h . k_nope_h + q_rope_h .
  kr) / sqrt(256)``. What is cached is ``[ckv | kr]``, 576 values; the ragged
  forward attends in the absorbed form (``W_kvb``'s key part folded into the
  query, its value part into the output), so no per-head K or V is ever
  stored (``TransformerConfig.kv_entry``);
* the first layer has a dense SwiGLU (10,240 wide), the others 64 routed
  experts (SwiGLU 1,536 wide) of which a token's 4 are chosen by ``sigmoid``
  score plus a per-expert bias that no gradient trains (``noaux_tc``, one
  group), weighted by the scores alone, normalised to sum to one and
  multiplied by ``routed_scaling_factor`` 1.8, beside ONE shared expert: the
  router, the shared expert and the leading dense layer are Trinity's fields
  with other numbers.

NOT built: the multi-token-prediction layer (``num_nextn_predict_layers`` 1).
It adds nothing to the model's own logits; it is a drafter.

Served through ``InferenceEngineV2`` alone (``ragged_forward`` unrolls the
layers); the whole-sequence forwards refuse this family
(``TransformerConfig.unscannable``): the scanned block has no latent
attention. The tiny preset's nope, rope, value and latent sizes all differ,
so that a mix-up of two of them fails a test.
"""

from .transformer import TransformerConfig, TransformerLM


def glm_config(size: str = "4.7-flash", **overrides) -> TransformerConfig:
    presets = {
        "tiny": dict(vocab_size=512, hidden_size=64, num_layers=5, num_heads=4, intermediate_size=160,
                     moe_intermediate_size=48, moe_num_experts=8, moe_top_k=2, max_seq_len=2048,
                     q_lora_rank=40, kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16),
        "4.7-flash": dict(vocab_size=154880, hidden_size=2048, num_layers=47, num_heads=20,
                          intermediate_size=10240, moe_intermediate_size=1536, moe_num_experts=64, moe_top_k=4,
                          max_seq_len=202752, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
                          qk_rope_head_dim=64, v_head_dim=256),
    }
    base = dict(presets[size], norm="rmsnorm", positions="rotary", mlp="swiglu", use_bias=False,
                tie_embeddings=False, norm_eps=1e-5, rope_theta=1e6, moe_dropless=True, moe_num_dense_layers=1,
                moe_norm_topk_prob=True, moe_num_shared_experts=1, moe_score_func="sigmoid", moe_route_bias=True,
                moe_route_scale=1.8)
    base.update(overrides)
    base.setdefault("num_kv_heads", base["num_heads"])  # every head has keys and values of its own, made from the latent
    return TransformerConfig(**base)


def glm(size: str = "4.7-flash", **overrides) -> TransformerLM:
    return TransformerLM(glm_config(size, **overrides))
