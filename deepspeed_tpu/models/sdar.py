"""SDAR model family configs (JetLM SDAR-30B-A3B-Chat, ``model_type``
``sdar_moe``): a Qwen3-MoE decoder that generates by masked diffusion over
blocks.

The layer is a pre-norm RMSNorm decoder without biases and with an untied
head: 32 query / 4 KV heads of 128 at a hidden size of 2,048 (a group of 8),
an RMSNorm over each head's 128 dimensions on q and on k (one gain vector
each, shared by the heads) before a rope over the whole head at theta
1,000,000; in every layer 128 experts of width 768 of which a token's top 8
by the float32 softmax over all of them run, their probabilities
renormalised, none dropped and no shared expert (``intermediate_size`` 6,144
is used by no layer).

What no other family here has is the MASK: query ``i`` sees key ``j`` iff
``j // B <= i // B`` on absolute positions, prompt included (causal between
blocks of ``B`` tokens, full inside one: ``diffusion_block_size``), and the
GENERATION: a block starts as ``mask_token_id`` slots and is unmasked over
several forwards, the logits at position ``i`` predicting the token AT ``i``
(``InferenceEngineV2.decode`` of such a model advances whole blocks).

Served through ``InferenceEngineV2`` alone. The whole-sequence forwards
refuse this family by name (``transformer._refuse_mixed_layers``:
``cfg.unscannable`` names the q/k norm and the block-causal mask), and so do
speculative decoding and token-tree verification. Not in ``config.json`` and
taken from the model's published modelling and generation code (the
benchmark's configuration file lists each under ``assumed``): the block
length, the mask token's id, the q/k norm's shape.
"""

from .transformer import TransformerConfig, TransformerLM


def sdar_config(size: str = "30b-a3b", **overrides) -> TransformerConfig:
    presets = {
        # a group of 2, blocks of 4 under KV blocks of 16 in the tests
        "tiny": dict(vocab_size=512, hidden_size=64, num_layers=3, num_heads=4, num_kv_heads=2,
                     head_size=32, intermediate_size=128, moe_intermediate_size=48,
                     moe_num_experts=8, moe_top_k=2, max_seq_len=512, mask_token_id=511),
        "30b-a3b": dict(vocab_size=151936, hidden_size=2048, num_layers=48, num_heads=32,
                        num_kv_heads=4, head_size=128, intermediate_size=6144,
                        moe_intermediate_size=768, moe_num_experts=128, moe_top_k=8,
                        max_seq_len=32768, mask_token_id=151669),
    }
    base = dict(presets[size], norm="rmsnorm", positions="rotary", mlp="swiglu", use_bias=False,
                tie_embeddings=False, norm_eps=1e-6, rope_theta=1000000.0, moe_dropless=True,
                moe_norm_topk_prob=True, qk_norm=True, diffusion_block_size=4)
    base.update(overrides)
    return TransformerConfig(**base)


def sdar(size: str = "30b-a3b", **overrides) -> TransformerLM:
    return TransformerLM(sdar_config(size, **overrides))
