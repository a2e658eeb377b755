"""Config → implementation selection (reference
``inference/v2/modules/heuristics.py``).

``build_modules`` is the single point where an engine decides which concrete
implementation serves each functionality slot. Every slot accepts either
``"auto"`` (policy below), an implementation name, or a
``{"name": ..., "implementation_config": {...}}`` dict; the chosen bundle
goes through the interface registry so third-party implementations
registered with ``@<Interface>Registry.register_module`` are selectable by
config string alone.

Auto policy:
- attention: the Pallas paged kernel when the engine resolved
  ``use_pallas_kernels`` to true (TPU), else the dense gather oracle;
- linear: int8 blockwise when the engine asks for weight quantization
  (decode is weight-stream-bound), else the plain-dtype gemm;
- embedding / unembed / norm: the single TPU implementation each (XLA fuses
  what the reference ships as kernel variants);
- moe (models with experts only): the grouped ragged matmul, the one serving
  MoE path; it is told the router's rule and which experts are held here.

A model whose layers differ in attention kind (``layer_types``) gets one
attention module a kind: ``attention`` serves the window layers,
``attention_full`` the full ones (the same implementation, built without a
window).
"""

from typing import Union

from .configs import (DSEmbeddingsConfig, DSLinearConfig, DSMoEConfig, DSNormConfig,
                      DSSelfAttentionConfig, DSUnembedConfig)
from .interfaces import (DSEmbeddingRegistry, DSLinearRegistry, DSMoERegistry, DSPreNormRegistry,
                         DSSelfAttentionRegistry, DSUnembedRegistry)
from .module_registry import ConfigBundle
from . import implementations  # noqa: F401 — populates the registries


def _bundle(choice: Union[str, dict], default_name: str, config) -> ConfigBundle:
    if isinstance(choice, dict):
        return ConfigBundle(name=choice.get("name", default_name), config=config,
                            implementation_config=choice.get("implementation_config", {}))
    name = default_name if choice in (None, "auto") else choice
    return ConfigBundle(name=name, config=config)


def instantiate_attention(attention_config: DSSelfAttentionConfig, engine_config,
                          use_pallas: bool = False):
    choice = getattr(engine_config.modules, "attention", "auto")
    default = "paged_pallas_attention" if use_pallas else "dense_blocked_attention"
    return DSSelfAttentionRegistry.instantiate_config(_bundle(choice, default, attention_config))


def instantiate_linear(linear_config: DSLinearConfig, engine_config):
    choice = getattr(engine_config.modules, "linear", "auto")
    qw = getattr(engine_config, "quantize_weights", False)
    # quantize_weights: False | True (-> int8) | 4 | 8
    default = ("int4_blockwise_linear" if qw == 4
               else "int8_blockwise_linear" if qw
               else "blas_fp_linear")
    return DSLinearRegistry.instantiate_config(_bundle(choice, default, linear_config))


def instantiate_embed(embed_config: DSEmbeddingsConfig, engine_config):
    choice = getattr(engine_config.modules, "embedding", "auto")
    return DSEmbeddingRegistry.instantiate_config(_bundle(choice, "ragged_embedding", embed_config))


def instantiate_unembed(unembed_config: DSUnembedConfig, engine_config):
    choice = getattr(engine_config.modules, "unembed", "auto")
    return DSUnembedRegistry.instantiate_config(_bundle(choice, "last_token_unembed", unembed_config))


def instantiate_pre_norm(norm_config: DSNormConfig, engine_config):
    choice = getattr(engine_config.modules, "norm", "auto")
    return DSPreNormRegistry.instantiate_config(_bundle(choice, "fused_pre_norm", norm_config))


def build_modules(model_config, engine_config, use_pallas: bool = False) -> dict:
    """Derive every slot's config from the model config and instantiate the
    full module set the ragged forward consumes."""
    mc = model_config
    dt = mc.dtype

    def attention(window):
        return instantiate_attention(DSSelfAttentionConfig(
            num_heads=mc.num_heads, num_kv_heads=mc.num_kv_heads, head_dim=mc.head_dim,
            block_size=engine_config.kv_block_size, sliding_window=window,
            positions=mc.positions, dtype=dt), engine_config, use_pallas=use_pallas)

    extra = {}
    if getattr(mc, "layer_types", None) is not None and "full_attention" in mc.layer_types:
        extra["attention_full"] = attention(None)
    if getattr(mc, "moe_num_experts", 0) > 0:
        # one implementation, so no choice is offered (``ModulesConfig`` has no such slot)
        extra["moe"] = DSMoERegistry.instantiate_config(ConfigBundle(
            name="grouped_gemm_moe", config=DSMoEConfig(
                n_experts=mc.moe_num_experts, top_k=mc.moe_top_k, activation=mc.mlp,
                norm_topk_prob=mc.moe_norm_topk_prob, score_func=mc.moe_score_func,
                route_scale=mc.moe_route_scale, n_held=mc.moe_experts_held,
                first_expert=mc.moe_first_expert, dtype=dt)))
    return {
        **extra,
        "attention": attention(mc.sliding_window),
        "linear": instantiate_linear(DSLinearConfig(dtype=dt), engine_config),
        "embedding": instantiate_embed(DSEmbeddingsConfig(
            positions=mc.positions, embed_layernorm=mc.embed_layernorm, norm=mc.norm,
            norm_eps=mc.norm_eps, scale=getattr(mc, "embed_scale", 1.0), dtype=dt), engine_config),
        "unembed": instantiate_unembed(DSUnembedConfig(
            tie_embeddings=mc.tie_embeddings, norm=mc.norm, norm_eps=mc.norm_eps,
            dtype=dt), engine_config),
        "norm": instantiate_pre_norm(DSNormConfig(norm=mc.norm, norm_eps=mc.norm_eps,
                                                  dtype=dt), engine_config),
    }
