"""Embedding implementation (reference
``implementations/embedding/ragged_embedding.py``): token gather + optional
learned-position add + optional embed layernorm over the flat ragged batch."""

import jax.numpy as jnp

from .....models.transformer import _norm
from ..configs import DSEmbeddingsConfig
from ..interfaces import DSEmbeddingBase, DSEmbeddingRegistry


@DSEmbeddingRegistry.register_module
class RaggedEmbedding(DSEmbeddingBase):

    @staticmethod
    def name() -> str:
        return "ragged_embedding"

    @staticmethod
    def supports_config(config: DSEmbeddingsConfig) -> bool:
        return True

    def __call__(self, params, token_ids, pos):
        cfg = self.config
        x = params["embed"]["embedding"].astype(cfg.dtype)[token_ids]
        if cfg.scale != 1.0:
            # in float32: sqrt(3072) rounded to bf16 first would be 0.13% off at every token
            x = (x.astype(jnp.float32) * cfg.scale).astype(cfg.dtype)
        if cfg.positions == "learned":
            x = x + params["pos_embed"]["embedding"].astype(cfg.dtype)[pos]
        if cfg.embed_layernorm:
            en = params["embed_norm"]
            x = _norm(x, en["scale"], en.get("bias"), cfg.norm, cfg.norm_eps)
        return x
