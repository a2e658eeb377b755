"""Per-interface module configs (reference ``inference/v2/modules/configs/*``).

Plain dataclasses derived from the model's ``TransformerConfig`` at engine
build (``heuristics.build_modules``); they carry exactly what each module
needs to trace — implementations never reach back into the model config.
"""

from dataclasses import dataclass
from typing import Any, Optional

import jax.numpy as jnp

from .ds_module import DSModuleConfig


@dataclass
class DSSelfAttentionConfig(DSModuleConfig):
    """Paged ragged attention over the flat KV pool
    (reference ``configs/attention_configs.py``)."""
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    block_size: int = 64
    sliding_window: Optional[int] = None
    positions: str = "rotary"  # 'alibi' adds slope-biased scores
    dtype: Any = jnp.bfloat16


@dataclass
class DSLinearConfig(DSModuleConfig):
    """A single gemm of the layer stack (reference ``configs/linear_config.py``)."""
    dtype: Any = jnp.bfloat16


@dataclass
class DSEmbeddingsConfig(DSModuleConfig):
    """Token (+ learned position) embedding with optional embed-layernorm
    (reference ``configs/embedding_config.py``)."""
    positions: str = "rotary"
    embed_layernorm: bool = False
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    scale: float = 1.0  # factor on the token embedding (muP: sqrt(hidden_size))
    dtype: Any = jnp.bfloat16


@dataclass
class DSUnembedConfig(DSModuleConfig):
    """Final norm + last-token gather + vocabulary projection
    (reference ``configs/unembed_config.py`` — its DSUnembed also folds the
    final norm and gather)."""
    tie_embeddings: bool = False
    norm: str = "rmsnorm"
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16


@dataclass
class DSNormConfig(DSModuleConfig):
    """Pre-attention / pre-MLP normalization (reference ``configs/norm_config.py``)."""
    norm: str = "rmsnorm"  # 'rmsnorm' | 'layernorm'
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16


@dataclass
class DSMoEConfig(DSModuleConfig):
    """Token-level top-k routed expert MLP (reference ``configs/moe_config.py``)."""
    n_experts: int = 1
    top_k: int = 1
    activation: str = "swiglu"
    norm_topk_prob: bool = True  # renormalise the top-k probabilities to sum to one
    score_func: str = "softmax"  # 'softmax' over all experts | 'sigmoid', each expert's own
    route_scale: float = 1.0  # factor on the kept weights
    # expert parallelism's share: ``n_held`` of the ``n_experts`` the router
    # scores live here, from ``first_expert`` on; None = all of them
    n_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held
