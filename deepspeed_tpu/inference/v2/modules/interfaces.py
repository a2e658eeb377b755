"""Functionality interfaces + their registries.

Analog of the reference ``inference/v2/modules/interfaces/`` package
(``attention_base.py``, ``linear_base.py``, ``embedding_base.py``,
``unembed_base.py``, ``pre_norm_base.py``, ``moe_base.py``) collapsed into
one module: each interface fixes the traced call signature its
implementations must honor, so the ragged forward can swap implementations
without re-plumbing.
"""

from abc import abstractmethod
from typing import Type

from .configs import (DSEmbeddingsConfig, DSLinearConfig, DSMoEConfig, DSNormConfig,
                      DSSelfAttentionConfig, DSUnembedConfig)
from .ds_module import DSModuleBase, DSModuleConfig
from .module_registry import DSModuleRegistryBase


class DSSelfAttentionBase(DSModuleBase):
    """Ragged paged attention (reference ``interfaces/attention_base.py``).

    ``__call__(q, k_flat, v_flat, tables_l, seq_idx, pos, k_scale=None,
    v_scale=None, pos_ids=None, mask=None)`` with q: [T, nq, d];
    k_flat/v_flat: flat layer-offset KV pool views [(L*NB*bs), nkv, d];
    tables_l: [S, max_blocks] block tables already offset to layer l;
    seq_idx/pos: [T]; k_scale/v_scale: int8-KV dequant factors
    [nkv, (L*NB*bs)] (None = full-precision pools). ``pos_ids``: logical
    positions for rotary/alibi when they differ from the KV slot positions
    (token-tree verification assigns tree nodes distinct KV slots but
    depth-based logical positions); ``mask``: explicit [T, C] visibility
    (C = table capacity in tokens) REPLACING the causal mask — the tree
    attention mask. Returns context [T, nq, d].
    """

    @staticmethod
    def config_class() -> Type[DSModuleConfig]:
        return DSSelfAttentionConfig

    @abstractmethod
    def __call__(self, q, k_flat, v_flat, tables_l, seq_idx, pos, k_scale=None, v_scale=None,
                 pos_ids=None, mask=None, ctx_pos_ids=None):
        ...


class DSSelfAttentionRegistry(DSModuleRegistryBase):
    registry = {}

    @staticmethod
    def associated_class():
        return DSSelfAttentionBase


class DSLinearBase(DSModuleBase):
    """One gemm: ``__call__(x, w, b=None)`` → ``x @ w (+ b)`` with the
    module's compute dtype (reference ``interfaces/linear_base.py``).
    ``transform_params`` may re-lay-out weights (e.g. int8 quantization)."""

    @staticmethod
    def config_class() -> Type[DSModuleConfig]:
        return DSLinearConfig

    @abstractmethod
    def __call__(self, x, w, b=None):
        ...


class DSLinearRegistry(DSModuleRegistryBase):
    registry = {}

    @staticmethod
    def associated_class():
        return DSLinearBase


class DSEmbeddingBase(DSModuleBase):
    """``__call__(params, token_ids, pos)`` → hidden [T, H]
    (reference ``interfaces/embedding_base.py``)."""

    @staticmethod
    def config_class() -> Type[DSModuleConfig]:
        return DSEmbeddingsConfig

    @abstractmethod
    def __call__(self, params, token_ids, pos):
        ...


class DSEmbeddingRegistry(DSModuleRegistryBase):
    registry = {}

    @staticmethod
    def associated_class():
        return DSEmbeddingBase


class DSUnembedBase(DSModuleBase):
    """``__call__(params, hidden, last_idx)`` → fp32 logits [S, V]: final
    norm, last-token gather, vocab projection
    (reference ``interfaces/unembed_base.py``)."""

    @staticmethod
    def config_class() -> Type[DSModuleConfig]:
        return DSUnembedConfig

    @abstractmethod
    def __call__(self, params, hidden, last_idx):
        ...


class DSUnembedRegistry(DSModuleRegistryBase):
    registry = {}

    @staticmethod
    def associated_class():
        return DSUnembedBase


class DSPreNormBase(DSModuleBase):
    """``__call__(x, scale, bias=None)`` → normalized x
    (reference ``interfaces/pre_norm_base.py``)."""

    @staticmethod
    def config_class() -> Type[DSModuleConfig]:
        return DSNormConfig

    @abstractmethod
    def __call__(self, x, scale, bias=None):
        ...


class DSPreNormRegistry(DSModuleRegistryBase):
    registry = {}

    @staticmethod
    def associated_class():
        return DSPreNormBase


class DSMoEBase(DSModuleBase):
    """``__call__(x, gate_w, expert_up, expert_gate, expert_down, valid=None,
    with_stats=False, layer=None, gate_bias=None)`` → [T, H] token-level top-k
    routed expert MLP (reference ``interfaces/moe_base.py``), the part of it
    that the experts held here give; with ``with_stats`` also int32
    ``[experts_hit, expert_load_max, slots]`` over them. ``padded_rows(tokens)``:
    the rows its kernel's grid covers for a bucket of that many tokens. How
    many slots land on the experts held here is data, not a function of the
    bucket: the program counts them (``slots``)."""

    @staticmethod
    def config_class() -> Type[DSModuleConfig]:
        return DSMoEConfig

    @abstractmethod
    def __call__(self, x, gate_w, expert_up, expert_gate, expert_down, valid=None,
                 with_stats: bool = False, layer=None, gate_bias=None):
        ...

    def padded_rows(self, tokens: int) -> int:
        return tokens * self.config.top_k


class DSMoERegistry(DSModuleRegistryBase):
    registry = {}

    @staticmethod
    def associated_class():
        return DSMoEBase
