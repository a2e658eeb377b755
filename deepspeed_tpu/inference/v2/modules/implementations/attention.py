"""Ragged paged-attention implementations (reference
``implementations/attention/dense_blocked_attention.py``).

Two real implementations behind one interface:

- ``dense_blocked_attention``: the gather-based jnp oracle — runs anywhere,
  the numerics reference.
- ``paged_pallas_attention``: the Pallas LUT-prefetch paged kernel — the TPU
  serving path; ``implementation_config={'interpret': True}`` runs the decode
  kernel through the Pallas interpreter so CPU CI can cover the kernel's
  program (not its Mosaic lowering).
"""

import numpy as np

from .....models.transformer import alibi_slopes
from .....ops.pallas.paged_attention import (_note_choice, _pallas_paged, paged_attention,
                                             paged_attention_reference)
from ..configs import DSSelfAttentionConfig
from ..interfaces import DSSelfAttentionBase, DSSelfAttentionRegistry


def _alibi(cfg: DSSelfAttentionConfig):
    return alibi_slopes(cfg.num_heads) if cfg.positions == "alibi" else None


@DSSelfAttentionRegistry.register_module
class DenseBlockedAttention(DSSelfAttentionBase):

    @staticmethod
    def name() -> str:
        return "dense_blocked_attention"

    @staticmethod
    def supports_config(config: DSSelfAttentionConfig) -> bool:
        return config.num_heads % max(config.num_kv_heads, 1) == 0

    def __call__(self, q, k_flat, v_flat, tables_l, seq_idx, pos, k_scale=None, v_scale=None,
                 pos_ids=None, mask=None, ctx_pos_ids=None, **latent):
        cfg = self.config
        return paged_attention_reference(q, k_flat, v_flat, tables_l, seq_idx, pos,
                                         cfg.block_size, window=cfg.sliding_window,
                                         alibi=_alibi(cfg), k_scale=k_scale, v_scale=v_scale,
                                         pos_ids=pos_ids, mask=mask, ctx_pos_ids=ctx_pos_ids, **latent)


@DSSelfAttentionRegistry.register_module
class PallasPagedAttention(DSSelfAttentionBase):

    @staticmethod
    def name() -> str:
        return "paged_pallas_attention"

    @staticmethod
    def supports_config(config: DSSelfAttentionConfig) -> bool:
        # the kernel tiles heads on the 8-lane sublane dim and d on 128 lanes
        return (config.num_heads % max(config.num_kv_heads, 1) == 0
                and config.head_dim % 2 == 0)

    def __call__(self, q, k_flat, v_flat, tables_l, seq_idx, pos, k_scale=None, v_scale=None,
                 pos_ids=None, mask=None, ctx_pos_ids=None, **latent):
        """``latent``: ``value_dim`` and ``softmax_scale`` of a latent pool
        (``v_flat`` None), handed on as they come."""
        cfg = self.config
        if mask is not None:
            # token-tree verification: the Pallas grids know only the causal
            # (+window) mask — the tree's ancestor mask routes the verify
            # forward through the gather oracle. A verify chunk is k+1
            # tokens per sequence, so the dense gather costs one prefill-
            # chunk-sized pass per round, not a per-token hot path.
            return paged_attention_reference(q, k_flat, v_flat, tables_l, seq_idx, pos,
                                             cfg.block_size, window=cfg.sliding_window,
                                             alibi=_alibi(cfg), k_scale=k_scale,
                                             v_scale=v_scale, pos_ids=pos_ids, mask=mask,
                                             ctx_pos_ids=ctx_pos_ids, **latent)
        if self.implementation_config.get("interpret", False):
            import jax.numpy as jnp

            al = _alibi(cfg)
            # the interpreter evaluates the decode kernel's body on the CPU: no
            # device kernel of that name runs, and the span says so. Its
            # ``kv_steps`` count every table column (``decode_kv_counts``
            # knows only the device kernel's work list): tests/perfbench
            # holds the CPU twins to that (PERF.md section 7, PR 29). Pools by
            # head (four dimensions) are the tiled kernel's alone: its body, at a tile of 8
            q_tile = 8 if k_flat.ndim == 4 else 1
            _note_choice(q.shape[0], tables_l.shape[0], tables_l.shape[1],
                         {"kernel": "paged_attn_interpreted", "q_tile": q_tile, "blocks_per_step": 1,
                          "rule": "interpret"})
            return _pallas_paged(q, k_flat, v_flat, tables_l, seq_idx.astype(jnp.int32),
                                 pos.astype(jnp.int32), block_size=cfg.block_size,
                                 interpret=True, window=cfg.sliding_window, q_tile=q_tile,
                                 alibi=tuple(np.asarray(al).tolist()) if al is not None else None,
                                 k_scale=k_scale, v_scale=v_scale, **latent)
        # paged_attention itself falls back (loudly) off-TPU / tiny heads
        return paged_attention(q, k_flat, v_flat, tables_l, seq_idx, pos,
                               cfg.block_size, window=cfg.sliding_window, alibi=_alibi(cfg),
                               k_scale=k_scale, v_scale=v_scale, **latent)
