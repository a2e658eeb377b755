"""The indexer of a learned block-sparse selection (InfLLM v2;
``models/minicpm.py`` has the equations) on the ragged serving path: the
pooled keys a sparse layer caches beside K and V, and the selection a step's
queries make over them, which both paged kernels then read by
(``ops/pallas/paged_attention.py``).

**Pooled keys.** ``kbar_m = mean(k_{stride m .. stride m + kernel - 1})`` a KV
head, ``kernel = 2 stride``. They live on the K/V blocks' own table: pooled
key ``m`` of a sequence is entry ``m % (block / stride)`` of the pooled block
under table column ``stride m // block``, so a sequence's blocks carry its
pooled keys and nothing else is allocated, freed or tracked. Pooled key ``m``
is complete when token ``stride m + kernel - 1`` is cached, so the step that
feeds that token makes it, from the K pool as it stands after the step's
scatter: a kernel straddles chunk and block boundaries, and its first tokens
are then an earlier step's. At most ``T // stride + S`` tokens of a step of
``T`` tokens over ``S`` rows end a kernel, and only those are gathered for.

**Selection.** A tile of query tokens of one row (the paged kernels' own run
and tile rule, :func:`paged_attention._tile_runs`) scores the row's pooled keys
once: ``softmax_m(q_h . kbar_m / sqrt(d))`` over the complete ones, summed
over the heads of a KV head's group, the largest over the ``block / stride +
1`` pooled keys that touch a block, the forced blocks at +inf, the ``topk``
largest a KV head. A token with at most ``dense_len`` tokens of context
selects every visible block. Everything under the name scope
``sparse_index``, tiles a few at a time so that the scores of one pass stay
within ``_SCORE_BYTES``.
"""

import math

import jax
import jax.numpy as jnp

from ....monitor.scopes import SPARSE_INDEX
from ....ops.pallas.paged_attention import _tile_runs

_SCORE_BYTES = 256 << 20


def index_tile(T: int) -> int:
    """Query tokens a tile of the indexer holds, from the program's tokens."""
    return 128 if T >= 256 else 8 if T >= 64 else 1


def pooled_capacity(T: int, S: int, stride: int) -> int:
    """The most tokens of a step that end a pooling kernel."""
    return min(T, T // stride + S)


def update_pooled_keys(cfg, block_size: int, k_flat, p_flat, tables_l, seq_idx, pos, valid):
    """The pooled keys this step's tokens complete, written into ``p_flat``
    ``[layers * blocks * (block / stride), nkv, d]``. ``k_flat``: the flat K
    pool AFTER this step's scatter; ``tables_l``: this layer's block ids in the
    flat pools. A padded token completes nothing."""
    ksize, stride = cfg.sparse_kernel_size, cfg.sparse_kernel_stride
    per_block = block_size // stride
    T, S = pos.shape[0], tables_l.shape[0]
    with jax.named_scope(SPARSE_INDEX):
        ends = valid & (pos >= ksize - 1) & ((pos - (ksize - 1)) % stride == 0)
        tok = jnp.nonzero(ends, size=pooled_capacity(T, S, stride), fill_value=T)[0]
        live = tok < T
        tok = jnp.minimum(tok, T - 1)
        seq, last = seq_idx[tok], pos[tok]
        at = jnp.maximum(last[:, None] - (ksize - 1) + jnp.arange(ksize, dtype=jnp.int32)[None, :], 0)   # [M, kernel]
        keys = k_flat[tables_l[seq[:, None], at // block_size] * block_size + at % block_size]        # [M, kernel, nkv, d]
        pooled = jnp.mean(keys.astype(jnp.float32), axis=1).astype(p_flat.dtype)
        m = (last - (ksize - 1)) // stride
        slot = tables_l[seq, m // per_block] * per_block + m % per_block
        return p_flat.at[jnp.where(live, slot, p_flat.shape[0])].set(pooled, mode="drop")


def block_scores(cfg, block_size: int, q, pooled, pos):
    """``R`` ``[tokens, nkv, blocks]`` float32 of query tokens ``q`` ``[tokens,
    nq, d]`` at positions ``pos`` over ONE row's pooled keys ``pooled``
    ``[blocks * block / stride, nkv, d]`` (in table order): the block scores
    before the forced blocks are raised. What the reference's scores are
    compared with."""
    ksize, stride = cfg.sparse_kernel_size, cfg.sparse_kernel_stride
    per_block = block_size // stride
    n, nq, d = q.shape
    nkv = pooled.shape[1]
    M = pooled.shape[0]
    s = jnp.einsum("qngd,mnd->qngm", q.reshape(n, nkv, nq // nkv, d), pooled,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    whole = (jnp.arange(M, dtype=jnp.int32) * stride + ksize - 1)[None, :] <= pos[:, None]            # [q, M]
    a = jax.nn.softmax(jnp.where(whole[:, None, None, :], s, -1e30), axis=-1)
    A = jnp.where(whole[:, None, :], jnp.sum(a, axis=2), 0.0)                                           # [q, nkv, M]
    by_block = A.reshape(n, nkv, M // per_block, per_block)
    before = jnp.pad(by_block[:, :, :-1, -1], ((0, 0), (0, 0), (1, 0)))   # the pooled key that ends in the block's first tokens
    return jnp.maximum(jnp.max(by_block, axis=-1), before)


def selection_of(cfg, block_size: int, scores, pos):
    """``[tokens, nkv, blocks]`` bool from the block scores: the forced blocks
    (the first ``init_blocks`` and those of the last ``window_size`` tokens) at
    +inf, the blocks past the token's own out of reach, the ``topk`` largest;
    every visible block for a token within ``dense_len`` tokens of context."""
    blocks = scores.shape[-1]
    j = jnp.arange(blocks, dtype=jnp.int32)[None, :]
    own = (pos // block_size)[:, None]
    first = (jnp.maximum(pos - (cfg.sparse_window_size - 1), 0) // block_size)[:, None]
    visible = j <= own
    forced = visible & ((j < cfg.sparse_init_blocks) | (j >= first))
    r = jnp.where(forced[:, None, :], jnp.inf, jnp.where(visible[:, None, :], scores, -jnp.inf))
    # exactly topk, a tie to the lower block (``top_k`` is stable): neighbouring blocks share the pooled key that
    # straddles them, so equal scores are common and a threshold would pass both
    _, chosen = jax.lax.top_k(r, min(cfg.sparse_topk, blocks))
    picked = jnp.any(chosen[..., None] == jnp.arange(blocks, dtype=jnp.int32), axis=-2)
    dense = (pos + 1 <= cfg.sparse_dense_len)[:, None, None]
    return jnp.where(dense, True, picked) & visible[:, None, :]


def select_blocks(cfg, block_size: int, q, p_flat, tables_l, seq_idx, pos, valid):
    """The selection of a step's queries, ``[T, nkv, max_blocks]`` bool (False
    everywhere for a padded token). ``q`` ``[T, nq, d]`` as the attention takes
    it (normed); ``p_flat`` the pooled keys after :func:`update_pooled_keys`."""
    T, nq, d = q.shape
    S, max_blocks = tables_l.shape
    per_block = block_size // cfg.sparse_kernel_stride
    nkv = p_flat.shape[1]
    qt = index_tile(T)
    n_tiles = -(-T // qt) + S + 1
    with jax.named_scope(SPARSE_INDEX):
        tile_id, place = _tile_runs(seq_idx, pos, qt)
        tile_tok = jnp.zeros((n_tiles, qt), jnp.int32).at[tile_id, place].set(jnp.arange(T, dtype=jnp.int32))
        filled = jnp.zeros((n_tiles, qt), bool).at[tile_id, place].set(valid)
        tile_pos = jnp.where(filled, pos[tile_tok], 0)
        tile_seq = seq_idx[tile_tok[:, 0]]
        entry = jnp.arange(per_block, dtype=jnp.int32)

        def one_tile(args):
            toks, at, row = args
            pooled = p_flat[(tables_l[row][:, None] * per_block + entry[None, :]).reshape(-1)]      # [M, nkv, d]
            return selection_of(cfg, block_size, block_scores(cfg, block_size, q[toks], pooled, at), at)

        at_once = max(1, min(n_tiles, _SCORE_BYTES // (qt * nq * max_blocks * per_block * 4)))
        picked = jax.lax.map(one_tile, (tile_tok, tile_pos, tile_seq), batch_size=at_once)        # [n_tiles, qt, nkv, blocks]
        picked = picked & filled[:, :, None, None]
        return picked.reshape(n_tiles * qt, nkv, max_blocks)[tile_id * qt + place] & valid[:, None, None]
