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
1`` pooled keys that touch a block (:func:`tile_scores`), then the forced
blocks at +inf and the ``topk`` largest a KV head (:func:`selection_of`). A
token with at most ``dense_len`` tokens of context selects every visible
block. Everything under the name scope ``sparse_index``.

**Two forms of the scores, one rule.** Where Pallas kernels run and a tile
holds whole sublanes of tokens (:func:`scores_by_kernel`: the chunk programs)
the scores are ONE kernel's, ``sparse_index_scores``
(``ops/pallas/sparse_index.py``), whose grid is a work list of the live (tile,
kv head, key block) items: a tile is scored over the key blocks that hold a
pooled key complete at its last token and no further, a tile without a token
past ``dense_len`` not at all, and the float32 ``[tile, heads, pooled keys]``
scores never leave VMEM. Everywhere else (the CPU, and a program of one-token
tiles: 16 matmul rows a kv head) :func:`block_scores` is the form, XLA's, a
few tiles a pass so that one pass's scores stay within ``_SCORE_BYTES``; it is
what the kernel is tested against. :func:`selection_of` takes either's scores
by tile: the XLA form's a few tiles a pass as ever, the kernel's every tile at
once (its sort is fast with a token a lane, the layout the kernel writes).
"""

import math

import jax
import jax.numpy as jnp

from ....monitor.scopes import SPARSE_INDEX
from ....ops.pallas.sparse_index import index_scores, index_work, keys_scored

# what one pass of the XLA form over a few tiles may hold: their float32 scores
_SCORE_BYTES = 256 << 20


def index_tile(T: int) -> int:
    """Query tokens a tile of the indexer holds, from the program's tokens."""
    return 128 if T >= 256 else 8 if T >= 64 else 1


def scores_by_kernel(T: int, use_pallas: bool, interpret: bool) -> bool:
    """Whether a program of ``T`` tokens takes its block scores from the
    kernel: wherever Pallas kernels run (or their bodies, on the interpreter)
    and a tile holds whole sublanes of tokens. A program of one-token tiles (16
    matmul rows a kv head) keeps the XLA form."""
    return (use_pallas or interpret) and index_tile(T) >= 8


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


def _tiles_at_once(n_tiles: int, qt: int, nq: int, keys: int) -> int:
    """The tiles one pass of the XLA form takes: their float32 scores within ``_SCORE_BYTES``."""
    return max(1, min(n_tiles, _SCORE_BYTES // (qt * nq * keys * 4)))


def tile_scores(cfg, block_size: int, q, p_flat, tables_l, seq_idx, pos, valid, use_pallas: bool = False,
                interpret: bool = False):
    """The block scores of a step's queries by tile, ``[n_tiles, q_tile, nkv,
    max_blocks]`` float32 as :func:`selection_of` takes them (0 in a slot that
    holds no token), and the tiles: ``(tile_id, place, filled, tile_pos)``, a
    token's tile and slot, and ``[n_tiles, q_tile]`` whether a slot holds a
    token and its position. From the kernel (``ops/pallas/sparse_index.py``)
    where :func:`scores_by_kernel` says, which leaves a tile without an item
    at 0: :func:`selection_of` raises the forced blocks and overwrites a token
    within ``dense_len``. Else from :func:`block_scores`, a few tiles a pass."""
    T, nq, d = q.shape
    S, max_blocks = tables_l.shape
    ksize, stride = cfg.sparse_kernel_size, cfg.sparse_kernel_stride
    per_block = block_size // stride
    nkv = p_flat.shape[1]
    qt = index_tile(T)
    n_tiles = -(-T // qt) + S + 1
    tile_id, place, _, n_kb, slots = index_work(seq_idx, pos, valid, n_tiles, qt, block_size, stride, ksize,
                                                cfg.sparse_dense_len)
    tile_tok = jnp.zeros((n_tiles, qt), jnp.int32).at[tile_id, place].set(jnp.arange(T, dtype=jnp.int32))
    filled = jnp.zeros((n_tiles, qt), bool).at[tile_id, place].set(valid)
    tile_pos = jnp.where(filled, pos[tile_tok], 0)
    tile_seq = seq_idx[tile_tok[:, 0]]
    # a row's pooled keys by table column, gathered once a row
    pooled = p_flat[tables_l[:, :, None] * per_block + jnp.arange(per_block, dtype=jnp.int32)]   # [S, blocks, entries, nkv, d]
    if scores_by_kernel(T, use_pallas, interpret):
        scores = index_scores(q, pooled, tile_tok, filled, tile_pos, tile_seq, n_kb, slots, block_size, stride, ksize,
                              interpret=interpret)
    else:
        scores = jax.lax.map(lambda a: block_scores(cfg, block_size, q[a[0]], pooled[a[2]].reshape(-1, nkv, d), a[1]),
                             (tile_tok, tile_pos, tile_seq),
                             batch_size=_tiles_at_once(n_tiles, qt, nq, max_blocks * per_block))
    return scores, (tile_id, place, filled, tile_pos)


def select_blocks(cfg, block_size: int, q, p_flat, tables_l, seq_idx, pos, valid, use_pallas: bool = False,
                  interpret: bool = False):
    """The selection of a step's queries, ``[T, nkv, max_blocks]`` bool (False
    everywhere for a padded token). ``q`` ``[T, nq, d]`` as the attention takes
    it (normed); ``p_flat`` the pooled keys after :func:`update_pooled_keys`;
    ``use_pallas`` / ``interpret`` as the state layers' kernels take them."""
    T, nq, _ = q.shape
    with jax.named_scope(SPARSE_INDEX):
        scores, (tile_id, place, filled, tile_pos) = tile_scores(
            cfg, block_size, q, p_flat, tables_l, seq_idx, pos, valid, use_pallas, interpret)
        n_tiles, qt, nkv, max_blocks = scores.shape
        if scores_by_kernel(T, use_pallas, interpret):
            # every tile at once: the sort takes its operand in the layout it arrives in, and the kernel's, a token a
            # lane, is the one it is fast in; the ``picked`` compare is fused into its reduction and never laid out
            picked = jax.vmap(lambda r, at: selection_of(cfg, block_size, r, at))(scores, tile_pos)
        else:  # with the scores' passes, as many tiles
            at_once = _tiles_at_once(n_tiles, qt, nq, max_blocks * (block_size // cfg.sparse_kernel_stride))
            picked = jax.lax.map(lambda a: selection_of(cfg, block_size, *a), (scores, tile_pos), batch_size=at_once)
        picked = picked & filled[:, :, None, None]                                                 # [n_tiles, qt, nkv, blocks]
        return picked.reshape(n_tiles * qt, nkv, max_blocks)[tile_id * qt + place] & valid[:, None, None]
