"""The scores of a learned block selection's indexer (InfLLM v2;
``inference/v2/model_implementations/sparse_index.py`` has the rule and the
XLA form this is tested against) as ONE kernel, ``sparse_index_scores``, whose
``[tile, heads, pooled keys]`` float32 scores live in VMEM and never in HBM.

A tile is up to ``q_tile`` query tokens of one row (the paged kernels' tiles,
``paged_attention._tile_runs``). Its rows are laid kv head after kv head and,
inside one, query head after query head (row ``h * q_tile + t``), so that the
sum over a group's heads is whole-array adds. A row's pooled keys are gathered
once a row, entry-major: ``[rows, nkv, block / stride, table columns, d]``, so
that the pooled keys of ``KEY_BLOCK`` table columns are ``block / stride``
plain ``[KEY_BLOCK, d]`` matrices, a column a lane, and the largest over a
block's own pooled keys is an elementwise max of that many arrays.

**The work list** (:func:`index_work`, scalar-prefetched; the grid's length is
the number of items, a dynamic bound): a tile has items only for the key blocks
that hold a pooled key complete at its LAST live token; a tile with no live
token, and a tile whose live tokens all sit at or under ``dense_len``, has
none. For each kv head of a live tile the items walk its key blocks twice:
once for the exact softmax's statistics (a running max and sum a query head,
kept a LANE until the pass ends, so no step reduces across lanes), once more
for ``exp(s - m) / l`` summed over the group's heads. The product is made
again in the second pass: the MXU has the room, and keeping a tile's scores (34
MB a kv head at 62k of context) in VMEM scratch instead read 6 to 8% faster a
call, a tenth of a percent of the cell's busy time (PERF.md section 6, PR 54). A tile of at most ``SHORT_TILE_TOKENS`` live tokens (a
riding one-token row) runs that many slots a head and not ``q_tile``.

What comes out a (tile, kv head, key block) is two ``[KEY_BLOCK, q_tile]``
planes, a table column a sublane and a token a lane: the largest of a block's
own pooled keys, and its LAST one, which also ends in the next block's first
tokens: the epilogue here shifts the second plane a column and takes the max,
so a key block never reads its neighbour's. Whatever no item covers is never
written and is masked there. Tokens lie along the lanes because the ``top_k``
that follows sorts along the columns: XLA hands the sort the layout its operand
arrives in, and a sort ALONG the lanes cost five times one across them (2.27 s
of a 10 s window against 0.46: PERF.md section 6, PR 54).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .paged_attention import _tile_runs

KERNEL_NAME = "sparse_index_scores"
KEY_BLOCK = 128           # table columns a grid step scores: a lane each
SHORT_TILE_TOKENS = 8     # a tile of at most this many live tokens runs this many slots a head
_VMEM_LIMIT = 64 << 20
_LANES = 128
_NEG = -1e30


def index_work(seq_idx, pos, valid, n_tiles: int, q_tile: int, block_size: int, stride: int, ksize: int, dense_len: int,
               key_block: int = KEY_BLOCK, xp=jnp):
    """The indexer's tiles of a ragged batch and the work each brings, from
    the batch's tokens as the paged kernels take them (``valid`` False on the
    pad run). Returns ``(tile_id, place, last, n_kb, slots)``: a token's tile
    and its slot there (:func:`paged_attention._tile_runs`), and a tile ``[n_tiles]``
    its last live position (-1 without one), the key blocks (``key_block``
    table columns, ``key_block * block_size / stride`` pooled keys each) its items
    cover, and the slots a head its body runs. ``xp`` is ``jnp`` inside the
    program and ``numpy`` for the engine's count of the same step
    (:func:`keys_scored`)."""
    tile_id, place = _tile_runs(seq_idx, pos, q_tile, xp=xp)
    mine = (tile_id[None, :] == xp.arange(n_tiles, dtype=xp.int32)[:, None]) & valid[None, :]       # [n_tiles, T]
    last = xp.max(xp.where(mine, pos[None, :], -1), axis=1)
    cnt = xp.sum(mine.astype(xp.int32), axis=1)
    whole = xp.maximum((last - (ksize - 1)) // stride + 1, 0)        # the pooled keys complete at the last token
    keys = key_block * (block_size // stride)
    n_kb = xp.where(last + 1 > dense_len, -(-whole // keys), 0)
    slots = xp.where(cnt <= SHORT_TILE_TOKENS, min(SHORT_TILE_TOKENS, q_tile), q_tile)
    return tile_id, place, last.astype(xp.int32), n_kb.astype(xp.int32), slots.astype(xp.int32)


def keys_scored(seen, new, blocks: int, q_tile: int, block_size: int, stride: int, ksize: int, dense_len: int,
                key_block: int = KEY_BLOCK) -> int:
    """The (query token slot, pooled key) pairs a kv head that the kernel's
    items cover in ONE layer of a step that feeds row ``r`` the ``new[r]``
    tokens after its ``seen[r]``, under a table of ``blocks`` columns (the
    lanes a last key block pads it with hold no pooled key):
    :func:`index_work` on the host."""
    seen, new = np.asarray(seen, np.int64), np.asarray(new, np.int64)
    seq_idx = np.repeat(np.arange(len(new)), new).astype(np.int32)
    pos = np.concatenate([np.arange(s, s + n) for s, n in zip(seen, new)] or [np.zeros(0, np.int64)]).astype(np.int32)
    if not pos.size:
        return 0
    n_tiles = -(-pos.size // q_tile) + len(new) + 1
    *_, n_kb, slots = index_work(seq_idx, pos, np.ones(pos.shape, bool), n_tiles, q_tile, block_size, stride, ksize,
                                 dense_len, key_block, xp=np)
    return int((np.minimum(n_kb.astype(np.int64) * key_block, blocks) * slots).sum()) * (block_size // stride)


def _work_list(n_kb, nkv: int, n_cols: int):
    """``(w_tile, w_head, w_phase, w_kb, total)``: item ``k < total`` is key
    block ``w_kb[k]`` of kv head ``w_head[k]`` of tile ``w_tile[k]`` in pass
    ``w_phase[k]``; a tile's items are consecutive, a kv head's inside them,
    the statistics' pass before the other, key blocks ascending. The arrays'
    length is one more than the most items the shapes allow; ``w_tile`` reads
    ``n_tiles`` from ``total`` on."""
    n_tiles = n_kb.shape[0]
    n_items = 2 * nkv * n_kb
    ends = jnp.cumsum(n_items)
    k = jnp.arange(n_tiles * 2 * nkv * n_cols + 1, dtype=jnp.int32)
    tile = jnp.sum((ends[None, :] <= k[:, None]).astype(jnp.int32), axis=1)
    of_tile = jnp.stack([ends - n_items, jnp.maximum(n_kb, 1)], axis=1)[jnp.minimum(tile, n_tiles - 1)]
    j, n = jnp.maximum(k - of_tile[:, 0], 0), of_tile[:, 1]
    live = tile < n_tiles
    return (tile, jnp.where(live, j // (2 * n), 0), jnp.where(live, (j // n) % 2, 0), jnp.where(live, j % n, 0),
            ends[-1].astype(jnp.int32))


def index_scores(q, pooled, tile_tok, filled, tile_pos, tile_seq, n_kb, slots, block_size: int, stride: int, ksize: int,
                 interpret: bool = False, key_block: int = KEY_BLOCK):
    """``[n_tiles, q_tile, nkv, blocks]`` float32: the block scores (before
    the forced blocks are raised) of every tile's slots over its row's pooled
    keys, 0 where no item covers a (tile, key block), a slot holds no token or
    a pooled key is not complete at the slot's token. ``q`` ``[T, nq, d]``;
    ``pooled`` ``[S, blocks, block / stride, nkv, d]``, a row's pooled keys by
    table column; ``tile_tok``/``filled``/``tile_pos`` ``[n_tiles, q_tile]``: a
    slot's token, whether it holds one, its position (0 without);
    ``tile_seq`` a tile's row; ``n_kb``/``slots``: :func:`index_work`'s.
    ``key_block``: the tests' to vary (off the interpreter the lanes' 128)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, nq, d = q.shape
    _, blocks, P, nkv, _ = pooled.shape
    n_tiles, qt = tile_tok.shape
    g, KB = nq // nkv, int(key_block)
    NKB = -(-blocks // KB)
    G = g * qt
    QL = max(qt, _LANES)      # a tile's tokens along the lanes of what comes out
    short = min(SHORT_TILE_TOKENS, qt)
    scale = 1.0 / math.sqrt(d)
    if qt % 8:
        raise ValueError(f"{KERNEL_NAME}: a tile of {qt} tokens is no whole number of sublanes")

    w_tile, w_head, w_phase, w_kb, total = _work_list(n_kb, nkv, NKB)
    # rows head-major [n_tiles, nkv, g * slots, d], once with every slot and once with a short tile's
    by_head = lambda toks: q[toks.reshape(-1)].reshape(n_tiles, toks.shape[1], nkv, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(n_tiles, nkv, g * toks.shape[1], d)
    q_rows = [by_head(tile_tok)] + ([by_head(tile_tok[:, :short])] if short < qt else [])
    keys = jnp.pad(pooled, ((0, 0), (0, NKB * KB - blocks), (0, 0), (0, 0), (0, 0))).transpose(0, 3, 2, 1, 4)
    pos_rows = jnp.broadcast_to(tile_pos.astype(jnp.int32)[:, :, None], (n_tiles, qt, KB))
    is_short = (slots < qt).astype(jnp.int32)

    def tile_of(i, tile_ref):
        return jnp.minimum(tile_ref[i], n_tiles - 1)

    def q_map(i, tile_ref, head_ref, *refs):
        return tile_of(i, tile_ref), head_ref[i], 0, 0

    def key_map(i, tile_ref, head_ref, phase_ref, kb_ref, seq_ref, short_ref):
        return seq_ref[tile_of(i, tile_ref)], head_ref[i], 0, kb_ref[i], 0

    def pos_map(i, tile_ref, *refs):
        return tile_of(i, tile_ref), 0, 0

    def out_map(i, tile_ref, head_ref, phase_ref, kb_ref, *refs):
        # the statistics' pass writes nothing: it waits on the block the other pass writes first
        return tile_ref[i], head_ref[i], 0, jnp.where(phase_ref[i] == 1, kb_ref[i], 0), 0

    nt_dims = (((1, ), (1, )), ((), ()))  # [rows, d] x [keys, d] -> [rows, keys]

    def kernel(tile_ref, head_ref, phase_ref, kb_ref, seq_ref, short_ref, *rest):
        q_refs, (k_ref, pos_ref, o_ref, s_ref, m_ref, l_ref) = rest[:len(q_rows)], rest[len(q_rows):]
        i = pl.program_id(0)
        tile, phase, kb = tile_ref[i], phase_ref[i], kb_ref[i]

        def compute(q_ref, rph: int):
            """This item with ``rph`` slots a query head."""
            rows = g * rph
            first = (kb * KB + lax.broadcasted_iota(jnp.int32, (1, KB), 1)) * block_size + ksize - 1
            at = pos_ref[0, :rph, :]
            whole = [first + e * stride <= at for e in range(P)]      # [rph, KB]: entry e of the column complete

            def products():
                for e in range(P):
                    s_ref[e, :rows, :] = lax.dot_general(q_ref[0, 0], k_ref[0, 0, e], nt_dims,
                                                         preferred_element_type=jnp.float32) * scale

            def rows_of(h):
                r0 = h * rph
                return pl.ds(r0 if isinstance(r0, int) else pl.multiple_of(r0, 8), rph)

            @pl.when(phase == 0)
            def _statistics():

                @pl.when(kb == 0)
                def _init():
                    m_ref[:rows, :] = jnp.full((rows, KB), _NEG, jnp.float32)
                    l_ref[:rows, :] = jnp.zeros((rows, KB), jnp.float32)

                products()

                def head(h, carry):
                    r = rows_of(h)
                    ss = [jnp.where(whole[e], s_ref[e, r, :], _NEG) for e in range(P)]
                    m_old = m_ref[r, :]
                    m_new = functools.reduce(jnp.maximum, ss, m_old)
                    l_ref[r, :] = l_ref[r, :] * jnp.exp(m_old - m_new) + sum(jnp.exp(s - m_new) for s in ss)
                    m_ref[r, :] = m_new
                    return carry

                lax.fori_loop(0, g, head, 0)

            @pl.when(phase == 1)
            def _shares():

                @pl.when(kb == 0)
                def _across_lanes():  # a row's max and 1 / sum, every lane the same
                    m = m_ref[:rows, :]
                    top = jnp.max(m, axis=-1, keepdims=True)
                    l = jnp.sum(l_ref[:rows, :] * jnp.exp(m - top), axis=-1, keepdims=True)
                    # (a row with no complete key: every share exp(-1e30 + 1e29) = 0)
                    m_ref[:rows, :] = jnp.broadcast_to(jnp.maximum(top, 0.1 * _NEG), (rows, KB))
                    l_ref[:rows, :] = jnp.broadcast_to(1.0 / l, (rows, KB))

                products()
                own = None
                for e in range(P):

                    def head(h, acc, e=e):
                        r = rows_of(h)
                        return acc + jnp.exp(jnp.where(whole[e], s_ref[e, r, :], _NEG) - m_ref[r, :]) * l_ref[r, :]

                    a = lax.fori_loop(0, g, head, jnp.zeros((rph, KB), jnp.float32))
                    own = a if own is None else jnp.maximum(own, a)
                for plane, x in enumerate((own, a)):  # a column a sublane, a token a lane
                    if rph < QL:
                        x = jnp.concatenate([x, jnp.zeros((QL - rph, KB), jnp.float32)], axis=0)
                    o_ref[0, 0, plane] = x.T

        live = tile < n_tiles
        if len(q_refs) == 1:
            pl.when(live)(lambda: compute(q_refs[0], qt))
        else:
            few = short_ref[tile_of(i, tile_ref)] > 0
            pl.when(live & few)(lambda: compute(q_refs[1], short))
            pl.when(live & jnp.logical_not(few))(lambda: compute(q_refs[0], qt))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(jnp.maximum(total, 1), ),
        in_specs=[pl.BlockSpec((1, 1, x.shape[2], d), q_map) for x in q_rows] + [
            pl.BlockSpec((1, 1, P, KB, d), key_map), pl.BlockSpec((1, qt, KB), pos_map)],
        out_specs=pl.BlockSpec((1, 1, 2, KB, QL), out_map),
        scratch_shapes=[pltpu.VMEM((P, G, KB), jnp.float32), pltpu.VMEM((G, KB), jnp.float32),
                        pltpu.VMEM((G, KB), jnp.float32)])
    kwargs = {} if interpret else {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)}
    # (one tile more than there are: what an empty list's one step writes to)
    out = pl.pallas_call(kernel, grid_spec=grid_spec,
                         out_shape=jax.ShapeDtypeStruct((n_tiles + 1, nkv, 2, NKB * KB, QL), jnp.float32),
                         interpret=interpret, name=KERNEL_NAME, **kwargs)(
                             w_tile, w_head, w_phase, w_kb, tile_seq.astype(jnp.int32), is_short, *q_rows, keys, pos_rows)
    covered = (jnp.arange(NKB * KB, dtype=jnp.int32) // KB)[None, :] < n_kb[:, None]                  # [n_tiles, columns]
    out = jnp.where(covered[:, None, None, :, None] & filled[:, None, None, None, :], out[:n_tiles, ..., :qt], 0.0)
    before = jnp.pad(out[:, :, 1, :-1], ((0, 0), (0, 0), (1, 0), (0, 0)))   # the pooled key that ends in the block's first tokens
    return jnp.maximum(out[:, :, 0], before)[:, :, :blocks].transpose(0, 3, 1, 2)
