"""Lightning attention (linear attention with a scalar decay a head) for the
ragged serving path: a float32 state ``S`` ``[dk, dv]`` a head a sequence,
living in a pool of slots, advanced in place.

The rule, a token (``lam = exp(-s)``, a constant of the head and the layer):

    S_t = lam S_{t-1} + k_t v_t^T;  o_t = S_t^T q_t

There is no delta term, no data-dependent decay and no convolution
(``ops/pallas/kda.py`` has the rule that has all three), so a chunk is plain
matmuls and a tile can be MXU-sized. Two forms, chosen by the caller:

* :func:`lightning_step` (kernel ``lightning_recurrent_step``): ONE token a
  row, a read-modify-write of the row's whole state, bound by memory
  bandwidth: ``2 x heads x dk x dv x 4`` bytes a row a layer. The decode
  horizon's step, where token ``i`` is row ``i``.
* :func:`lightning_chunks` (kernel ``lightning_chunk_scan``): a ragged batch
  of rows fed any number of tokens each. A row's tokens are laid into tiles of
  ``TILE`` tokens that start at the row's own first token, so no tile holds
  two rows and a chunk boundary falls wherever the scheduler put it; the last
  tile of a row is padded with zeros, which add nothing, and the state decays
  by the tile's LIVE tokens alone. Inside a tile of ``n`` live tokens, with
  ``D_ab = lam^(a - b)`` for ``a >= b`` and 0 above the diagonal:

      O = ((Q K^T) . D) V + diag(lam^(a + 1)) Q S_0
      S_n = lam^n S_0 + (diag(lam^(n - 1 - b)) K)^T V

  Every exponent is at most zero, so nothing overflows whatever the decay. The
  kernel carries the state through a row's tiles in VMEM and touches the pool
  once a row. A row fed one token takes a tile like any other: at 128 x 128
  that costs what streaming its state does.

Both kernels take the pool flattened over layers, ``[layers * slots, heads,
dk, dv]``, aliased to their output, and the rows' slots as prefetched
scalars, as the delta rule's do. A row whose first token opens its sequence
(``fresh``) starts from zero whatever the slot held. A dead row or tile
(bucket padding) maps to the last live one's block and does nothing, so
padding moves no byte and leaves every state as it was. Off the TPU both run
as ``jax.numpy`` over the same tile quantities (``interpret=True`` runs the
kernels' own bodies through the interpreter). All products are float32 at
``HIGHEST``: a 2,048-token chunk of 32 heads is 9 GFLOP a layer, 0.3 ms at six
bfloat16 passes.
"""

import jax
import jax.numpy as jnp
from jax import lax

TILE = 128
_HI = lax.Precision.HIGHEST
# heads a grid step of either kernel holds (a block of the state is ``_HEAD_BLOCK x dk x dv x 4`` bytes)
_HEAD_BLOCK = 8

KERNEL_NAMES = ("lightning_recurrent_step", "lightning_chunk_scan")


def recurrence_reference(q, k, v, slope, state):
    """The rule as written, token by token (``lax.scan``), float32: ``q, k``
    ``[n, H, dk]``, ``v`` ``[n, H, dv]``, ``slope`` ``[H]``, ``state`` ``[H, dk,
    dv]``. Returns ``(o [n, H, dv], state)``. What both forms are tested
    against."""
    lam = jnp.exp(-slope.astype(jnp.float32))[:, None, None]

    def step(S, x):
        qt, kt, vt = x
        S = lam * S + kt[..., None] * vt[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=_HI)

    f32 = lambda x: x.astype(jnp.float32)
    state, o = lax.scan(step, f32(state), (f32(q), f32(k), f32(v)))
    return o, state


def _head_block(H: int) -> int:
    hb = min(_HEAD_BLOCK, H)
    while H % hb:
        hb -= 1
    return hb


# ---------------------------------------------------------------------------
# one token a row
# ---------------------------------------------------------------------------

def _lightning_step_pallas(cols, vl, pool, slot, fresh, n_live, hb: int, interpret: bool):
    """``cols`` ``[R, H/hb, dk, 128]``: lane ``2 h + {0, 1}`` of a head block
    holds ``k, q`` of its head ``h`` with the key channel on sublanes, laid so
    by XLA; ``vl`` ``[R, H/hb, hb, dv + 128]``: a head's ``v`` then its decay
    ``lam`` broadcast over 128 lanes (the layout of ``kda._kda_step_pallas``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, nb, dk, _ = cols.shape
    dv = pool.shape[-1]

    def live_row(r, n_ref):
        return jnp.maximum(jnp.minimum(r, n_ref[0] - 1), 0)

    def pool_map(j, r, slot_ref, fresh_ref, n_ref):
        return slot_ref[live_row(r, n_ref)], j, 0, 0

    def row_map(j, r, slot_ref, fresh_ref, n_ref):
        return live_row(r, n_ref), j, 0, 0

    def kernel(slot_ref, fresh_ref, n_ref, cols_ref, vl_ref, s_in, o_ref, s_out):
        r = pl.program_id(1)
        n = n_ref[0]

        @pl.when(r < n)
        def _live():
            keep = jnp.where(fresh_ref[r] > 0, 0.0, 1.0)
            for h in range(hb):
                k_col = cols_ref[0, 0, :, 2 * h:2 * h + 1]
                q_col = cols_ref[0, 0, :, 2 * h + 1:2 * h + 2]
                S = s_in[0, h] * (keep * vl_ref[0, 0, h:h + 1, dv:dv + 1]) + k_col * vl_ref[0, 0, h:h + 1, :dv]
                s_out[0, h] = S
                o_ref[0, 0, h:h + 1, :] = jnp.sum(q_col * S, axis=0, keepdims=True)

        @pl.when((n == 0) & (r == 0))
        def _untouched():  # no live row at all: the one block this grid maps goes back as it came
            s_out[...] = s_in[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(nb, R),
        in_specs=[pl.BlockSpec((1, 1, dk, 128), row_map), pl.BlockSpec((1, 1, hb, dv + 128), row_map),
                  pl.BlockSpec((1, hb, dk, dv), pool_map)],
        out_specs=[pl.BlockSpec((1, 1, hb, dv), row_map), pl.BlockSpec((1, hb, dk, dv), pool_map)])
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
    o, pool = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, nb, hb, dv), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={5: 1}, interpret=interpret, name=KERNEL_NAMES[0], **kwargs)(
            slot, fresh, n_live, cols, vl, pool)
    return o, pool


def lightning_step(q, k, v, slope, pool, slot, fresh, n_live, use_pallas: bool = False, interpret: bool = False):
    """One token a row. ``q, k`` ``[R, H, dk]`` float32 (normed, roped, ``q``
    scaled), ``v`` ``[R, H, dv]``, ``slope`` ``[H]`` float32; ``pool``
    ``[slots, H, dk, dv]`` float32 (every layer's slots in one run); ``slot``
    ``[R]`` each row's slot in it, ``fresh`` ``[R]`` rows that start from zero,
    ``n_live`` (traced) the live rows, which come first. Returns ``(o [R, H,
    dv] float32, pool)``; rows past ``n_live`` read and write nothing and
    their ``o`` is undefined."""
    R, H, dk = q.shape
    dv = v.shape[-1]
    slot, fresh = slot.astype(jnp.int32), fresh.astype(jnp.int32)
    n_live = jnp.asarray(n_live, jnp.int32).reshape(1)
    lam = jnp.exp(-slope.astype(jnp.float32))
    f32 = lambda x: x.astype(jnp.float32)
    if use_pallas or interpret:
        hb = _head_block(H)
        nb = H // hb
        # [R, H, 2, dk] -> [R, nb, dk, hb * 2] -> lanes padded to 128
        cols = jnp.stack([f32(k), f32(q)], axis=2).reshape(R, nb, hb * 2, dk)
        cols = jnp.pad(jnp.swapaxes(cols, 2, 3), ((0, 0), (0, 0), (0, 0), (0, 128 - hb * 2)))
        vl = jnp.concatenate([f32(v), jnp.broadcast_to(lam[None, :, None], (R, H, 128))], axis=-1).reshape(
            R, nb, hb, dv + 128)
        o, pool = _lightning_step_pallas(cols, vl, pool, slot, fresh, n_live, hb, interpret)
        return o.reshape(R, H, dv), pool
    live = jnp.arange(R) < n_live[0]
    S = jnp.where((fresh > 0)[:, None, None, None], 0.0, pool[slot])
    S = lam[None, :, None, None] * S + f32(k)[..., None] * f32(v)[:, :, None, :]
    o = jnp.einsum("rhkv,rhk->rhv", S, f32(q), precision=_HI)
    return o, pool.at[jnp.where(live, slot, pool.shape[0])].set(S, mode="drop")


# ---------------------------------------------------------------------------
# any number of tokens a row
# ---------------------------------------------------------------------------

def tile_plan(n_tok, T: int, tile: int = TILE, xp=jnp):
    """The chunk scan's tiles of a ragged batch whose row ``r`` is fed
    ``n_tok[r]`` contiguous tokens, rows in order from flat token 0: a row
    takes ``ceil(n_tok / tile)`` tiles. ``NT = T // tile + R`` tile slots
    (static; every row may end in a partial tile), of which the first
    ``n_tiles`` are live. Returns ``(row, tok0, cnt, first, n_tiles)``, each
    ``[NT]`` but the last: a tile's row, its first flat token, its live tokens,
    whether it opens its row. A dead tile names the last live tile's row and
    holds no token."""
    R = n_tok.shape[0]
    NT = T // tile + R
    n_tok = n_tok.astype(xp.int32)
    tiles = -(-n_tok // tile)
    ends = xp.cumsum(tiles)
    n_tiles = ends[-1]
    t = xp.arange(NT, dtype=xp.int32)
    at = xp.minimum(t, xp.maximum(n_tiles - 1, 0))
    row = xp.minimum(xp.sum((ends[None, :] <= at[:, None]).astype(xp.int32), axis=1), R - 1).astype(xp.int32)
    of_row = xp.stack([n_tok, xp.cumsum(n_tok) - n_tok, ends - tiles], axis=1)[row]   # tokens, first token, first tile
    j = t - of_row[:, 2]
    live = t < n_tiles
    cnt = xp.where(live, xp.clip(of_row[:, 0] - j * tile, 0, tile), 0).astype(xp.int32)
    return row, (of_row[:, 1] + j * tile).astype(xp.int32), cnt, live & (j == 0), n_tiles.astype(xp.int32)


def _tile_math(S0, q, k, v, neg_s, cnt):
    """One tile of ``C`` tokens of one head: ``q, k`` ``[C, dk]`` as the rule
    takes them, ``v`` ``[C, dv]``, zeros at a dead token; ``S0`` ``[dk, dv]``;
    ``neg_s`` ``-s`` of the head as a row ``[1, lanes]`` of equal values (128
    lanes in the kernel, where a ``[1, 1]`` cannot be spread over sublanes and
    lanes at once; one lane off it); ``cnt`` the tile's live tokens (a float32
    scalar). Returns ``(o [C, dv], S_end)``: see the module's docstring."""
    C = q.shape[0]
    spread = lambda n: neg_s if neg_s.shape[-1] == n else neg_s[:, :1]   # the row against a matrix of n lanes
    a = lax.broadcasted_iota(jnp.int32, (C, C), 0) - lax.broadcasted_iota(jnp.int32, (C, C), 1)
    D = jnp.where(a >= 0, jnp.exp(spread(C) * jnp.maximum(a, 0).astype(jnp.float32)), 0.0)
    A = lax.dot_general(q, k, (((1, ), (1, )), ((), ())), precision=_HI, preferred_element_type=jnp.float32) * D
    at = lax.broadcasted_iota(jnp.int32, (C, neg_s.shape[-1]), 0).astype(jnp.float32)   # a token's place, down the sublanes
    column = lambda x, n: x if x.shape[-1] == n else x[:, :1]
    q_in = q * column(jnp.exp(neg_s * (at + 1.0)), q.shape[-1])
    o = jnp.dot(A, v, precision=_HI, preferred_element_type=jnp.float32) + jnp.dot(
        q_in, S0, precision=_HI, preferred_element_type=jnp.float32)
    k_end = k * column(jnp.exp(neg_s * jnp.maximum(cnt - 1.0 - at, 0.0)), k.shape[-1])
    return o, jnp.exp(spread(S0.shape[-1]) * cnt) * S0 + lax.dot_general(
        k_end, v, (((0, ), (0, )), ((), ())), precision=_HI, preferred_element_type=jnp.float32)


def _lightning_chunks_pallas(tiles, neg_s, pool, tile_slot, tile_first, tile_fresh, tile_cnt, n_tiles, hb: int,
                             interpret: bool):
    """``tiles``: ``(q, k, v)``, each ``[NT, H, C, d]`` float32; ``neg_s`` ``[H /
    hb, hb, 8, 128]``: ``-s`` of a head over a whole register."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    NT, H, C, dk = tiles[0].shape
    dv, nb = tiles[2].shape[-1], H // hb

    def live_tile(t, n_ref):
        return jnp.maximum(jnp.minimum(t, n_ref[0] - 1), 0)

    def pool_map(j, t, slot_ref, first_ref, fresh_ref, cnt_ref, n_ref):
        return slot_ref[live_tile(t, n_ref)], j, 0, 0

    def tile_map(j, t, slot_ref, first_ref, fresh_ref, cnt_ref, n_ref):
        return live_tile(t, n_ref), j, 0, 0

    def kernel(slot_ref, first_ref, fresh_ref, cnt_ref, n_ref, q_ref, k_ref, v_ref, s_ref, s_in, o_ref, s_out):
        t = pl.program_id(1)
        n = n_ref[0]

        @pl.when(t < n)
        def _live():
            first = first_ref[t] > 0
            keep = jnp.where(fresh_ref[t] > 0, 0.0, 1.0)
            cnt = cnt_ref[t].astype(jnp.float32)

            def head(h, carry):  # ONE loop body for the block's heads
                # a row's first tile reads the pool; its later ones what the tile before left in the block
                S0 = jnp.where(first, s_in[0, h] * keep, s_out[0, h])
                o, S = _tile_math(S0, q_ref[0, h], k_ref[0, h], v_ref[0, h], s_ref[0, h][:1, :], cnt)
                s_out[0, h] = S
                o_ref[0, h] = o
                return carry

            lax.fori_loop(0, hb, head, 0)

        @pl.when((n == 0) & (t == 0))
        def _untouched():
            s_out[...] = s_in[...]

    tile_spec = lambda d: pl.BlockSpec((1, hb, C, d), tile_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(nb, NT),
        in_specs=[tile_spec(dk), tile_spec(dk), tile_spec(dv), pl.BlockSpec((1, hb, 8, 128), lambda j, t, *refs: (j, 0, 0, 0)),
                  pl.BlockSpec((1, hb, dk, dv), pool_map)],
        out_specs=[tile_spec(dv), pl.BlockSpec((1, hb, dk, dv), pool_map)])
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
    o, pool = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NT, H, C, dv), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={9: 1}, interpret=interpret, name=KERNEL_NAMES[1], **kwargs)(
            tile_slot, tile_first, tile_fresh, tile_cnt, n_tiles, *tiles, neg_s, pool)
    return o, pool


def lightning_chunks(q, k, v, slope, pool, slot, fresh, n_tok, use_pallas: bool = False, interpret: bool = False,
                     tile: int = TILE):
    """A ragged batch. ``q, k`` ``[T, H, dk]`` float32, ``v`` ``[T, H, dv]``:
    the flat tokens, row ``r``'s ``n_tok[r]`` (traced, ``[R]``; 0 for a padded
    row) in a run, rows in order from token 0, whatever is past the last row's
    run ignored; ``slope``, ``pool``, ``slot``, ``fresh`` as
    :func:`lightning_step` takes them. Returns ``(o [T, H, dv] float32,
    pool)`` with the states of the rows that were fed advanced and no other
    touched. ``tile``: the tokens a tile holds (the tests' to vary)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    C = int(tile)
    n_tok, slot, fresh = n_tok.astype(jnp.int32), slot.astype(jnp.int32), fresh.astype(jnp.int32)
    f32 = lambda x: x.astype(jnp.float32)
    neg_s = -slope.astype(jnp.float32)
    starts = jnp.cumsum(n_tok) - n_tok
    row, tok0, cnt, first, n_tiles = tile_plan(n_tok, T, C)
    NT = row.shape[0]
    # what a tile takes, one run of values a token a head, so that ONE gather lays the tiles (zeros where no token is)
    flat = jnp.concatenate([f32(q), f32(k), f32(v)], axis=-1)
    c = jnp.arange(C, dtype=jnp.int32)
    at = jnp.where(c[None, :] < cnt[:, None], tok0[:, None] + c[None, :], T).reshape(-1)
    tiled = jnp.swapaxes(jnp.take(flat, at, axis=0, mode="fill", fill_value=0.0).reshape(NT, C, H, -1), 1, 2)
    operands = jnp.split(tiled, (dk, 2 * dk), axis=-1)
    of_tile = jnp.stack([slot, fresh], axis=1)[row]
    tile_slot, tile_fresh = of_tile[:, 0], jnp.where(first, of_tile[:, 1], 0)
    if use_pallas or interpret:
        hb = _head_block(H)
        o, pool = _lightning_chunks_pallas(operands, jnp.broadcast_to(neg_s.reshape(H // hb, hb, 1, 1), (H // hb, hb, 8, 128)),
                                           pool, tile_slot, first.astype(jnp.int32), tile_fresh, cnt, n_tiles.reshape(1),
                                           hb, interpret)
    else:
        def step(carry, x):
            pool, S = carry
            *tile_ops, s, opens, is_fresh, n, is_live = x
            S0 = jnp.where(opens, jnp.where(is_fresh > 0, 0.0, pool[s]), S)
            o, S = jax.vmap(_tile_math, in_axes=(0, 0, 0, 0, 0, None))(S0, *tile_ops, neg_s.reshape(-1, 1, 1),
                                                                       n.astype(jnp.float32))
            S = jnp.where(is_live, S, S0)
            return (pool.at[jnp.where(is_live, s, pool.shape[0])].set(S, mode="drop"), S), o

        (pool, _), o = lax.scan(step, (pool, jnp.zeros((H, dk, dv), jnp.float32)),
                                (*operands, tile_slot, first, tile_fresh, cnt, jnp.arange(NT) < n_tiles))
    o = jnp.swapaxes(o, 1, 2).reshape(NT * C, H, dv)
    # back to the flat order: token t of row r lies at tile (row r's first tile + i // C), place i % C
    tiles_of = -(-n_tok // C)
    t = jnp.arange(T, dtype=jnp.int32)
    r = jnp.minimum(jnp.sum(((starts + n_tok)[None, :] <= t[:, None]).astype(jnp.int32), axis=1), n_tok.shape[0] - 1)
    of_tok = jnp.stack([starts, jnp.cumsum(tiles_of) - tiles_of], axis=1)[r]   # ONE gather a token
    return o[jnp.clip(of_tok[:, 1] * C + t - of_tok[:, 0], 0, NT * C - 1)], pool
