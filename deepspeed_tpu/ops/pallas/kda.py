"""Kimi Delta Attention (the gated delta rule with a decay per key channel)
for the ragged serving path: a float32 state ``S`` ``[dk, dv]`` a head a
sequence, living in a pool of slots, advanced in place.

The rule, a token (``a = exp(g)`` per key channel, ``b`` the step size):

    S' = diag(a_t) S_{t-1};  S_t = S' + b_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t

Two forms of it, chosen by what a row is fed:

* :func:`kda_step` (kernel ``kda_recurrent_step``): ONE token a row. A
  read-modify-write of the row's whole state, bound by memory bandwidth: ``2
  x heads x dk x dv x 4`` bytes a row a layer and nothing else. The decode
  horizon's step, where every row is such a row, calls it directly.
* :func:`kda_chunks`: a ragged batch of rows fed ANY number of tokens each
  (SplitFuse chunks beside the one-token rows of the sequences that decode).
  It splits the batch by ``n_tok``, which is all it needs to see. A row fed
  exactly one token goes through the recurrent step, wherever it stands in
  the batch: compacted to the front on the device (:func:`step_rows`), its
  token, slot and ``fresh`` gathered, its ``o`` taken back to its flat token.
  A row fed two or more goes through the chunkwise-parallel form (kernel
  ``kda_chunk_scan``). Why: the chunk scan pays the same for every live tile
  whatever it holds (4.0 us a tile a block of 8 heads at 128 x 128 on a v5e,
  32 us a row a layer for a tile that holds one token and seven dead places,
  and its operands laid besides) and the step 13 us for the same row; both
  read and write the row's state once, the step does nothing else (PERF.md
  section 6, PR 44). With
  120 rows decoding beside one prompt's chunk, nine tiles of ten were such
  rows. The two sets of rows are disjoint and both kernels advance the one
  aliased pool, so the order of the two calls does not matter.

  The chunkwise form: a row's tokens are laid into tiles of ``TILE``
  tokens that start at the row's own first token, so no tile holds two rows
  and a chunk boundary falls wherever the scheduler put it: the last tile of
  a row is padded with tokens of decay one and step size zero, which leave
  the state as it is. Inside a tile, with ``G`` the running sum of
  ``g`` in the tile, ``A_ij = b_i sum_c k_ic k_jc exp(G_ic - G_jc)`` (``j <
  i``; every exponent is at most zero, so nothing overflows whatever the
  decay) and ``B_ij`` the same with ``q_i`` (``j <= i``), ``T = (I + A)^-1``
  (``A`` is strictly lower triangular, so the inverse is a product of ``log2
  TILE`` factors; the kernel solves by forward substitution instead), and the
  WY pair ``W = T (b . G-decayed k)``, ``U_v = T (b . v)``; then ``U = U_v - W
  S_0``, ``O = (Q+ - B W) S_0 + B U_v``, ``S_end = diag(exp(G_last)) S_0 +
  K_end^T U``. All of it is the kernel's, a tile's operands one register each
  (a batch of 8 x 8 matrices made by XLA for every tile was 6 MB of code a
  layer a program); it carries the state through a row's tiles in VMEM and
  touches the pool once a row. The tiles' operands are laid by XLA (one
  gather a block) and scanned a BLOCK of ``_TILE_BLOCK`` tiles at a time,
  under a loop that runs as many blocks as hold a live tile: the plan's
  static bound is ``T // TILE + R`` tiles, of which a chunk of 200 tokens
  beside 120 one-token rows fills 25, and laying all of them cost 2.3 ms a
  layer a call with not one token fed. A row that crosses a block goes on
  from the pool, where the block before left its state.

Both kernels take the pool flattened over layers, ``[layers * slots, heads,
dk, dv]``, aliased to their output, and the rows' slots as prefetched
scalars. A row whose first token opens its sequence (``fresh``) starts from
zero whatever the slot held: a freed slot's old state never reaches its next
owner. A dead row or tile (bucket padding) maps to the last live one's block
and does nothing, so padding moves no byte and leaves every state as it was.
Off the TPU both run as ``jax.numpy`` over the same tile quantities
(``interpret=True`` runs the kernels' own bodies through the interpreter).
"""

import jax
import jax.numpy as jnp
from jax import lax

TILE = 8
_HI = lax.Precision.HIGHEST
# heads a grid step of either kernel holds: a block of the state is
# ``_HEAD_BLOCK x dk x dv x 4`` bytes (512 KiB at 128 x 128), in and out, double-buffered
_HEAD_BLOCK = 8
# tiles a call of the chunk scan takes: ``kda_chunks`` lays a block of tiles and scans it, as many times as the
# chunk rows' tiles fill blocks, so that what XLA moves to lay tiles follows the tokens and not the static bound
_TILE_BLOCK = 16

KERNEL_NAMES = ("kda_recurrent_step", "kda_chunk_scan")


def recurrence_reference(q, k, v, g, beta, state):
    """The rule as written, token by token (``lax.scan``), float32: ``q, k, g``
    ``[n, H, dk]``, ``v`` ``[n, H, dv]``, ``beta`` ``[n, H]``, ``state`` ``[H,
    dk, dv]``. Returns ``(o [n, H, dv], state)``. What both forms are tested
    against."""

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt, precision=_HI))
        S = S + kt[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=_HI)

    f32 = lambda x: x.astype(jnp.float32)
    state, o = lax.scan(step, f32(state), tuple(f32(x) for x in (q, k, v, g, beta)))
    return o, state


def _head_block(H: int) -> int:
    hb = min(_HEAD_BLOCK, H)
    while H % hb:
        hb -= 1
    return hb


# ---------------------------------------------------------------------------
# one token a row
# ---------------------------------------------------------------------------

def _step_math(S, a_col, k_col, q_col, v_row, b):
    """One head's step. ``S`` ``[dk, dv]``; ``a_col, k_col, q_col`` ``[dk, 1]``
    (the key channel on sublanes, as the state's rows); ``v_row`` ``[1, dv]``;
    ``b`` a scalar or ``[1, 1]``. Returns ``(o [1, dv], S)``."""
    S = a_col * S
    u = b * (v_row - jnp.sum(k_col * S, axis=0, keepdims=True))
    S = S + k_col * u
    return jnp.sum(q_col * S, axis=0, keepdims=True), S


def _kda_step_pallas(cols, vb, pool, slot, fresh, n_live, hb: int, interpret: bool):
    """``cols`` ``[R, H/hb, dk, 128]``: lane ``3 * h + {0, 1, 2}`` of a head
    block holds ``a, k, q`` of its head ``h`` with the key channel on sublanes,
    laid so by XLA, so that no vector is turned inside the kernel; ``vb`` ``[R,
    H/hb, hb, dv + 128]``: a head's ``v`` then its ``b`` broadcast over 128
    lanes. The block's heads are unrolled, each reading its three columns at
    static lanes: with the heads under one loop body (a tile of ``a, k, q``
    padded to a square and transposed a head, a third of the code) a call of
    128 rows took 3,318 us where this takes 1,997 (a v5e; PERF.md section 6,
    PR 41)."""
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

    def kernel(slot_ref, fresh_ref, n_ref, cols_ref, vb_ref, s_in, o_ref, s_out):
        r = pl.program_id(1)
        n = n_ref[0]

        @pl.when(r < n)
        def _live():
            keep = jnp.where(fresh_ref[r] > 0, 0.0, 1.0)
            for h in range(hb):
                col = lambda i: cols_ref[0, 0, :, 3 * h + i:3 * h + i + 1]
                o, S = _step_math(s_in[0, h] * keep, col(0), col(1), col(2), vb_ref[0, 0, h:h + 1, :dv],
                                  vb_ref[0, 0, h:h + 1, dv:dv + 1])
                s_out[0, h] = S
                o_ref[0, 0, h:h + 1, :] = o

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
            slot, fresh, n_live, cols, vb, pool)
    return o, pool


def kda_step(q, k, v, g, beta, pool, slot, fresh, n_live, use_pallas: bool = False, interpret: bool = False):
    """One token a row. ``q, k, g`` ``[R, H, dk]`` float32 (``q`` and ``k`` as the
    rule takes them, normalised and scaled), ``v`` ``[R, H, dv]``, ``beta``
    ``[R, H]``; ``pool`` ``[slots, H, dk, dv]`` float32 (every layer's slots
    in one run); ``slot`` ``[R]`` each row's slot in it, ``fresh`` ``[R]``
    rows that start from zero, ``n_live`` (traced) the live rows, which come
    first. Returns ``(o [R, H, dv] float32, pool)``; rows past ``n_live`` read
    and write nothing and their ``o`` is undefined."""
    R, H, dk = q.shape
    dv = v.shape[-1]
    slot, fresh = slot.astype(jnp.int32), fresh.astype(jnp.int32)
    n_live = jnp.asarray(n_live, jnp.int32).reshape(1)
    if use_pallas or interpret:
        hb = _head_block(H)
        nb = H // hb
        # [R, H, 3, dk] -> [R, nb, dk, hb * 3] -> lanes padded to 128
        cols = jnp.stack([jnp.exp(g), k, q], axis=2).astype(jnp.float32).reshape(R, nb, hb * 3, dk)
        cols = jnp.pad(jnp.swapaxes(cols, 2, 3), ((0, 0), (0, 0), (0, 0), (0, 128 - hb * 3)))
        vb = jnp.concatenate([v.astype(jnp.float32), jnp.broadcast_to(beta.astype(jnp.float32)[..., None], (R, H, 128))],
                             axis=-1).reshape(R, nb, hb, dv + 128)
        o, pool = _kda_step_pallas(cols, vb, pool, slot, fresh, n_live, hb, interpret)
        return o.reshape(R, H, dv), pool
    live = jnp.arange(R) < n_live[0]
    S = jnp.where((fresh > 0)[:, None, None, None], 0.0, pool[slot])
    f32 = lambda x: x.astype(jnp.float32)
    S = jnp.exp(f32(g))[..., None] * S
    u = f32(beta)[..., None] * (f32(v) - jnp.einsum("rhkv,rhk->rhv", S, f32(k), precision=_HI))
    S = S + f32(k)[..., None] * u[:, :, None, :]
    o = jnp.einsum("rhkv,rhk->rhv", S, f32(q), precision=_HI)
    return o, pool.at[jnp.where(live, slot, pool.shape[0])].set(S, mode="drop")


# ---------------------------------------------------------------------------
# any number of tokens a row
# ---------------------------------------------------------------------------

def _row_tiles(n_tok, xp=jnp, tile: int = TILE):
    """The tiles each row takes in the chunk scan: none for a row fed one
    token (the recurrent step's) or none."""
    return xp.where(n_tok > 1, -(-n_tok // tile), 0)


def step_rows(n_tok, xp=jnp):
    """The rows of a ragged batch fed exactly ONE token, compacted in order
    (no sort: a cumulative sum of the flag, compared with all, as
    :func:`tile_plan` finds a tile's row). Returns ``(rows, place, n_live)``:
    ``rows`` ``[R]``, the first ``n_live`` of them the one-token rows (the
    rest name the last row and are dead); ``place`` ``[R]``, where row ``r``
    stands among them (if it is one)."""
    R = n_tok.shape[0]
    one = (n_tok == 1).astype(xp.int32)
    upto = xp.cumsum(one)
    rows = xp.minimum(xp.sum((upto[None, :] <= xp.arange(R, dtype=xp.int32)[:, None]).astype(xp.int32), axis=1), R - 1)
    return rows.astype(xp.int32), (upto - one).astype(xp.int32), upto[-1].astype(xp.int32)


def tile_plan(n_tok, T: int, xp=jnp, tile: int = TILE):
    """The chunk scan's tiles of a ragged batch whose row ``r`` is fed
    ``n_tok[r]`` contiguous tokens, rows in order from flat token 0: a row of
    two or more tokens takes ``ceil(n_tok / tile)`` tiles (``tile``: this
    rule's ``TILE``, or the selective scan's 128), a row of one token
    none (:func:`step_rows` has it), though its token keeps its place in the
    flat order. ``NT = T // tile + R`` tile slots (static; every row may end
    in a partial tile), of which the first ``n_tiles`` are live. Returns
    ``(row, tok0, cnt, first, n_tiles)``, each ``[NT]`` but the last: a tile's
    row, its first flat token, its live tokens, whether it opens its row. A
    dead tile names the last live tile's row and holds no token."""
    R = n_tok.shape[0]
    NT = T // tile + R
    n_tok = n_tok.astype(xp.int32)
    tiles = _row_tiles(n_tok, xp, tile)
    ends = xp.cumsum(tiles)
    n_tiles = ends[-1]
    t = xp.arange(NT, dtype=xp.int32)
    # a tile's row: the rows whose tiles end at or before it (compared with all: R is small, and a sorted
    # search or a gather an array is a kernel's worth of code each on the TPU)
    at = xp.minimum(t, xp.maximum(n_tiles - 1, 0))
    row = xp.minimum(xp.sum((ends[None, :] <= at[:, None]).astype(xp.int32), axis=1), R - 1).astype(xp.int32)
    of_row = xp.stack([n_tok, xp.cumsum(n_tok) - n_tok, ends - tiles], axis=1)[row]   # ONE gather: tokens, first token, first tile
    j = t - of_row[:, 2]
    live = t < n_tiles
    cnt = xp.where(live, xp.clip(of_row[:, 0] - j * tile, 0, tile), 0).astype(xp.int32)
    return row, (of_row[:, 1] + j * tile).astype(xp.int32), cnt, live & (j == 0), n_tiles.astype(xp.int32)


def _tile_math(S0, q, k, kb, vb, g):
    """One tile of ``C`` tokens of one head, every operand one ``[C, d]`` tile
    (a register at ``C`` = 8, ``d`` = 128): ``q, k`` as the rule takes them,
    ``kb = b . k`` and ``vb = b . v`` (the step size folded in outside, so that
    it need not be turned from a lane into a sublane here), ``g`` the log
    decay, all zero at a dead token; ``S0`` ``[dk, dv]``. Returns ``(o [C, dv],
    S_end)``. See the module's docstring for the algebra; here a matrix of the
    tile's own tokens (``A``, ``B``) is held as its COLUMNS ``[C, 1]``, and
    ``(I + A) X = R`` is solved by forward substitution a column at a time."""
    C = q.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    G = jnp.zeros_like(g)
    for j in range(C):  # the running sum of g over the tile's tokens
        G = G + jnp.where(row >= j, g[j:j + 1, :], 0.0)
    eG = jnp.exp(G)
    a_cols, b_cols = [], []
    for j in range(C):  # every exponent is at most zero: nothing overflows whatever the decay
        e = jnp.exp(jnp.minimum(G - G[j:j + 1, :], 0.0)) * k[j:j + 1, :]
        a_cols.append(jnp.where(row > j, jnp.sum(kb * e, axis=-1, keepdims=True), 0.0))
        b_cols.append(jnp.where(row >= j, jnp.sum(q * e, axis=-1, keepdims=True), 0.0))
    W, Uv = eG * kb, vb
    for j in range(C):  # row j is final once the columns before it are taken off
        W = W - a_cols[j] * W[j:j + 1, :]
        Uv = Uv - a_cols[j] * Uv[j:j + 1, :]
    Qe, Ov = eG * q, jnp.zeros_like(vb)
    for j in range(C):
        Qe = Qe - b_cols[j] * W[j:j + 1, :]
        Ov = Ov + b_cols[j] * Uv[j:j + 1, :]
    both = jnp.dot(jnp.concatenate([Qe, W], axis=0), S0, precision=_HI, preferred_element_type=jnp.float32)
    o = both[:C] + Ov
    U = Uv - both[C:]
    Gc = G[C - 1:C, :]
    dk = S0.shape[0]
    dec_col = jnp.transpose(jnp.broadcast_to(jnp.exp(Gc), (dk, dk)))[:, :1]  # the tile's whole decay a key channel, as a column
    return o, dec_col * S0 + lax.dot_general(k * jnp.exp(Gc - G), U, (((0, ), (0, )), ((), ())), precision=_HI,
                                             preferred_element_type=jnp.float32)


def _kda_chunks_pallas(tiles, pool, tile_slot, tile_first, tile_fresh, n_tiles, hb: int, interpret: bool):
    """``tiles``: ``(q, k, kb, vb, g)``, each ``[NT, H, C, d]`` float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    NT, H, C, dk = tiles[0].shape
    dv, nb = tiles[3].shape[-1], H // hb

    def live_tile(t, n_ref):
        return jnp.maximum(jnp.minimum(t, n_ref[0] - 1), 0)

    def pool_map(j, t, slot_ref, first_ref, fresh_ref, n_ref):
        return slot_ref[live_tile(t, n_ref)], j, 0, 0

    def tile_map(j, t, slot_ref, first_ref, fresh_ref, n_ref):
        return live_tile(t, n_ref), j, 0, 0

    def kernel(slot_ref, first_ref, fresh_ref, n_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref, s_in, o_ref, s_out):
        t = pl.program_id(1)
        n = n_ref[0]

        @pl.when(t < n)
        def _live():
            first = first_ref[t] > 0
            keep = jnp.where(fresh_ref[t] > 0, 0.0, 1.0)

            def head(h, carry):  # ONE loop body for the block's heads
                # a row's first tile reads the pool; its later ones what the tile before left in the block
                S0 = jnp.where(first, s_in[0, h] * keep, s_out[0, h])
                o, S = _tile_math(S0, q_ref[0, h], k_ref[0, h], kb_ref[0, h], vb_ref[0, h], g_ref[0, h])
                s_out[0, h] = S
                o_ref[0, h] = o
                return carry

            lax.fori_loop(0, hb, head, 0)

        @pl.when((n == 0) & (t == 0))
        def _untouched():
            s_out[...] = s_in[...]

    tile_spec = lambda d: pl.BlockSpec((1, hb, C, d), tile_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(nb, NT),
        in_specs=[tile_spec(dk), tile_spec(dk), tile_spec(dk), tile_spec(dv), tile_spec(dk),
                  pl.BlockSpec((1, hb, dk, dv), pool_map)],
        out_specs=[tile_spec(dv), pl.BlockSpec((1, hb, dk, dv), pool_map)])
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
    o, pool = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NT, H, C, dv), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={9: 1}, interpret=interpret, name=KERNEL_NAMES[1], **kwargs)(
            tile_slot, tile_first, tile_fresh, n_tiles, *tiles, pool)
    return o, pool


def kda_chunks(q, k, v, g, beta, pool, slot, fresh, n_tok, use_pallas: bool = False, interpret: bool = False):
    """A ragged batch. ``q, k, g`` ``[T, H, dk]`` float32, ``v`` ``[T, H,
    dv]``, ``beta`` ``[T, H]``: the flat tokens, row ``r``'s ``n_tok[r]``
    (traced, ``[R]``; 0 for a padded row) in a run, rows in order from token 0,
    whatever is past the last row's run ignored; ``pool``, ``slot``, ``fresh``
    as :func:`kda_step` takes them. Returns ``(o [T, H, dv] float32, pool)``
    with the states of the rows that were fed advanced and no other touched.

    The rows fed exactly ONE token, wherever they stand, go through
    :func:`kda_step` (13 us a row a layer at 64 heads of 128 x 128 on a v5e,
    where a tile of the chunk scan that holds one token costs 32); the rows
    fed two or more through the chunk scan, whose plan gives the others no
    tile, a block of ``_TILE_BLOCK`` tiles laid and scanned at a time for as
    many blocks as hold a live tile (a chunk of 200 tokens beside 120
    one-token rows: 3,173 us a layer where the parent took 6,925, PERF.md
    section 6, PR 44). The plain path, ``interpret`` and the chip make the
    same split. A batch with no one-token row and one with nothing else both
    run: the step with no live row hands the one block it maps back as it
    came, and the chunk scan with no live tile is not called at all."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    n_tok, slot, fresh = n_tok.astype(jnp.int32), slot.astype(jnp.int32), fresh.astype(jnp.int32)
    f32 = lambda x: x.astype(jnp.float32)
    starts = jnp.cumsum(n_tok) - n_tok
    # the one-token rows, live rows first as the step takes them, each with its token, slot and ``fresh``
    rows1, place1, n_one = step_rows(n_tok)
    tok1 = jnp.minimum(starts[rows1], T - 1)
    o1, pool = kda_step(q[tok1], k[tok1], v[tok1], g[tok1], beta[tok1], pool, slot[rows1], fresh[rows1], n_one,
                        use_pallas=use_pallas, interpret=interpret)
    b = f32(beta)[..., None]
    # what a tile takes, one run of values a token a head, so that ONE gather lays a block: q, k, b . k, b . v, g
    flat = jnp.concatenate([f32(q), f32(k), b * f32(k), b * f32(v), f32(g)], axis=-1)
    row, tok0, cnt, first, n_tiles = tile_plan(n_tok, T)
    NB = min(_TILE_BLOCK, row.shape[0])
    pad = -row.shape[0] % NB   # whole blocks: a padding tile is one more dead one
    row = jnp.pad(row, (0, pad), mode="edge")
    tok0, cnt, first = (jnp.pad(a, (0, pad)) for a in (tok0, cnt, first))
    NT = row.shape[0]
    of_tile = jnp.stack([slot, fresh], axis=1)[row]
    c = jnp.arange(TILE, dtype=jnp.int32)
    hb = _head_block(H)

    def block(i, carry):
        """Tiles ``[i NB, (i + 1) NB)``: their operands laid by XLA, ``[NB, H, C, d]`` each (zeros where no token
        is), through the kernel, their ``o`` into its place among the tiles'. A row that began in an earlier
        block goes on from what that block left in the pool."""
        pool, o_tiles = carry
        cut = lambda a: lax.dynamic_slice_in_dim(a, i * NB, NB)
        at = jnp.where(c[None, :] < cut(cnt)[:, None], cut(tok0)[:, None] + c[None, :], T).reshape(-1)   # a dead token reads the fill
        tiled = jnp.swapaxes(jnp.take(flat, at, axis=0, mode="fill", fill_value=0.0).reshape(NB, TILE, H, -1), 1, 2)
        operands = jnp.split(tiled, (dk, 2 * dk, 3 * dk, 3 * dk + dv), axis=-1)
        opens = cut(first)
        from_pool = opens | (jnp.arange(NB) == 0)
        tile_slot, tile_fresh = cut(of_tile[:, 0]), jnp.where(opens, cut(of_tile[:, 1]), 0)
        live = jnp.clip(n_tiles - i * NB, 0, NB)
        if use_pallas or interpret:
            o, pool = _kda_chunks_pallas(operands, pool, tile_slot, from_pool.astype(jnp.int32), tile_fresh, live.reshape(1),
                                         hb, interpret)
        else:
            def step(carry, x):
                pool, S = carry
                *tile, s, reads, is_fresh, is_live = x
                S0 = jnp.where(reads, jnp.where(is_fresh > 0, 0.0, pool[s]), S)
                o, S = jax.vmap(_tile_math)(S0, *tile)
                S = jnp.where(is_live, S, S0)
                return (pool.at[jnp.where(is_live, s, pool.shape[0])].set(S, mode="drop"), S), o

            (pool, _), o = lax.scan(step, (pool, jnp.zeros((H, dk, dv), jnp.float32)),
                                    (*operands, tile_slot, from_pool, tile_fresh, jnp.arange(NB) < live))
        o = jnp.swapaxes(o, 1, 2).reshape(NB * TILE, H, dv)
        return pool, lax.dynamic_update_slice_in_dim(o_tiles, o, i * NB * TILE, axis=0)

    # as many blocks as hold a live tile, and no more: what laying tiles costs follows the chunk rows' tokens
    pool, o = lax.fori_loop(0, -(-n_tiles // NB), block, (pool, jnp.zeros((NT * TILE, H, dv), jnp.float32)))
    # back to the flat order: token t of a chunk row r lies at tile (row r's first tile + i // C), place i % C;
    # the token of a one-token row where the step left its row
    tiles_of = _row_tiles(n_tok)
    t = jnp.arange(T, dtype=jnp.int32)
    r = jnp.minimum(jnp.sum(((starts + n_tok)[None, :] <= t[:, None]).astype(jnp.int32), axis=1), n_tok.shape[0] - 1)
    of_tok = jnp.stack([starts, jnp.cumsum(tiles_of) - tiles_of, place1, n_tok], axis=1)[r]   # ONE gather a token
    o = o[jnp.clip(of_tok[:, 1] * TILE + t - of_tok[:, 0], 0, NT * TILE - 1)]
    return jnp.where((of_tok[:, 3] == 1)[:, None, None], o1[of_tok[:, 2]], o), pool
