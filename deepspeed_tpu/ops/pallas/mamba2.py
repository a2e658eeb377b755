"""Mamba-2's selective scan (a state-space layer's recurrence) for the ragged
serving path: a float32 state ``S`` ``[P, N]`` a head a sequence (``P`` the
head's width, ``N`` the state's), living in a pool of slots, advanced in place.

The rule, a token (``dt`` the step after its softplus, ``A_h < 0`` a constant
of the head, ``B`` and ``C`` shared by the heads of a group):

    S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) (x) B_t;  y_t = S_t C_t

The decay is made from the INPUT, a scalar a head a token, which is what parts
this rule from ``ops/pallas/lightning.py``'s (a constant of the head: a tile's
decays are powers of one number there and a running sum here) and from
``ops/pallas/kda.py``'s (a decay a key channel and a delta term). The state is
laid ``[P, N]`` with the state width LAST: at the published 64 x 128 the 128
lie on the lanes and a head's state is eight whole registers, where ``[N, P]``
would put 64 values on 128 lanes and take twice the bytes in the chip's tiled
memory (lightning's ``[dk, dv]`` is this rule's ``[N, P]``: one body for both
would cost that layout or a transpose a call, so the file is its own). The
``D`` skip, the convolution before and the gated norm after are the caller's
(``flat_model``'s state-space mixer), in XLA.

Two forms, split by what a row is fed as the delta rule's are:

* :func:`mamba2_step` (kernel ``mamba2_recurrent_step``): ONE token a row, a
  read-modify-write of the row's whole state, bound by memory bandwidth: ``2 x
  heads x P x N x 4`` bytes a row a layer. The decode horizon's step, where
  token ``i`` is row ``i``. A grid step holds as many of a row's GROUPS as
  VMEM takes (``_groups_per_step``: all eight at the published widths, where
  a row's layer state is ONE contiguous 2 MiB block each way and a layer's
  grid 256 steps for 256 rows; a step of one group's 256 KiB ran at 55% of
  the memory's stream, PERF.md section 6, PR 52). ``dt x`` comes as ONE tile
  a step (the head's width on sublanes, lane ``j`` the step's head ``j``) and
  ``y`` goes back the same way, one transposition a row each laid by XLA, so
  that no vector is turned in the kernel; ``B``, ``C`` and the decays come as
  rows over the lanes, a group's ``B`` and ``C`` taken once for its heads.
  The body takes the step's heads one at a time, whole, every product on the
  vector unit in float32, and leaves a head's ``y`` on the head's lane by a
  tree of merges (``_LaneSums``: a select and one turn of the cross-lane unit
  a head, where a reduction a head costs seven and was what the copies did
  not hide); the call is jitted so that a program's layers share one trace
  of it.
* :func:`mamba2_chunks`: a ragged batch of rows fed any number of tokens. A
  row fed exactly one token goes through the recurrent step wherever it
  stands (``kda.step_rows``); a row fed two or more through the chunk scan
  (kernel ``mamba2_chunk_scan``): its tokens laid into tiles of ``TILE``
  tokens (the published ``chunk_size``) that start at the row's own first
  token, the last tile padded with tokens of ``dt = 0`` and ``x = B = C = 0``,
  which decay nothing and add nothing. Inside a tile, with ``c_t`` the running
  sum of ``dt A`` and ``L_ts = exp(c_t - c_s)`` for ``t >= s`` (every exponent
  at most zero), 0 above the diagonal:

      Y = ((C B^T) . L) X + diag(exp(c)) C S_0^T
      S_end = exp(c_last) S_0 + (diag(exp(c_last - c)) X)^T B

  ``C B^T`` is made once a group and shared by its heads. The kernel carries
  the state through a row's tiles in VMEM and touches the pool once a row.
  Tiles are laid by XLA a block of ``TILE_BLOCK`` at a time under a loop that
  runs as many blocks as hold a live tile.

Both kernels take the pool flattened over layers, ``[layers * slots, heads, P,
N]``, aliased to their output, and the rows' slots as prefetched scalars. A
row whose first token opens its sequence (``fresh``) starts from zero whatever
the slot held. A dead row or tile (bucket padding) maps to the last live one's
block and does nothing, so padding moves no byte and leaves every state as it
was. Off the TPU both run as ``jax.numpy`` over the same tile quantities
(``interpret=True`` runs the kernels' own bodies through the interpreter).
Products are float32 at ``HIGHEST``.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .kda import step_rows, tile_plan as kda_tile_plan

TILE = 128
_HI = lax.Precision.HIGHEST
# tiles a call of the chunk scan takes (``kda.TILE_BLOCK``'s reason: what XLA moves to lay tiles follows the
# tokens and not the static bound of a partial tile a row)
TILE_BLOCK = 4

KERNEL_NAMES = ("mamba2_recurrent_step", "mamba2_chunk_scan")

_LANES = 128
# what the recurrent step's buffers may take of VMEM (``_groups_per_step``): at the published widths a row's whole
# layer state is 2 MiB, read and written in two buffers each
_STEP_VMEM_BYTES = 24 << 20


def recurrence_reference(x, B, C, dt, A, state):
    """The rule as written, token by token (``lax.scan``), float32: ``x`` ``[n,
    H, P]``, ``B, C`` ``[n, G, N]``, ``dt`` ``[n, H]``, ``A`` ``[H]``, ``state``
    ``[H, P, N]``. Returns ``(y [n, H, P], state)``. What both forms are tested
    against."""
    f32 = lambda a: a.astype(jnp.float32)
    hb = x.shape[1] // B.shape[1]

    def step(S, xs):
        xt, Bt, Ct, dtt = xs
        Bh, Ch = jnp.repeat(Bt, hb, axis=0), jnp.repeat(Ct, hb, axis=0)
        S = jnp.exp(dtt * f32(A))[:, None, None] * S + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, Ch, precision=_HI)

    state, y = lax.scan(step, f32(state), (f32(x), f32(B), f32(C), f32(dt)))
    return y, state


# ---------------------------------------------------------------------------
# one token a row
# ---------------------------------------------------------------------------

def _tile_lanes(heads: int) -> int:
    return -(-heads // _LANES) * _LANES


def _step_operand_bytes(gs: int, hb: int, P: int, N: int) -> int:
    """A grid step's small operands: the ``dt x`` tile in, the ``y`` tile out
    (a head a lane, whole tiles of lanes), the rows of B, C and the decays."""
    return (2 * P * _tile_lanes(gs * hb) + (2 * gs + gs * hb) * N) * 4


def _step_vmem_bytes(gs: int, hb: int, P: int, N: int) -> int:
    """What a grid step of ``gs`` groups keeps in VMEM: the state's block read
    and the one written and the step's small operands, two buffers each."""
    return 2 * (2 * gs * hb * P * N * 4 + _step_operand_bytes(gs, hb, P, N))


def _groups_per_step(G: int, hb: int, P: int, N: int) -> int:
    """Groups of a row one grid step of ``mamba2_recurrent_step`` takes: the
    largest divisor of ``G`` whose buffers fit ``_STEP_VMEM_BYTES`` (all eight
    at the published widths, a row's whole layer state as ONE contiguous
    block), at least one. It follows from the static shapes alone: no
    argument, field or variable changes it."""
    return max([gs for gs in range(1, G + 1) if G % gs == 0 and _step_vmem_bytes(gs, hb, P, N) <= _STEP_VMEM_BYTES],
               default=1)


def step_operand_bytes(H: int, G: int, P: int, N: int) -> int:
    """What XLA lays a ROW for the recurrent step beside the pool, over the
    row's grid steps (``engine_v2`` sizes the K/V pool by it)."""
    gs = _groups_per_step(G, H // G, P, N)
    return (G // gs) * _step_operand_bytes(gs, H // G, P, N)


class _LaneSums:
    """The sums over the lanes of up to ``W`` arrays ``[rows, W]`` (``W`` a
    power of two), array ``j``'s on lane ``j`` of ONE array: a tree of merges,
    each a select of two arrays' halves and one turn (level ``k`` folds lanes
    ``2^k`` apart and leaves bit ``k`` of a lane to say which of its two
    arrays the lane sums), so that an array costs one turn on the cross-lane
    unit where a reduction of its own costs one a halving. Arrays are merged
    as they come: no more than one a level waits."""

    def __init__(self, lane, roll):
        self.lane, self.roll, self.W = lane, roll, lane.shape[1]
        self.waiting = []   # (level, array), levels falling
        self.count = 0
        self.firsts = {}    # a level's lanes whose bit is clear, made once

    def _merge(self, a, b, level: int):
        if level not in self.firsts:
            self.firsts[level] = (self.lane & (1 << level)) == 0
        first = self.firsts[level]
        if b is None:   # no array came for the other half: its lanes sum nothing
            return jnp.where(first, a, 0.0) + self.roll(jnp.where(first, 0.0, a), 1 << level, 1)
        return jnp.where(first, a, b) + self.roll(jnp.where(first, b, a), 1 << level, 1)

    def add(self, a):
        level = 0
        while self.waiting and self.waiting[-1][0] == level:
            a, level = self._merge(self.waiting.pop()[1], a, level), level + 1
        self.waiting.append((level, a))
        self.count += 1

    def result(self):
        """``[rows, W]``: lane ``l`` the sum of array ``l`` mod the count rounded up to a power of two."""
        level, a = self.waiting.pop()
        while self.waiting or (1 << level) < self.count:
            if self.waiting and self.waiting[-1][0] == level:
                a = self._merge(self.waiting.pop()[1], a, level)
            else:
                a = self._merge(a, None, level)
            level += 1
        while (1 << level) < self.W:   # every lane of an array's class takes the whole sum
            a, level = a + self.roll(a, 1 << level, 1), level + 1
        return a


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))   # a program's layers share ONE trace of the body
def _mamba2_step_pallas(xt, rows, pool, slot, n_live, hb: int, interpret: bool):
    """``xt`` ``[R, steps, P, lanes]``: lane ``j`` of a step's tile holds ``dt
    x`` of its head ``j``, the head's width on sublanes; ``rows`` ``[R, steps,
    2 gs + gs hb, N]``: the step's ``gs`` groups' ``B``, then their ``C``, then
    each of its heads' decay over every lane (0 for a row that starts from
    zero). Returns ``y`` laid as ``xt``, and the pool."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, steps, P, lanes = xt.shape
    N = pool.shape[-1]
    gs = rows.shape[2] // (2 + hb)
    nh = gs * hb
    if N % _LANES and N & (N - 1):
        raise ValueError(f"mamba2_recurrent_step: a state of {N} is neither whole tiles of {_LANES} lanes nor a power of two")
    W = min(N, _LANES)             # the lanes a tree of sums runs over, and the heads it takes

    def live_row(r, n_ref):
        return jnp.maximum(jnp.minimum(r, n_ref[0] - 1), 0)

    def pool_map(j, r, slot_ref, n_ref):
        return slot_ref[live_row(r, n_ref)], j, 0, 0

    def row_map(j, r, slot_ref, n_ref):
        return live_row(r, n_ref), j, 0, 0

    def kernel(slot_ref, n_ref, x_ref, rows_ref, s_in, o_ref, s_out):
        r = pl.program_id(1)
        n = n_ref[0]

        @pl.when(r < n)
        def _live():
            # a head at a time, whole: the products stay on the vector unit in float32, and the heads' ``y`` leave
            # as ONE tile, a head a lane (a pass over 8 sublanes of every head at a time under a loop read 1,883 us
            # for 256 rows where this reads 1,736: a loop's passes do not overlap, PERF.md section 6, PR 52)
            lane = lax.broadcasted_iota(jnp.int32, (P, W), 1)
            for first in range(0, nh, W):
                x = x_ref[0, 0, :, first:first + W]
                sums = _LaneSums(lane, pltpu.roll)
                for head in range(first, min(first + W, nh)):
                    g = head // hb
                    if head % hb == 0 or head == first:   # a group's B and C once for its heads
                        b_row, c_row = rows_ref[0, 0, g:g + 1, :], rows_ref[0, 0, gs + g:gs + g + 1, :]
                    S = s_in[0, head] * rows_ref[0, 0, 2 * gs + head:2 * gs + head + 1, :] \
                        + x[:, head - first:head - first + 1] * b_row
                    s_out[0, head] = S
                    prod = S * c_row
                    sums.add(functools.reduce(jnp.add, [prod[:, c:c + W] for c in range(0, N, W)]))   # a state of several tiles of lanes, folded
                o_ref[0, 0, :, first:first + W] = sums.result()

        @pl.when((n == 0) & (r == 0))
        def _untouched():  # no live row at all: the one block this grid maps goes back as it came
            s_out[...] = s_in[...]

    of_row = lambda a: pl.BlockSpec((1, 1) + a.shape[2:], row_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(steps, R),
        in_specs=[of_row(xt), of_row(rows), pl.BlockSpec((1, nh, P, N), pool_map)],
        out_specs=[of_row(xt), pl.BlockSpec((1, nh, P, N), pool_map)])
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                                         vmem_limit_bytes=_STEP_VMEM_BYTES + (8 << 20))   # and the body's own
    o, pool = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(xt.shape, jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={4: 1}, interpret=interpret, name=KERNEL_NAMES[0], **kwargs)(
            slot, n_live, xt, rows, pool)
    return o, pool


def mamba2_step(x, B, C, dt, A, pool, slot, fresh, n_live, use_pallas: bool = False, interpret: bool = False):
    """One token a row. ``x`` ``[R, H, P]``, ``B, C`` ``[R, G, N]`` (after the
    convolution and its SiLU), ``dt`` ``[R, H]`` float32 (after its softplus),
    ``A`` ``[H]`` float32, negative; ``pool`` ``[slots, H, P, N]`` float32
    (every layer's slots in one run); ``slot`` ``[R]`` each row's slot in it,
    ``fresh`` ``[R]`` rows that start from zero, ``n_live`` (traced) the live
    rows, which come first. Returns ``(y [R, H, P] float32, pool)``, ``y``
    without the ``D`` skip; rows past ``n_live`` read and write nothing and
    their ``y`` is undefined."""
    R, H, P = x.shape
    G, N = B.shape[1:]
    hb = H // G
    slot, fresh = slot.astype(jnp.int32), fresh.astype(jnp.int32)
    n_live = jnp.asarray(n_live, jnp.int32).reshape(1)
    f32 = lambda a: a.astype(jnp.float32)
    dt = f32(dt)
    xdt, decay = f32(x) * dt[..., None], jnp.exp(dt * f32(A))
    if use_pallas or interpret:
        gs = _groups_per_step(G, hb, P, N)
        steps, nh = G // gs, gs * hb
        # [R, steps, nh, P] -> [R, steps, P, nh]: ONE transposition a row, the lanes padded to whole tiles
        xt = jnp.pad(jnp.swapaxes(xdt.reshape(R, steps, nh, P), 2, 3), ((0, 0), ) * 3 + ((0, _tile_lanes(nh) - nh), ))
        starts = jnp.where((fresh > 0)[:, None], 0.0, decay)   # a fresh row: whatever the slot held, times zero
        rows = jnp.concatenate([f32(B).reshape(R, steps, gs, N), f32(C).reshape(R, steps, gs, N),
                                jnp.broadcast_to(starts.reshape(R, steps, nh, 1), (R, steps, nh, N))], axis=2)
        o, pool = _mamba2_step_pallas(xt, rows, pool, slot, n_live, hb, interpret)
        return jnp.swapaxes(o[..., :nh], 2, 3).reshape(R, H, P), pool
    live = jnp.arange(R) < n_live[0]
    Bh, Ch = jnp.repeat(f32(B), hb, axis=1), jnp.repeat(f32(C), hb, axis=1)
    S = jnp.where((fresh > 0)[:, None, None, None], 0.0, pool[slot])
    S = decay[..., None, None] * S + xdt[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("rhpn,rhn->rhp", S, Ch, precision=_HI)
    return y, pool.at[jnp.where(live, slot, pool.shape[0])].set(S, mode="drop")


# ---------------------------------------------------------------------------
# any number of tokens a row
# ---------------------------------------------------------------------------

def tile_plan(n_tok, T: int, tile: int = TILE, xp=jnp):
    """The chunk scan's tiles of a ragged batch: the delta rule's plan
    (``kda.tile_plan``: a row of two or more tokens takes ``ceil(n_tok /
    tile)`` tiles that start at its own first token, a row of one token none)
    at this rule's tile. A name of this module's own, which the benchmark's
    ``padding_touches`` control patches."""
    return kda_tile_plan(n_tok, T, xp=xp, tile=tile)


def _tile_math(S0, x, cb, Cm, Bm, c_col, c_row, tail_col, end_row):
    """One tile of ``T`` tokens of one head. ``S0`` ``[P, N]``; ``x`` ``[T,
    P]``, ``dt x`` of the head, zeros at a dead token; ``cb`` ``[T, T]``, the
    group's ``C B^T``; ``Cm, Bm`` ``[T, N]``; the running sum ``c`` of ``dt A``
    as a column ``[T, 1]`` and as a row ``[1, T]``; ``tail_col`` ``c_last - c``
    ``[T, 1]``; ``end_row`` ``c_last`` over ``[1, N]`` (or ``[1, 1]`` off the
    kernel). Returns ``(y [T, P], S_end)``: see the module's docstring."""
    T = x.shape[0]
    lower = lax.broadcasted_iota(jnp.int32, (T, T), 0) >= lax.broadcasted_iota(jnp.int32, (T, T), 1)
    L = jnp.where(lower, jnp.exp(jnp.minimum(c_col - c_row, 0.0)), 0.0)
    from_state = lax.dot_general(Cm, S0, (((1, ), (1, )), ((), ())), precision=_HI, preferred_element_type=jnp.float32)
    y = jnp.dot(cb * L, x, precision=_HI, preferred_element_type=jnp.float32) + jnp.exp(c_col) * from_state
    S = jnp.exp(end_row) * S0 + lax.dot_general(x * jnp.exp(tail_col), Bm, (((0, ), (0, )), ((), ())), precision=_HI,
                                                preferred_element_type=jnp.float32)
    return y, S


def _group_cb(Cm, Bm):
    return lax.dot_general(Cm, Bm, (((1, ), (1, )), ((), ())), precision=_HI, preferred_element_type=jnp.float32)


def _mamba2_chunks_pallas(x, Bm, Cm, cols, rows, ends, pool, tile_slot, tile_first, tile_fresh, n_tiles, hb: int,
                          interpret: bool):
    """``x`` ``[NT, H, T, P]``; ``Bm, Cm`` ``[NT, G, T, N]``; ``cols`` ``[NT, G,
    T, 128]``: lane ``h`` the running sum ``c`` of the group's head ``h``,
    lane ``hb + h`` its ``c_last - c``; ``rows`` ``[NT, G, 8k, T]``: row ``h``
    the same ``c`` along the lanes; ``ends`` ``[NT, G, 8k, N]``: row ``h`` its
    ``c_last`` over every lane."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    NT, H, T, P = x.shape
    G, N = Bm.shape[1], Bm.shape[-1]

    def live_tile(t, n_ref):
        return jnp.maximum(jnp.minimum(t, n_ref[0] - 1), 0)

    def pool_map(j, t, slot_ref, first_ref, fresh_ref, n_ref):
        return slot_ref[live_tile(t, n_ref)], j, 0, 0

    def tile_map(j, t, slot_ref, first_ref, fresh_ref, n_ref):
        return live_tile(t, n_ref), j, 0, 0

    def kernel(slot_ref, first_ref, fresh_ref, n_ref, x_ref, b_ref, c_ref, cols_ref, rows_ref, ends_ref, s_in, o_ref, s_out):
        t = pl.program_id(1)
        n = n_ref[0]

        @pl.when(t < n)
        def _live():
            first = first_ref[t] > 0
            keep = jnp.where(fresh_ref[t] > 0, 0.0, 1.0)
            Bt, Ct = b_ref[0, 0], c_ref[0, 0]
            cb = _group_cb(Ct, Bt)
            for h in range(hb):
                # a row's first tile reads the pool; its later ones what the tile before left in the block
                S0 = jnp.where(first, s_in[0, h] * keep, s_out[0, h])
                y, S = _tile_math(S0, x_ref[0, h], cb, Ct, Bt, cols_ref[0, 0, :, h:h + 1], rows_ref[0, 0, h:h + 1, :],
                                  cols_ref[0, 0, :, hb + h:hb + h + 1], ends_ref[0, 0, h:h + 1, :])
                s_out[0, h] = S
                o_ref[0, h] = y

        @pl.when((n == 0) & (t == 0))
        def _untouched():
            s_out[...] = s_in[...]

    group_spec = lambda a: pl.BlockSpec((1, 1) + a.shape[2:], tile_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(G, NT),
        in_specs=[pl.BlockSpec((1, hb, T, P), tile_map), group_spec(Bm), group_spec(Cm), group_spec(cols),
                  group_spec(rows), group_spec(ends), pl.BlockSpec((1, hb, P, N), pool_map)],
        out_specs=[pl.BlockSpec((1, hb, T, P), tile_map), pl.BlockSpec((1, hb, P, N), pool_map)])
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
    o, pool = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((NT, H, T, P), jnp.float32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={10: 1}, interpret=interpret, name=KERNEL_NAMES[1], **kwargs)(
            tile_slot, tile_first, tile_fresh, n_tiles, x, Bm, Cm, cols, rows, ends, pool)
    return o, pool


def mamba2_chunks(x, B, C, dt, A, pool, slot, fresh, n_tok, use_pallas: bool = False, interpret: bool = False,
                  tile: int = TILE):
    """A ragged batch. ``x`` ``[T, H, P]``, ``B, C`` ``[T, G, N]``, ``dt`` ``[T,
    H]``: the flat tokens, row ``r``'s ``n_tok[r]`` (traced, ``[R]``; 0 for a
    padded row) in a run, rows in order from token 0, whatever is past the
    last row's run ignored; ``A``, ``pool``, ``slot``, ``fresh`` as
    :func:`mamba2_step` takes them. Returns ``(y [T, H, P] float32, pool)``
    with the states of the rows that were fed advanced and no other touched.
    The rows fed exactly ONE token go through :func:`mamba2_step`, the others
    through the chunk scan (see the module's docstring); ``tile``: the tokens
    a tile holds (the tests' to vary)."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    hb, Tt = H // G, int(tile)
    n_tok, slot, fresh = n_tok.astype(jnp.int32), slot.astype(jnp.int32), fresh.astype(jnp.int32)
    f32 = lambda a: a.astype(jnp.float32)
    dt, A = f32(dt), f32(A)
    starts = jnp.cumsum(n_tok) - n_tok
    # the one-token rows, live rows first as the step takes them, each with its token, slot and ``fresh``
    rows1, place1, n_one = step_rows(n_tok)
    tok1 = jnp.minimum(starts[rows1], T - 1)
    y1, pool = mamba2_step(x[tok1], B[tok1], C[tok1], dt[tok1], A, pool, slot[rows1], fresh[rows1], n_one,
                           use_pallas=use_pallas, interpret=interpret)
    # what a tile takes, one run of values a token, so that ONE gather lays a block: dt x, dt A, B, C
    flat = jnp.concatenate([(f32(x) * dt[..., None]).reshape(T, H * P), dt * A, f32(B).reshape(T, G * N),
                            f32(C).reshape(T, G * N)], axis=-1)
    row, tok0, cnt, first, n_tiles = tile_plan(n_tok, T, Tt)
    NB = min(TILE_BLOCK, row.shape[0])
    pad = -row.shape[0] % NB   # whole blocks: a padding tile is one more dead one
    row = jnp.pad(row, (0, pad), mode="edge")
    tok0, cnt, first = (jnp.pad(a, (0, pad)) for a in (tok0, cnt, first))
    of_tile = jnp.stack([slot, fresh], axis=1)[row]
    c = jnp.arange(Tt, dtype=jnp.int32)
    rows8 = -(-hb // 8) * 8

    def block(i, carry):
        """Tiles ``[i NB, (i + 1) NB)``: their operands laid by XLA (zeros where no token is), through the
        kernel, their ``y`` back to the flat order (the gather's own index: a dead place is dropped). A row that
        began in an earlier block goes on from what that block left in the pool."""
        pool, y_flat = carry
        cut = lambda a: lax.dynamic_slice_in_dim(a, i * NB, NB)
        at = jnp.where(c[None, :] < cut(cnt)[:, None], cut(tok0)[:, None] + c[None, :], T).reshape(-1)   # a dead token reads the fill
        tiled = jnp.take(flat, at, axis=0, mode="fill", fill_value=0.0).reshape(NB, Tt, -1)
        xt, la, Bt, Ct = jnp.split(tiled, (H * P, H * P + H, H * P + H + G * N), axis=-1)
        xt = jnp.swapaxes(xt.reshape(NB, Tt, H, P), 1, 2)                            # [NB, H, Tt, P]
        Bt, Ct = (jnp.swapaxes(a.reshape(NB, Tt, G, N), 1, 2) for a in (Bt, Ct))     # [NB, G, Tt, N]
        cum = jnp.cumsum(la, axis=1)                                                 # [NB, Tt, H]: a dead token adds 0
        last = cum[:, -1:, :]
        opens = cut(first)
        from_pool = opens | (jnp.arange(NB) == 0)
        tile_slot, tile_fresh = cut(of_tile[:, 0]), jnp.where(opens, cut(of_tile[:, 1]), 0)
        live = jnp.clip(n_tiles - i * NB, 0, NB)
        if use_pallas or interpret:
            by_group = lambda a: jnp.swapaxes(a.reshape(NB, Tt, G, hb), 1, 2)        # [NB, G, Tt, hb]
            cols = jnp.pad(jnp.concatenate([by_group(cum), by_group(last - cum)], axis=-1),
                           ((0, 0), (0, 0), (0, 0), (0, 128 - 2 * hb)))
            rows = jnp.pad(jnp.swapaxes(by_group(cum), 2, 3), ((0, 0), (0, 0), (0, rows8 - hb), (0, 0)))
            ends = jnp.pad(jnp.broadcast_to(last.reshape(NB, G, hb, 1), (NB, G, hb, N)),
                           ((0, 0), (0, 0), (0, rows8 - hb), (0, 0)))
            y, pool = _mamba2_chunks_pallas(xt, Bt, Ct, cols, rows, ends, pool, tile_slot, from_pool.astype(jnp.int32),
                                            tile_fresh, live.reshape(1), hb, interpret)
        else:
            cum_h = jnp.swapaxes(cum, 1, 2)                                          # [NB, H, Tt]

            def step(carry, xs):
                pool, S = carry
                x_t, B_t, C_t, cum_t, s, reads, is_fresh, is_live = xs
                S0 = jnp.where(reads, jnp.where(is_fresh > 0, 0.0, pool[s]), S)
                cb = jnp.repeat(jax.vmap(_group_cb)(C_t, B_t), hb, axis=0)
                of_head = lambda a: jnp.repeat(a, hb, axis=0)
                y, S = jax.vmap(_tile_math)(S0, x_t, cb, of_head(C_t), of_head(B_t), cum_t[:, :, None], cum_t[:, None, :],
                                            (cum_t[:, -1:] - cum_t)[:, :, None], cum_t[:, -1:, None])
                S = jnp.where(is_live, S, S0)
                return (pool.at[jnp.where(is_live, s, pool.shape[0])].set(S, mode="drop"), S), y

            (pool, _), y = lax.scan(step, (pool, jnp.zeros((H, P, N), jnp.float32)),
                                    (xt, Bt, Ct, cum_h, tile_slot, from_pool, tile_fresh, jnp.arange(NB) < live))
        y = jnp.swapaxes(y, 1, 2).reshape(NB * Tt, H * P)
        return pool, y_flat.at[at].set(y, mode="drop")

    # as many blocks as hold a live tile, and no more: what laying tiles costs follows the chunk rows' tokens
    pool, y = lax.fori_loop(0, -(-n_tiles // NB), block, (pool, jnp.zeros((T, H * P), jnp.float32)))
    # the token of a one-token row takes what the step left at its row's place among such rows
    t = jnp.arange(T, dtype=jnp.int32)
    r = jnp.minimum(jnp.sum(((starts + n_tok)[None, :] <= t[:, None]).astype(jnp.int32), axis=1), n_tok.shape[0] - 1)
    of_tok = jnp.stack([place1, n_tok], axis=1)[r]   # ONE gather a token
    return jnp.where((of_tok[:, 1] == 1)[:, None, None], y1[of_tok[:, 0]], y.reshape(T, H, P)), pool
