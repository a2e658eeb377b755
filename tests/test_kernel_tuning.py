"""Raw-speed pass tests: both paged-attention kernels' parity matrices (vs
the gather oracle), the one selector that picks between them from the
shapes, explicit ZeRO-3 overlap bit-identical loss."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import alibi_slopes
from deepspeed_tpu.ops.pallas import paged_attention as pa_mod
from deepspeed_tpu.ops.pallas.paged_attention import (_block_heads, _contiguity_ok, _decode_work_list,
                                                      _head_load_path, _pallas_paged, _tiled_work_list,
                                                      choose_kernel, decode_kv_counts, paged_attention_reference,
                                                      tiled_kv_counts)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


# ---------------------------------------------------------------------------
# q-tiled paged attention: interpret-mode parity matrix vs the gather oracle
# ---------------------------------------------------------------------------

def _int8_pool(xf):
    """``xf`` [pool, nkv, d] as an int8 pool and its [nkv, pool] absmax/127 scales."""
    sc = (np.abs(xf).max(axis=2) / 127.0).T
    return jnp.asarray(np.round(xf / sc.T[:, :, None]).clip(-127, 127), jnp.int8), jnp.asarray(sc, jnp.float32)


def _paged_setup(seed=0, nkv=2, g=2, d=32, bs=16, n_seqs=3, blocks_per_seq=4, int8=False):
    rng = np.random.default_rng(seed)
    nq = nkv * g
    pool = bs * blocks_per_seq * n_seqs
    kf = rng.normal(size=(pool, nkv, d))
    vf = rng.normal(size=(pool, nkv, d))
    tables = jnp.arange(n_seqs * blocks_per_seq, dtype=jnp.int32).reshape(n_seqs, blocks_per_seq)
    if int8:
        (kp, ks), (vp, vs) = _int8_pool(kf), _int8_pool(vf)
        scales = dict(k_scale=ks, v_scale=vs)
    else:
        kp, vp = jnp.asarray(kf, jnp.float32), jnp.asarray(vf, jnp.float32)
        scales = {}
    return rng, nq, kp, vp, tables, scales


def _mixed_batch(rng, nq, d, bs):
    """SplitFuse-shaped batch: a 13-token prefill chunk for seq 0 (ragged
    tail at every q_tile), a 6-token chunk mid-context for seq 1, one decode
    token for seq 2 — same-sequence tokens contiguous, the ragged layout
    invariant (ragged_wrapper.finalize)."""
    seq_idx = np.asarray([0] * 13 + [1] * 6 + [2], np.int32)
    pos = np.asarray(list(range(20, 33)) + list(range(bs, bs + 6)) + [3 * bs + 5], np.int32)
    T = seq_idx.size
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.float32)
    return q, jnp.asarray(seq_idx), jnp.asarray(pos)


def _rows_of_4_batch(rng, nq, d, bs, n_rows, T, dtype=jnp.float32):
    """The block-diffusion cell's forward in small: ``n_rows`` rows of one
    4-token block each, at contexts of 1-5 KV blocks of 16 under a table
    several times wider, every token masked by its block's LAST position
    (``pos | 3``, as ``ragged_forward`` hands it to the kernels), then the pad
    run up to ``T`` tokens. Most of the static tile bound stays empty."""
    seq_idx = np.repeat(np.arange(n_rows), 4)
    pos = np.concatenate([np.arange(4) + 4 * (2 + 3 * (r % 6)) for r in range(n_rows)]) | 3
    assert pos.max() // bs <= 4 and seq_idx.size < T
    seq_idx, pos = (np.pad(a, (0, T - a.size)).astype(np.int32) for a in (seq_idx, pos))
    q = jnp.asarray(rng.normal(size=(T, nq, d)), dtype)
    return q, jnp.asarray(seq_idx), jnp.asarray(pos)


def _unwritten_tiles_reach_no_token(tables, seq_idx, pos, bs, window, q_tile):
    """An empty tile has no item, so the kernel never writes its output tile;
    the scatter back reads ``tile_id * q_tile + slot`` of every token, the pad
    run's included: those are exactly the tiles the grid wrote. Returns how
    many tiles stayed unwritten."""
    tile_id, slot, tile_tok, valid, _, tile_cnt, w_tile, _, total = (
        np.asarray(a) for a in _tiled_work_list(tables, seq_idx, pos, bs, window, q_tile))
    written = set(w_tile[:int(total)].tolist())
    assert written == set(np.flatnonzero(tile_cnt).tolist()) == set(tile_id.tolist())
    assert valid[tile_id, slot].all() and (tile_tok[tile_id, slot] == np.arange(tile_id.size)).all()
    return tile_cnt.size - len(written)


@pytest.mark.parametrize("q_tile", [4, 8])
@pytest.mark.parametrize("case", ["plain", "int8", "alibi", "window", "window_alibi",
                                  "int8_window", "gqa", "rows_of_4", "rows_of_4_window",
                                  "bf16_nkv4", "bf16_nkv8_window", "int8_nkv4"])
def test_qtiled_parity_matrix(case, q_tile):
    """The q-tiled grid must match the gather oracle bit-for-tolerance on
    every kernel feature — ragged tile tails, int8 dequant-at-tile-read,
    alibi, sliding window, GQA — on a mixed prefill+decode batch, and so
    must the decode kernel, which serves the same batch a token a row.
    ``rows_of_4``: the block-diffusion cell's shape in small, six rows of 4
    tokens under the block bound and a 16-column table of which no row fills
    more than 5, where most tiles of the static bound hold nothing and are
    never written. The float32 and ``nkv=2`` int8 pools of the other cases
    reach the tiled kernel's heads by row loads and by the value-level cut;
    ``*_nkv4`` / ``*_nkv8`` (heads of 128, a bf16 or int8 pool under float32
    queries, so the tight tolerance holds) by the strided word loads every
    serving configuration takes."""
    import zlib

    nkv = int(case.split("nkv")[1][0]) if "nkv" in case else 2
    g = 4 if case == "gqa" else 2
    int8 = case.startswith("int8")
    rows4 = case.startswith("rows_of_4")
    d, bs = (128, 16) if "nkv" in case else (32, 16)
    # crc32, not hash(): PYTHONHASHSEED salting would make a tolerance-edge
    # failure unreproducible across runs
    rng, nq, kp, vp, tables, scales = _paged_setup(seed=zlib.crc32(case.encode()), nkv=nkv, g=g, d=d, int8=int8,
                                                   **(dict(n_seqs=6, blocks_per_seq=16) if rows4 else {}))
    if case.startswith("bf16"):
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    assert _head_load_path(kp.dtype, nkv) == ("words" if "nkv" in case else "cut" if int8 else "rows")
    q, seq_idx, pos = _rows_of_4_batch(rng, nq, d, bs, 6, 32) if rows4 else _mixed_batch(rng, nq, d, bs)
    if rows4:  # 8 or 6 run tiles and the pad run's of a bound of 32 / q_tile + 7
        assert _unwritten_tiles_reach_no_token(tables, seq_idx, pos, bs, 17 if "window" in case else None,
                                               q_tile) == {4: 7, 8: 4}[q_tile]
    kw = dict(scales)
    if "alibi" in case:
        kw["alibi"] = tuple(alibi_slopes(nq).tolist())
    if "window" in case:
        kw["window"] = 17
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs, **kw)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                        q_tile=q_tile, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)
    out1 = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                         q_tile=1, **kw)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref), rtol=2e-4, atol=2e-5)


def _prefill_batch(rng, nq, d, bs, q_tile, dtype):
    """A long-prompt SplitFuse step at the tile sizes the heuristic now picks:
    a chunk longer than two tiles mid-context with a ragged tail (seq 0), two
    one-token decode rows riding beside it (seqs 1, 2: the short pass), a
    5-token chunk from position 0 (seq 3), and the trailing pad run
    ragged_wrapper.finalize emits (seq 0, pos 0); with S = 4 the static tile
    bound leaves empty tiles behind it."""
    n_chunk = 2 * q_tile + 13
    seq_idx = np.asarray([0] * n_chunk + [1, 2] + [3] * 5 + [0] * 4, np.int32)
    pos = np.asarray(list(range(40, 40 + n_chunk)) + [20 * bs + 3, 9 * bs] + list(range(5)) + [0] * 4,
                     np.int32)
    q = jnp.asarray(rng.normal(size=(seq_idx.size, nq, d)), dtype)
    return q, jnp.asarray(seq_idx), jnp.asarray(pos)


@pytest.mark.parametrize("q_tile", [32, 128])
@pytest.mark.parametrize("case", ["plain", "window", "alibi", "window_alibi", "int8", "int8_window",
                                  "gqa4", "f32", "f32_window_alibi", "rows_of_4", "rows_of_4_window",
                                  "nkv4", "nkv8_window", "int8_nkv4", "rows_of_4_nkv4"])
def test_qtiled_prefill_tile_parity_matrix(case, q_tile):
    """The tiles the shape heuristic picks for prefill (32 for many rows, 128
    for long prompts) against the gather oracle: bf16 q and pool, so both
    dots run on bf16 operands with float32 accumulation, at the tolerances
    the chip tests hold bf16 and int8 to; float32 inputs at the interpret
    matrix's. The window (50) is shorter than a tile, so its lower edge
    crosses KV blocks inside one tile. ``rows_of_4``: the block-diffusion
    cell's forward (:func:`_rows_of_4_batch`, 12 rows under a 24-column
    table), every tile a short one. ``*nkv4`` / ``nkv8*``: 4 and 8 kv heads
    of 128, the serving configurations' head counts, so that the strided word
    loads deliver whole heads two (bf16) or four (int8) to a word; the
    ``nkv=2`` bf16 cases take word loads too, at a stride of one word."""
    import zlib

    f32 = case.startswith("f32")
    rows4 = case.startswith("rows_of_4")
    dtype = jnp.float32 if f32 else jnp.bfloat16
    nkv = int(case.split("nkv")[1][0]) if "nkv" in case else 2
    g = 4 if case == "gqa4" else 2
    d, bs, blocks_per_seq = 128 if "nkv" in case else 32, 16, 24
    rng, nq, kp, vp, tables, scales = _paged_setup(seed=zlib.crc32(case.encode()), nkv=nkv, g=g, d=d, bs=bs,
                                                   n_seqs=12 if rows4 else 4, blocks_per_seq=blocks_per_seq,
                                                   int8=case.startswith("int8"))
    if not scales:
        kp, vp = kp.astype(dtype), vp.astype(dtype)
    assert _head_load_path(kp.dtype, nkv) == ("rows" if f32 else "cut" if scales and nkv == 2 else "words")
    if rows4:
        q, seq_idx, pos = _rows_of_4_batch(rng, nq, d, bs, 12, 64, dtype)
        # 12 run tiles and the pad run's, of ceil(64 / q_tile) + 13
        assert _unwritten_tiles_reach_no_token(tables, seq_idx, pos, bs, 50 if "window" in case else None,
                                               q_tile) == -(-64 // q_tile)
    else:
        q, seq_idx, pos = _prefill_batch(rng, nq, d, bs, q_tile, dtype)
    assert int(pos.max()) < blocks_per_seq * bs
    kw = dict(scales)
    if "alibi" in case:
        kw["alibi"] = tuple(alibi_slopes(nq).tolist())
    if "window" in case:
        kw["window"] = 50
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs, **kw)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                        q_tile=q_tile, **kw)
    assert out.dtype == q.dtype and out.shape == q.shape
    tol = dict(rtol=2e-4, atol=2e-5) if f32 else dict(rtol=2e-2, atol=2e-2) if scales else \
        dict(rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol)
    if not f32:
        # and tighter than any one element: the whole output within bf16 rounding
        err = np.linalg.norm(np.asarray(out, np.float32) - np.asarray(ref, np.float32))
        assert err / np.linalg.norm(np.asarray(ref, np.float32)) < 6e-3


@pytest.mark.parametrize("dtype,nkv,path", [
    # every serving configuration: a bf16 pool of 4 or 8 kv heads (an int8 one of 4 or 8) packs whole heads into a word
    ("bfloat16", 2, "words"), ("bfloat16", 4, "words"), ("bfloat16", 8, "words"),
    ("int8", 4, "words"), ("int8", 8, "words"),
    ("float32", 2, "rows"), ("float32", 3, "rows"),
    # the static fall-backs: one head is the block; a word would mix two tokens; a float16 is no half of a float32
    ("bfloat16", 1, "whole"), ("bfloat16", 3, "cut"), ("int8", 2, "cut"), ("float16", 4, "cut"),
])
def test_block_heads_are_the_pools_own_bits(dtype, nkv, path):
    """What the tiled kernel's loader delivers for head ``n`` of a KV block
    ``[block x nkv, d]`` is ``pool_block[:, n, :]`` BIT FOR BIT, whichever
    path the pool's static dtype and head count choose (each case names its
    own): the unpacked halves of a 32-bit word are the pool's values, so the
    dots' operands are what the value-level cut gave them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bs, d, n_blocks = 16, 128, 3
    dt = jnp.dtype(dtype)
    assert _head_load_path(dt, nkv) == path
    rng = np.random.default_rng(nkv)
    if dt == jnp.int8:
        pool = jnp.asarray(rng.integers(-128, 128, size=(n_blocks, bs, nkv, d)), jnp.int8)
    else:  # normal draws over seven decades, a signed zero, the largest and the smallest normal magnitudes
        x = rng.normal(size=(n_blocks, bs, nkv, d)) * np.exp(rng.uniform(-8, 8, size=(n_blocks, bs, nkv, 1)))
        x[0, 0, :, :4] = [0.0, -0.0, float(jnp.finfo(dt).max), float(jnp.finfo(dt).tiny)]
        pool = jnp.asarray(x, dt)

    def kernel(k_ref, o_ref):
        for n, head in enumerate(_block_heads(pl, pltpu, k_ref, nkv, bs)):
            o_ref[0, n] = head.astype(dt)   # int8 comes widened to int32, value for value

    out = pl.pallas_call(kernel, grid=(n_blocks, ), interpret=True,
                         in_specs=[pl.BlockSpec((1, bs * nkv, d), lambda i: (i, 0, 0))],
                         out_specs=pl.BlockSpec((1, nkv, bs, d), lambda i: (i, 0, 0, 0)),
                         out_shape=jax.ShapeDtypeStruct((n_blocks, nkv, bs, d), dt))(
                             pool.reshape(n_blocks, bs * nkv, d))
    bits = lambda a: np.asarray(a).view({1: np.uint8, 2: np.uint16, 4: np.uint32}[dt.itemsize])
    for n in range(nkv):
        np.testing.assert_array_equal(bits(out[:, n]), bits(pool[:, :, n, :]))


def test_qtiled_decode_only_with_pad_run():
    """Pure-decode shape: one token per sequence plus the trailing pad run
    (seq 0, pos 0 — exactly what ragged_wrapper.finalize emits). Every tile
    holds a single valid token; the tiled grid and the decode kernel must
    agree with the oracle."""
    rng, nq, kp, vp, tables, _ = _paged_setup(seed=7, n_seqs=4)
    d, bs = 32, 16
    n_seqs = 4
    T = 8  # 4 decode tokens + 4 pad tokens
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.float32)
    seq_idx = jnp.asarray([0, 1, 2, 3, 0, 0, 0, 0], jnp.int32)
    pos = jnp.asarray([30, 17, 45, 9, 0, 0, 0, 0], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs)
    for qt in (1, 4):
        out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                            q_tile=qt)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5,
                                   err_msg=f"q_tile={qt}")


# ---------------------------------------------------------------------------
# the decode kernel: interpret-mode parity matrix vs the gather oracle
# ---------------------------------------------------------------------------

def _decode_batch(rng, nq, d, bs, blocks_per_seq):
    """Decode-shaped batch: one token per sequence at varied live depths —
    seq 0 fully live, the rest early in their tables — plus the trailing pad
    run ragged_wrapper.finalize emits."""
    seq_idx = np.asarray([0, 1, 2, 0, 0], np.int32)
    pos = np.asarray([blocks_per_seq * bs - 1, bs + 3, 2 * bs + 7, 0, 0], np.int32)
    q = jnp.asarray(rng.normal(size=(seq_idx.size, nq, d)), jnp.float32)
    return q, jnp.asarray(seq_idx), jnp.asarray(pos)


def _verify_batch(rng, nq, d, bs, blocks_per_seq):
    """``T = 5 S``: a linear speculative verify, five tokens a row at
    consecutive positions (row 0's run ends on the table's last slot and
    crosses a block edge), then the pad run."""
    starts = [blocks_per_seq * bs - 5, bs - 2, 3 * bs + 1]
    seq_idx = np.asarray(np.repeat(np.arange(3), 5).tolist() + [0], np.int32)
    pos = np.asarray([p + i for p in starts for i in range(5)] + [0], np.int32)
    q = jnp.asarray(rng.normal(size=(seq_idx.size, nq, d)), jnp.float32)
    return q, jnp.asarray(seq_idx), jnp.asarray(pos)


@pytest.mark.parametrize("shape", ["short_table", "multi_token"])
@pytest.mark.parametrize("case", ["plain", "int8", "alibi", "window", "window_alibi",
                                  "int8_window", "gqa"])
def test_kv_split_parity_matrix(case, shape):
    """The decode kernel against the gather oracle on every kernel feature —
    int8 dequant, alibi, sliding window, GQA, partially-live contexts, pad
    rows — on the two classes of input it took over from the deleted
    per-token grid: a table under 8 columns (``short_table``: 4) and a batch
    with several tokens a row (``multi_token``: T = 5 S)."""
    import zlib

    nkv, g = (2, 4) if case == "gqa" else (2, 2)
    int8 = case.startswith("int8")
    blocks_per_seq = 4 if shape == "short_table" else 8
    rng, nq, kp, vp, tables, scales = _paged_setup(seed=zlib.crc32(case.encode()), nkv=nkv,
                                                   g=g, int8=int8, blocks_per_seq=blocks_per_seq)
    d, bs = 32, 16
    batch = _decode_batch if shape == "short_table" else _verify_batch
    q, seq_idx, pos = batch(rng, nq, d, bs, blocks_per_seq)
    kw = dict(scales)
    if "alibi" in case:
        kw["alibi"] = tuple(alibi_slopes(nq).tolist())
    if "window" in case:
        kw["window"] = 21
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs, **kw)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


# the decode kernel at what it keys on in the serving cells, scaled down in
# d and block only: (case, nkv, g, window, int8, alibi, contexts in tokens of
# each row). Blocks are 16 tokens, the table 65 columns wide, as the cells'.
_DECODE_CASES = {
    # mistral-7b's heads: rows of 1 to 16 live blocks in a 65-column table
    "g4_nkv8_rows_of_1_to_16_blocks": (8, 4, None, False, False, [16 * b - (b % 3) for b in range(1, 17)]),
    # mellum's heads and window layers: rows under the window beside rows past it
    "g8_nkv4_rows_under_and_past_the_window": (4, 8, 128, False, False,
                                               [5, 40, 127, 128, 129, 200, 255, 256, 257, 505]),
    "g8_nkv4_full_layer": (4, 8, None, False, False, [5, 40, 127, 128, 129, 200, 255, 256, 257, 505]),
    # longprompt's decode rows: one long row among short ones
    "one_long_row_and_many_short": (8, 4, 512, False, False, [1030, 3, 17, 33, 2, 16, 31, 9]),
    "int8_g4": (8, 4, None, True, False, [16 * b + 5 for b in range(1, 9)]),
    "int8_window_g8": (4, 8, 128, True, False, [5, 130, 255, 256, 300]),
    "alibi_g4": (8, 4, None, False, True, [1, 16, 17, 100, 250]),
    "alibi_window_g8": (4, 8, 128, False, True, [1, 16, 17, 129, 300]),
}


def _decode_case(name, pad_rows=3):
    """Pools, a 65-column table whose live columns hold distinct random
    blocks, one decode token a row and ``pad_rows`` pad rows (sequence 0 at
    position 0, as ``ragged_wrapper.finalize`` emits them)."""
    import zlib

    nkv, g, window, int8, alibi, ctx = _DECODE_CASES[name]
    d, bs, mb = 32, 16, 65
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    S = len(ctx)
    need = [-(-c // bs) for c in ctx]
    n_blocks = sum(need) + 3
    tables = np.zeros((S, mb), np.int32)
    free = rng.permutation(n_blocks)
    for r, n in enumerate(need):
        tables[r, :n], free = free[:n], free[n:]
    kf = rng.normal(size=(n_blocks * bs, nkv, d))
    vf = rng.normal(size=(n_blocks * bs, nkv, d))
    kw = {}
    if int8:
        (kp, ks), (vp, vs) = _int8_pool(kf), _int8_pool(vf)
        kw = dict(k_scale=ks, v_scale=vs)
    else:
        kp, vp = jnp.asarray(kf, jnp.float32), jnp.asarray(vf, jnp.float32)
    if alibi:
        kw["alibi"] = tuple(alibi_slopes(nkv * g).tolist())
    if window is not None:
        kw["window"] = window
    seq_idx = jnp.asarray(list(range(S)) + [0] * pad_rows, jnp.int32)
    pos = jnp.asarray([c - 1 for c in ctx] + [0] * pad_rows, jnp.int32)
    q = jnp.asarray(rng.normal(size=(S + pad_rows, nkv * g, d)), jnp.float32)
    return q, kp, vp, jnp.asarray(tables), seq_idx, pos, bs, kw


@pytest.fixture
def blocks_per_step(request, monkeypatch):
    """The decode kernel with ``request.param`` KV blocks a grid step, whatever
    the rule would give the cases' small blocks (the jitted kernel is traced
    anew)."""
    monkeypatch.setattr(pa_mod, "_decode_blocks_per_step", lambda rows, d, itemsize, parts=2: request.param)
    _pallas_paged.clear_cache()
    yield request.param
    _pallas_paged.clear_cache()


@pytest.mark.parametrize("blocks_per_step", [1, 2, 4], indirect=True)
@pytest.mark.parametrize("case", sorted(_DECODE_CASES))
def test_decode_kernel_parity_at_what_the_cells_key_on(case, blocks_per_step):
    """``paged_attn_kv_split`` against the gather oracle at the head shapes of
    both decode-heavy cells (g 4 x 8 kv heads, g 8 x 4), a 65-column table
    with rows of very different length, rows on both sides of a window, pad
    rows at position 0, int8 KV and alibi, at one, two and four blocks a grid
    step (odd tails and rows shorter than a step included)."""
    q, kp, vp, tables, seq_idx, pos, bs, kw = _decode_case(case)
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs, **kw)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("per_step", [1, 2, 4])
@pytest.mark.parametrize("window", [None, 40, 128])
def test_decode_work_list_holds_exactly_the_live_pairs(window, per_step):
    """The grid of the decode kernel IS the work list: for a ragged batch
    with a window its items, spread over their slots, are exactly the (row,
    column) pairs with a visible key, each once, with the table's block;
    a slot past a row's end repeats the block it last held (no fetch); the
    host's ``decode_kv_counts`` counts the same pairs and the same slots;
    and the arrays are as long as the shapes' bound."""
    bs, mb = 16, 65
    rng = np.random.default_rng(3)
    pos = np.asarray([0, 15, 16, 39, 40, 41, 127, 128, 200, 1039, 0, 0], np.int32)
    T = pos.size
    seq_idx = np.asarray(list(range(T - 2)) + [0, 0], np.int32)   # two pad rows
    tables = rng.integers(0, 500, size=(T - 2, mb)).astype(np.int32)
    w_row, w_col, w_blk, total = (np.asarray(a) for a in _decode_work_list(
        jnp.asarray(tables), jnp.asarray(seq_idx), jnp.asarray(pos), bs, window, per_step))
    cols = mb if window is None else min(mb, (window + bs - 2) // bs + 1)
    bound = T * -(-cols // per_step)
    assert w_col.shape == (bound, ) and w_blk.shape == (per_step * bound, ) and w_row.shape == (bound + 1, )
    want = {(t, c) for t in range(T) for c in range(mb)
            if c * bs <= pos[t] and (window is None or (c + 1) * bs - 1 > pos[t] - window)}
    got = []
    for i in range(int(total)):
        for b in range(per_step):
            t, c = int(w_row[i]), int(w_col[i]) + b
            if c <= pos[t] // bs:
                got.append((t, c))
                assert w_blk[b * bound + i] == tables[seq_idx[t], c]
            elif i:  # item 0 has no item before it: its dead slots cost a fetch
                assert w_blk[b * bound + i] == w_blk[b * bound + i - 1]
    assert len(got) == len(set(got)) and set(got) == want
    assert list(w_row[:int(total)]) == sorted(w_row[:int(total)]) and (w_row[int(total):] == T).all()
    choice = {"kernel": "paged_attn_kv_split", "blocks_per_step": per_step}
    real = T - 2
    steps, live = decode_kv_counts(choice, pos[:real], [(window, 1)], bs, mb, T)
    assert live == len({p for p in want if p[0] < real}) and steps == int(total) * per_step
    # the gather walks the whole table: every column of every bucket row
    assert decode_kv_counts({"kernel": "paged_attention_reference", "blocks_per_step": 1}, pos[:real],
                            [(window, 3)], bs, mb, T) == (3 * T * mb, 3 * live)


def _tiled_batch(bs, bound4):
    """A chunk deep in row 0's context, five decode rows, a short chunk of a
    second prompt from position 0, and the pad run: 8 table rows, 96 tokens.
    ``bound4``: under a block-diffusion model's bound, chunks on 4-token
    boundaries and every token masked by its block's last position."""
    runs = [(0, 200, 44), (1, 7 * bs, 1), (2, 1039, 1), (3, 15, 1), (4, 16, 1), (5, 12 * bs - 1, 1), (6, 0, 20)]
    if bound4:
        runs = [(r, p // 4 * 4, -(-n // 4) * 4) for r, p, n in runs]
    seq_idx = np.concatenate([np.full(n, r) for r, _, n in runs])
    pos = np.concatenate([np.arange(p, p + n) for _, p, n in runs])
    pad = 96 - seq_idx.size
    assert pad > 0
    seq_idx, pos = (np.pad(a, (0, pad)).astype(np.int32) for a in (seq_idx, pos))
    return seq_idx, (pos | 3 if bound4 else pos)


@pytest.mark.parametrize("bound4", [False, True], ids=["causal", "block_bound"])
@pytest.mark.parametrize("window", [None, 40, 300])
@pytest.mark.parametrize("q_tile", [8, 32, 128])
def test_tiled_work_list_holds_exactly_the_live_pairs(q_tile, window, bound4):
    """The grid of the tiled kernel IS the work list: against a plain
    enumeration of the tiles (a run cut every ``q_tile`` tokens) and of the
    table columns in which some token of a tile has a key in sight, the items
    are exactly those (tile, column) pairs, each once, tile after tile and
    ascending by column, under the tile's table row; an empty tile has no item,
    no item lies past a tile's last position or under its window; the arrays
    are as long as the shapes' bound and one more; and the host's
    ``tiled_kv_counts`` counts the same items and the same rectangle."""
    bs, mb, S = 16, 65, 8
    seq_idx, pos = _tiled_batch(bs, bound4)
    T = pos.size
    tables = np.random.default_rng(4).integers(0, 500, size=(S, mb)).astype(np.int32)
    tile_id, slot, tile_tok, valid, tile_seq, tile_cnt, w_tile, w_col, total = (
        np.asarray(a) for a in _tiled_work_list(jnp.asarray(tables), jnp.asarray(seq_idx), jnp.asarray(pos), bs,
                                                window, q_tile))
    total = int(total)
    # the plain enumeration: runs, then tiles, then each tile's columns
    tiles, run = [], []
    for t in range(T):
        if t and (seq_idx[t] != seq_idx[t - 1] or pos[t] < pos[t - 1]):
            tiles += [run[i:i + q_tile] for i in range(0, len(run), q_tile)]
            run = []
        run.append(t)
    tiles += [run[i:i + q_tile] for i in range(0, len(run), q_tile)]
    want = [(i, c) for i, toks in enumerate(tiles) for c in range(mb)
            if any(c * bs <= pos[t] and (window is None or (c + 1) * bs - 1 > pos[t] - window) for t in toks)]
    n_tiles = -(-T // q_tile) + S + 1
    cols = mb if window is None else min(mb, (window + q_tile - 2) // bs + 2)
    assert len(tiles) < n_tiles and tile_cnt.shape == (n_tiles, )
    assert tile_cnt.tolist() == [len(toks) for toks in tiles] + [0] * (n_tiles - len(tiles))
    assert all(tile_tok[i, :len(toks)].tolist() == toks and valid[i].sum() == len(toks) for i, toks in enumerate(tiles))
    assert w_tile.shape == w_col.shape == (n_tiles * cols + 1, ) and (w_col < mb).all()
    assert list(zip(w_tile[:total].tolist(), w_col[:total].tolist())) == want and total == len(want)
    assert (w_tile[total:] == n_tiles).all()
    assert tile_seq.tolist() == [seq_idx[toks[0]] for toks in tiles] + [0] * (n_tiles - len(tiles))
    for i, toks in enumerate(tiles):
        mine = [c for j, c in want if j == i]
        assert mine and mine[-1] == pos[toks].max() // bs and len(mine) <= cols
        assert window is None or mine[0] == max(pos[toks].min() - window + 1, 0) // bs
    assert tiled_kv_counts(q_tile, seq_idx, pos, [(window, 1)], bs, mb, S) == (n_tiles * cols, total, total)
    both = 3 * total + 2 * tiled_kv_counts(q_tile, seq_idx, pos, [(None, 1)], bs, mb, S)[1]
    assert tiled_kv_counts(q_tile, seq_idx, pos, [(window, 3), (None, 2)], bs, mb, S) == (
        3 * n_tiles * cols + 2 * n_tiles * mb, both, both)


def test_a_work_list_past_the_scalar_memory_raises_at_trace_time():
    """The list's two arrays live in scalar memory beside the block table: a shape whose bound
    would not fit is refused while the program is traced, by name, and there
    is no other grid to fall back to."""
    sd = jax.ShapeDtypeStruct
    args = lambda mb: (sd((256, 4, 32), jnp.float32), sd((64, 2, 32), jnp.float32), sd((64, 2, 32), jnp.float32),
                       sd((64, mb), jnp.int32), sd((256, ), jnp.int32), sd((256, ), jnp.int32))
    fn = lambda *a, **kw: _pallas_paged(*a, block_size=16, interpret=True, q_tile=8, **kw)
    assert pa_mod._tiled_smem_bytes(97, 65, 64, 65) == 83992          # the claimed cell's forward
    assert jax.eval_shape(fn, *args(65)).shape == (256, 4, 32)
    with pytest.raises(ValueError, match="97 tiles x 700 table columns takes 740592 bytes of scalar memory"):
        jax.eval_shape(functools.partial(fn, blocks_per_step=1), *args(700))
    # four 16-token blocks an item: a slot's column each and a quarter of the items, so five eighths of the list
    with pytest.raises(ValueError, match="97 tiles x 700 table columns takes 536892 bytes of scalar memory"):
        jax.eval_shape(fn, *args(700))


def test_decode_kernel_full_table_and_single_block_rows():
    """A row whose context fills its whole table beside a row living inside
    its first block: each is one softmax chain of its own live blocks."""
    rng, nq, kp, vp, tables, _ = _paged_setup(seed=5, n_seqs=2, blocks_per_seq=6)
    d, bs = 32, 16
    q = jnp.asarray(rng.normal(size=(2, nq, d)), jnp.float32)
    seq_idx = jnp.asarray([0, 1], jnp.int32)
    pos = jnp.asarray([6 * bs - 1, 2], jnp.int32)  # full table; single-block
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the selector: one function of the static shapes
# ---------------------------------------------------------------------------

@pytest.fixture
def on_tpu(monkeypatch):
    """``choose_kernel`` asks the backend once; answer for the chip, so that
    the rules the cells run are the ones under test (nothing is lowered)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# heads and blocks of the two serving configurations: (nq, nkv, d, block, itemsize)
_HEADS = {"mistral-7b": (32, 8, 128, 128, 2), "mellum2-12b-a2.5b": (32, 4, 128, 128, 2)}
# ... and of the others that run the selector (PR 40): their grouped heads read the table of the two above
# (Trinity's 8 kv heads Mistral's blocks a step, SDAR's 4 Mellum's), and GLM's ABSORBED call is a group of 20
# over the one latent entry a token (``parts`` 1), which is not one query head a kv head either
_MORE_HEADS = {"trinity-large-preview": (48, 8, 128, 128, 2, "mistral-7b"),
               "sdar-30b-a3b-chat": (32, 4, 128, 128, 2, "mellum2-12b-a2.5b")}


def _choose(T, S, max_blocks, config="mistral-7b", **kw):
    """As ``paged_attention`` calls the selector over a token-major pool."""
    nq, nkv, d, bs, itemsize = (_HEADS.get(config) or _MORE_HEADS[config])[:5]
    return choose_kernel(T, S, max_blocks, nq, bs * nkv, d, itemsize, **kw)


def test_decode_kernel_choice_contract(on_tpu):
    """Decode-shaped batches take the decode kernel whatever the table's
    width, at the blocks a step its block's bytes give; a tiled prefill never
    does; off the TPU and at heads the kernels do not tile, the gather."""
    assert _choose(4, 4, 64) == {"kernel": "paged_attn_kv_split", "q_tile": 1, "blocks_per_step": 2,
                                 "rule": "heuristic:long_table"}
    assert _choose(4, 4, 4)["rule"] == "heuristic:short_table"
    assert _choose(4, 4, 4)["kernel"] == "paged_attn_kv_split"
    assert _choose(40, 8, 17)["rule"] == "heuristic:multi_token"     # a linear verify of 8 rows x 5
    assert _choose(256, 4, 64)["kernel"] == "paged_attn_q_tiled"
    # 1 MiB a grid step: 8 kv heads of 128 in bf16 are 512 KiB a block, 4 heads or int8 256 KiB
    assert _choose(8, 8, 65, "mellum2-12b-a2.5b")["blocks_per_step"] == 4
    assert choose_kernel(8, 8, 65, 32, 128 * 8, 128, 1)["blocks_per_step"] == 4
    assert choose_kernel(8, 8, 65, 4, 128 * 4, 128, 2) == {
        "kernel": "paged_attention_reference", "q_tile": 1, "blocks_per_step": 1, "rule": "unsupported_shape"}
    assert choose_kernel(8, 8, 65, 32, 128 * 8, 64, 2)["rule"] == "unsupported_shape"


def test_off_the_tpu_the_choice_is_the_gather():
    assert _choose(2048, 8, 65) == {"kernel": "paged_attention_reference", "q_tile": 1,
                                    "blocks_per_step": 1, "rule": "off_tpu"}


def test_noncontiguous_batch_is_demoted_to_the_decode_kernel(on_tpu):
    """A concrete batch that breaks the tiled grid's layout contract is not
    tiled (the grid would overflow its static tile bound and scatter tokens
    into the wrong tiles): the decode kernel takes it, a token a row, and
    its output is the oracle's."""
    rng, nq, kp, vp, tables, _ = _paged_setup(seed=11, n_seqs=2)
    d, bs = 32, 16
    T = 64
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.float32)
    seq_idx = jnp.asarray(np.arange(T) % 2, jnp.int32)  # interleaved: runs = T
    pos = jnp.asarray(rng.integers(0, 2 * bs, size=T), jnp.int32)
    assert not _contiguity_ok(seq_idx, 2)
    assert _choose(T, 2, 65)["kernel"] == "paged_attn_q_tiled"     # traced callers: the layout invariant
    demoted = _choose(T, 2, 65, seq_idx=seq_idx, pos=pos)
    assert (demoted["kernel"], demoted["q_tile"], demoted["rule"]) == ("paged_attn_kv_split", 1,
                                                                       "contiguity_demoted")
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                        q_tile=demoted["q_tile"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_q_tile_choice_contract(on_tpu):
    """Prefill-ish shapes tile, decode-ish ones do not; a CONCRETE seq_idx
    violating the same-sequence-contiguity contract demotes the tile, one
    that holds it keeps it."""
    assert _choose(256, 4, 65)["q_tile"] == 128
    assert _choose(8, 8, 65)["q_tile"] == 1
    interleaved = jnp.asarray(np.arange(64) % 2, jnp.int32)
    assert _choose(64, 2, 65, seq_idx=interleaved)["rule"] == "contiguity_demoted"
    contiguous = jnp.asarray(np.repeat([0, 1], 32), jnp.int32)
    assert _choose(64, 2, 65, seq_idx=contiguous) == {
        "kernel": "paged_attn_q_tiled", "q_tile": 64, "blocks_per_step": 1, "rule": "heuristic:short_rows"}
    # a pure-decode shape never tiles, however many tokens: every tile would be 7/8 masked
    assert _choose(256, 256, 65)["kernel"] == "paged_attn_kv_split"


@pytest.mark.parametrize("T,S,want", [
    # mistral-7b.longprompt's prefill bucket (table 65 wide): hundreds of tokens a row
    (2048, 8, (128, "heuristic:long_rows")),
    (1024, 8, (128, "heuristic:long_rows")),
    (2048, 1, (128, "heuristic:long_rows")),
    # mistral-7b.chat's SplitFuse put: 512 tokens over 32 rows, most of them decode rows
    (512, 32, (32, "heuristic:short_rows")),
    (256, 32, (16, "heuristic:short_rows")),
    (256, 8, (64, "heuristic:short_rows")),
    (64, 1, (64, "heuristic:short_rows")),      # never a tile beyond the batch
    (160, 32, (8, "heuristic:short_rows")),     # a linear verify of 5 tokens a row
    (64, 32, (8, "heuristic:short_rows")),      # two tokens a row: the smallest tile
    (63, 1, (1, "heuristic:multi_token")),      # a tiny batch: the decode kernel
    (32, 32, (1, "heuristic:long_table")),
    (64, 33, (1, "heuristic:long_table")),      # fewer than two tokens a row
])
def test_q_tile_heuristic_follows_tokens_per_row(T, S, want, on_tpu):
    """The tile follows from the static (T, S) alone, one rule name per
    outcome."""
    choice = _choose(T, S, 65)
    assert (choice["q_tile"], choice["rule"]) == want
    assert choice["kernel"] == ("paged_attn_q_tiled" if want[0] > 1 else "paged_attn_kv_split")


# What the parent commit (5b4cb0f) chose for every (tokens, rows) program shape
# the engines of ``mistral-7b`` and ``mellum2-12b-a2.5b`` can warm (token
# buckets x row buckets, rows <= tokens, at the 65 table columns that
# ``max_context`` 8,320 over blocks of 128 gives both), generated ONCE from
# that commit's ``paged_attention`` with no environment variable set and an
# empty registry: (tokens, rows) -> (kernel, q_tile, rule). Both
# configurations read the same; the decode kernel's blocks a grid step were 2
# (8 kv heads) and 4 (4 kv heads). ONE row is rewritten, (32, 8): the parent
# ran ``paged_attn_per_token`` there, the grid this PR deleted; the rule's
# name is kept.
_PARENT_CHOICES = {
    (8, 8): ("paged_attn_kv_split", 1, "heuristic:long_table"),
    (16, 8): ("paged_attn_kv_split", 1, "heuristic:long_table"),
    (16, 16): ("paged_attn_kv_split", 1, "heuristic:long_table"),
    (32, 8): ("paged_attn_kv_split", 1, "heuristic:multi_token"),   # was paged_attn_per_token
    (32, 16): ("paged_attn_kv_split", 1, "heuristic:long_table"),
    (32, 32): ("paged_attn_kv_split", 1, "heuristic:long_table"),
    (64, 8): ("paged_attn_q_tiled", 16, "heuristic:short_rows"),
    (64, 16): ("paged_attn_q_tiled", 8, "heuristic:short_rows"),
    (64, 32): ("paged_attn_q_tiled", 8, "heuristic:short_rows"),
    (64, 64): ("paged_attn_kv_split", 1, "heuristic:long_table"),
    (128, 8): ("paged_attn_q_tiled", 32, "heuristic:short_rows"),
    (128, 16): ("paged_attn_q_tiled", 16, "heuristic:short_rows"),
    (128, 32): ("paged_attn_q_tiled", 8, "heuristic:short_rows"),
    (128, 64): ("paged_attn_q_tiled", 8, "heuristic:short_rows"),
    (256, 8): ("paged_attn_q_tiled", 64, "heuristic:short_rows"),
    (256, 16): ("paged_attn_q_tiled", 32, "heuristic:short_rows"),
    (256, 32): ("paged_attn_q_tiled", 16, "heuristic:short_rows"),
    (256, 64): ("paged_attn_q_tiled", 8, "heuristic:short_rows"),
    (512, 8): ("paged_attn_q_tiled", 128, "heuristic:long_rows"),
    (512, 16): ("paged_attn_q_tiled", 64, "heuristic:short_rows"),
    (512, 32): ("paged_attn_q_tiled", 32, "heuristic:short_rows"),
    (512, 64): ("paged_attn_q_tiled", 16, "heuristic:short_rows"),
    (1024, 8): ("paged_attn_q_tiled", 128, "heuristic:long_rows"),
    (1024, 16): ("paged_attn_q_tiled", 128, "heuristic:long_rows"),
    (1024, 32): ("paged_attn_q_tiled", 64, "heuristic:short_rows"),
    (1024, 64): ("paged_attn_q_tiled", 32, "heuristic:short_rows"),
    (2048, 8): ("paged_attn_q_tiled", 128, "heuristic:long_rows"),
    (2048, 16): ("paged_attn_q_tiled", 128, "heuristic:long_rows"),
    (2048, 32): ("paged_attn_q_tiled", 128, "heuristic:long_rows"),
    (2048, 64): ("paged_attn_q_tiled", 64, "heuristic:short_rows"),
}
_PARENT_DECODE_BLOCKS_PER_STEP = {"mistral-7b": 2, "mellum2-12b-a2.5b": 4}


def _program_shapes():
    """The engine's own bucket lists at the cells' engine settings (no model
    built): 2,048 tokens and 64 rows a batch."""
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import _pow2_buckets

    return [(T, S) for T in _pow2_buckets(2048) for S in _pow2_buckets(64) if S <= T]


def test_the_table_covers_the_engines_buckets():
    assert sorted(_PARENT_CHOICES) == sorted(_program_shapes())


@pytest.mark.parametrize("config", sorted(_HEADS))
@pytest.mark.parametrize("T,S", sorted(_PARENT_CHOICES))
def test_choice_for_every_program_shape_of_the_cells(T, S, config, on_tpu):
    """No behaviour changed: every serving program of both configurations
    compiles the kernel, tile, blocks a step and rule the parent compiled,
    but for the one 32-token x 8-row ``put`` the deleted grid served."""
    kernel, q_tile, rule = _PARENT_CHOICES[(T, S)]
    per_step = _PARENT_DECODE_BLOCKS_PER_STEP[config] if kernel == "paged_attn_kv_split" else 1
    assert _choose(T, S, -(-8320 // 128), config) == {
        "kernel": kernel, "q_tile": q_tile, "blocks_per_step": per_step, "rule": rule}


@pytest.mark.parametrize("config", sorted(_MORE_HEADS))
@pytest.mark.parametrize("T,S", sorted(_PARENT_CHOICES))
def test_the_one_head_rule_moves_no_grouped_configuration(T, S, config, on_tpu):
    """The rule added for pools by head with one query head a kv head (PR 40)
    moves no token-major pool: Trinity's 48/8 and SDAR's 32/4 choose what the
    table of the two configurations above says."""
    kernel, q_tile, rule = _PARENT_CHOICES[(T, S)]
    per_step = _PARENT_DECODE_BLOCKS_PER_STEP[_MORE_HEADS[config][5]] if kernel == "paged_attn_kv_split" else 1
    assert _choose(T, S, 65, config) == {"kernel": kernel, "q_tile": q_tile, "blocks_per_step": per_step, "rule": rule}


@pytest.mark.parametrize("T,S", [(T, 8) for T in (8, 16, 32, 64, 128, 256, 512, 1024, 2048)])
def test_the_absorbed_call_over_a_latent_pool_keeps_its_choice(T, S, on_tpu):
    """``glm-4.7-flash.longdoc``'s own programs (token buckets to 2,048 over 8
    rows, a table of 257 columns, 20 heads over ONE latent entry of 640 lanes,
    ``parts`` 1): the absorbed call is a group of 20 over a token-major pool,
    and its tile stays at 128 at most."""
    said = choose_kernel(T, S, 257, 20, 128, 640, 2, parts=1)
    assert said["q_tile"] <= 128 and not said["rule"].endswith("one_head")


@pytest.mark.parametrize("T,rows,want", [
    (2048, 2, (512, "heuristic:long_rows_one_head")),     # the cell's chunk step: two workspace slots, 5 table rows
    (1024, 1, (512, "heuristic:long_rows_one_head")),
    (512, 1, (256, "heuristic:long_rows_one_head")),      # never a tile beyond 2 T / S
    (256, 1, (128, "heuristic:long_rows_one_head")),
    (128, 1, (64, "heuristic:short_rows")),
])
def test_one_query_head_a_kv_head_takes_the_largest_tile_its_vmem_admits(T, rows, want, on_tpu):
    """The expanded form's call (``flat_model.expanded_batch``): 20 heads of
    256 over pools by head, one query head a kv head, so the MXU's left operand
    is the tile's own rows and the tile grows to the largest whose working set
    and half again is under the limit the kernel asks the compiler for: 512,
    where ``2 T / S`` allows it."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    nq = 20
    choice = choose_kernel(T, 2 * rows + 1, 257, nq, 128 * nq, 256, 2, kv_by_head=nq)
    assert (choice["kernel"], choice["q_tile"], choice["rule"]) == ("paged_attn_q_tiled", ) + want
    need = lambda qt: pa._q_tiled_vmem_bytes(nq * qt, qt, 256, 128, nq, 2, 2) * 3 // 2
    assert need(512) <= pa._Q_TILED_VMEM_LIMIT < need(1024)
    assert choose_kernel(8192, 2, 257, nq, 128 * nq, 256, 2, kv_by_head=nq)["q_tile"] == 512     # the limit, not 2 T / S


def test_one_head_rule_at_other_shapes(on_tpu):
    """Pools by head of 32 heads of 128 take the tile the same limit gives
    them; a served multi-head model (32/32 on the TOKEN-MAJOR pool, whose heads
    go through the scratch by kv head: no tile beyond 128 has run there), or
    pools by head under a group of several query heads, keep 128 as before,
    and a short-row step keeps its small tile."""
    assert choose_kernel(2048, 8, 65, 32, 128 * 32, 128, 2, kv_by_head=32) == {
        "kernel": "paged_attn_q_tiled", "q_tile": 512, "blocks_per_step": 1, "rule": "heuristic:long_rows_one_head"}
    assert choose_kernel(2048, 8, 65, 32, 128 * 32, 128, 2) == {
        "kernel": "paged_attn_q_tiled", "q_tile": 128, "blocks_per_step": 1, "rule": "heuristic:long_rows"}
    assert choose_kernel(2048, 8, 65, 32, 128 * 8, 128, 2, kv_by_head=8)["rule"] == "heuristic:long_rows"
    assert choose_kernel(512, 32, 65, 32, 128 * 32, 128, 2, kv_by_head=32)["rule"] == "heuristic:short_rows"
    assert choose_kernel(8, 8, 257, 20, 128 * 20, 256, 2, kv_by_head=20)["kernel"] == "paged_attn_kv_split"


@pytest.mark.parametrize("q_tile", [8, 16])
def test_pools_by_head_read_as_the_token_major_pool_does(q_tile):
    """Pools of four dimensions ``[nkv, blocks, block, d]`` (latent
    attention's workspace, PR 40) through the tiled kernel's body and through
    the gather: the outputs of the same keys and values as a token-major pool,
    with a softmax scale of the caller's; the decode kernel and int8 scales
    refuse them by name."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    rng, nq, kp, vp, tables, _ = _paged_setup(seed=9, nkv=4, g=1, n_seqs=3)
    d, bs = 32, 16
    by_head = lambda pool: jnp.moveaxis(pool.reshape(-1, bs, 4, d), 2, 0)
    seq_idx = np.asarray([0] * 13 + [1] * 6 + [2], np.int32)          # as _mixed_batch lays its rows out
    pos = np.asarray(list(range(20, 33)) + list(range(bs, bs + 6)) + [3 * bs + 5], np.int32)
    q = jnp.asarray(rng.normal(size=(20, nq, d)), jnp.float32)
    want = paged_attention_reference(q, kp, vp, tables, jnp.asarray(seq_idx), jnp.asarray(pos), bs, softmax_scale=0.3)
    gathered = paged_attention_reference(q, by_head(kp), by_head(vp), tables, jnp.asarray(seq_idx), jnp.asarray(pos), bs,
                                         softmax_scale=0.3)
    np.testing.assert_array_equal(np.asarray(gathered), np.asarray(want))
    got = _pallas_paged(q, by_head(kp), by_head(vp), tables, jnp.asarray(seq_idx), jnp.asarray(pos), block_size=bs,
                        interpret=True, q_tile=q_tile, softmax_scale=0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
    assert np.asarray(paged_attention(q, by_head(kp), by_head(vp), tables, jnp.asarray(seq_idx), jnp.asarray(pos), bs,
                                      softmax_scale=0.3)).shape == (20, nq, d)                  # off the TPU: the gather
    with pytest.raises(NotImplementedError, match="pools by head"):
        _pallas_paged(q, by_head(kp), by_head(vp), tables, jnp.asarray(seq_idx), jnp.asarray(pos), block_size=bs, interpret=True)
    with pytest.raises(ValueError, match="pools by head"):
        paged_attention(q, by_head(kp), None, tables, jnp.asarray(seq_idx), jnp.asarray(pos), bs, value_dim=8)


@pytest.mark.parametrize("q_tile", [8, 32])
def test_a_run_at_a_negative_position_has_no_item_and_leaves_the_others_alone(q_tile):
    """A caller takes a row out of a tiled call by handing its tokens the
    position -1 (latent attention's two calls, PR 40): they see no key, so
    their tiles have no item in the work list (the host's count agrees) and
    the other rows' outputs are the oracle's."""
    from deepspeed_tpu.ops.pallas.paged_attention import tiled_kv_counts

    rng, nq, kp, vp, tables, _ = _paged_setup(seed=5, n_seqs=3)
    d, bs = 32, 16
    # row 0: one decode token; row 1: 40 tokens taken out; row 2: 20 tokens after 10; the pad run
    seq_idx = np.asarray([0] + [1] * 40 + [2] * 20 + [0] * 3, np.int32)
    pos = np.asarray([37] + [-1] * 40 + list(range(10, 30)) + [0] * 3, np.int32)
    live = pos.copy()
    live[1:41] = np.arange(5, 45)
    q = jnp.asarray(rng.normal(size=(64, nq, d)), jnp.float32)
    work = lambda p: [np.asarray(a) for a in _tiled_work_list(tables, jnp.asarray(seq_idx), jnp.asarray(p), bs, None, q_tile)]
    *_, tile_seq, tile_cnt, w_tile, w_col, total = work(pos)
    items = np.bincount(w_tile[:int(total)], minlength=tile_cnt.size)
    assert (items[(tile_seq == 1) & (tile_cnt > 0)] == 0).all() and (items[(tile_seq != 1) & (tile_cnt > 0)] > 0).all()
    assert int(total) < int(work(live)[-1])
    max_blocks = tables.shape[1]
    assert tiled_kv_counts(q_tile, seq_idx, pos, [(None, 1)], bs, max_blocks, 3)[1] == int(total)
    ref = paged_attention_reference(q, kp, vp, tables, jnp.asarray(seq_idx), jnp.asarray(live), bs)
    out = _pallas_paged(q, kp, vp, tables, jnp.asarray(seq_idx), jnp.asarray(pos), block_size=bs, interpret=True,
                        q_tile=q_tile)
    keep = np.r_[0, 41:61]
    np.testing.assert_allclose(np.asarray(out)[keep], np.asarray(ref)[keep], rtol=2e-4, atol=2e-5)


def test_no_file_or_environment_can_change_the_choice(tmp_path, monkeypatch, on_tpu):
    """What moved the choice before this selector moves nothing now: the two
    override variables, and a ``kernel_config.json`` where the registry
    looked (``DS_TPU_KERNEL_CONFIG``, ``~/.cache/deepspeed_tpu/``) naming
    other tiles for every kernel, bucket and topology."""
    from deepspeed_tpu.ops.pallas.flash_attention import _default_tile, _resolve_tiles
    from deepspeed_tpu.ops.pallas.grouped_matmul import _resolve_gmm_tiles

    def everything():
        return ([_choose(T, S, 65, c) for c in sorted(_HEADS) for T, S in sorted(_PARENT_CHOICES)],
                _resolve_tiles(), _resolve_tiles(block_q=256), _resolve_gmm_tiles(2304, 896),
                _resolve_gmm_tiles(896, 2304, itemsize=4))

    before = everything()
    assert before[1] == (_default_tile(), ) * 2 and before[2] == (256, _default_tile())
    tiles = {"q_tile": 4, "kv_splits": 1, "block_q": 128, "block_k": 128, "block_n": 128}
    topo = f"{jax.devices()[0].device_kind}|n{len(jax.devices())}"
    planted = {"version": 1, "configs": {t: {k: {"*": tiles} for k in (
        "paged_attention", "flash_attention", "grouped_matmul")} for t in (topo, "TPU v5 lite|n1")}}
    home = tmp_path / "home"
    (home / ".cache" / "deepspeed_tpu").mkdir(parents=True)
    for path in (home / ".cache" / "deepspeed_tpu" / "kernel_config.json", tmp_path / "kernel_config.json"):
        path.write_text(json.dumps(planted))
    monkeypatch.setenv("HOME", str(home))
    monkeypatch.setenv("DS_TPU_KERNEL_CONFIG", str(tmp_path / "kernel_config.json"))
    monkeypatch.setenv("DS_TPU_PAGED_Q_TILE", "1")
    monkeypatch.setenv("DS_TPU_PAGED_KV_SPLITS", "1")
    assert everything() == before


@pytest.mark.parametrize("q_tile", [32, 128])
def test_qtiled_pad_run_behind_row0_under_window(q_tile):
    """A one-row batch deep in its context, then the pad run (seq 0 again,
    position 0): the fall in position starts a new run, so no tile mixes the
    chunk's positions with the pad's and the columns the work list allows a
    tile under the window (here 7 and 13 of a 32-block table) still cover
    every row's blocks: each tile's items are its own 4 to 12 blocks."""
    rng, nq, kp, vp, tables, _ = _paged_setup(seed=3, n_seqs=2, blocks_per_seq=32)
    d, bs, window = 32, 16, 50
    n = q_tile + 9
    seq_idx = jnp.asarray([0] * (n + 6), jnp.int32)
    pos = jnp.asarray(list(range(350, 350 + n)) + [0] * 6, jnp.int32)
    assert int(pos.max()) < 32 * bs
    q = jnp.asarray(rng.normal(size=(n + 6, nq, d)), jnp.float32)
    assert _contiguity_ok(seq_idx, 1, pos) and not _contiguity_ok(seq_idx, 1, pos[::-1])
    assert not _contiguity_ok(seq_idx, 1, pos.at[3].add(7))  # a jump forward inside a run
    *_, tile_cnt, w_tile, _, total = (np.asarray(a) for a in _tiled_work_list(tables, seq_idx, pos, bs, window, q_tile))
    cols = {32: 7, 128: 13}[q_tile]
    assert w_tile.size == tile_cnt.size * cols + 1
    per_tile = np.bincount(w_tile[:int(total)])
    assert per_tile.tolist() == {32: [6, 5, 1], 128: [12, 5, 1]}[q_tile] and per_tile.max() <= cols
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs, window=window)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                        q_tile=q_tile, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# grouped matmul oracle
# ---------------------------------------------------------------------------

def test_gmm_matches_reference_oracle():
    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm, gmm_reference

    rng = np.random.default_rng(3)
    T, K, N, E, bt = 32, 16, 24, 3, 8
    lhs = jnp.asarray(rng.normal(size=(T, K)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    be = jnp.asarray(np.sort(rng.integers(0, E, size=T // bt)), jnp.int32)
    out = gmm(lhs, rhs, be, block_t=bt, interpret=True)
    ref = gmm_reference(lhs, rhs, be, block_t=bt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# explicit ZeRO-3 overlap
# ---------------------------------------------------------------------------

def _overlap_engine(overlap, n_layers=4):
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=n_layers, num_heads=4,
                            intermediate_size=128, max_seq_len=64, dtype=jnp.float32,
                            attention_impl="reference")
    model = TransformerLM(cfg)
    n = len(jax.devices())
    config = {
        "train_batch_size": 2 * n,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3, "overlap_comm": bool(overlap)},
        "steps_per_print": 10**9,
        "tpu": {"mesh": {"data": n}},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    return engine, model


def test_overlap_on_off_bit_identical_loss():
    """zero_optimization.overlap_comm=true double-buffers next-layer gathers
    in the scan carry — same slices, same math: losses must be BIT-identical
    to the implicit path, and the engine must actually arm the model flag."""
    from deepspeed_tpu.parallel import groups

    losses = {}
    for overlap in (False, True):
        groups.reset()
        engine, model = _overlap_engine(overlap)
        assert model.config.overlap_gather is overlap
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, 128, size=(2 * len(jax.devices()), 64),
                                           dtype=np.int32)}
        losses[overlap] = [float(np.asarray(engine.train_batch(batch))) for _ in range(2)]
    assert losses[True] == losses[False], f"overlap changed the loss: {losses}"


def test_overlap_gather_rides_trace_bus():
    """The explicit gather is a PUBLIC collective: under jit its trace-time
    instant (comm/zero3_params_allgather, real payload bytes) lands on the
    PR 1 trace bus — the observable difference between the two schedules."""
    from deepspeed_tpu import dist
    from deepspeed_tpu.monitor.trace import get_tracer
    from deepspeed_tpu.parallel import groups

    tr = get_tracer().configure(enabled=True)
    try:
        groups.reset()
        engine, _ = _overlap_engine(True, n_layers=2)
        rng = np.random.default_rng(0)
        batch = {"input_ids": rng.integers(0, 128, size=(2 * len(jax.devices()), 64),
                                           dtype=np.int32)}
        engine.train_batch(batch)
        events = tr.drain()
        gathers = [e for e in events if e["name"] == "comm/zero3_params_allgather"]
        assert gathers, "explicit overlap gather left no trace instant"
        assert gathers[0]["args"]["msg_size"] > 0
        assert gathers[0]["args"].get("traced") is True
    finally:
        tr.reset()
        dist.comms_logger.enabled = False


def test_overlap_flag_cleared_for_reused_model():
    """A model object reused across engines must not leak one engine's
    overlap mode into the next (same sync contract as quantized_weights)."""
    from deepspeed_tpu.parallel import groups

    groups.reset()
    _, model = _overlap_engine(True)
    assert model.config.overlap_gather
    groups.reset()
    import deepspeed_tpu

    n = len(jax.devices())
    config = {
        "train_batch_size": 2 * n,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3},  # default: implicit overlap
        "steps_per_print": 10**9,
        "tpu": {"mesh": {"data": n}},
    }
    deepspeed_tpu.initialize(model=model, config=config)
    assert model.config.overlap_gather is False
