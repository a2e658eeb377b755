"""Raw-speed pass tests: q-tiled paged attention parity matrix (vs the
gather oracle), explicit ZeRO-3 overlap bit-identical loss, kernel-config
cache round-trip, and the ``tools/check_kernel_configs.py`` AST gate."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.autotuning.kernel_config import (CONFIG_FILENAME, KernelAutotuner,
                                                    KernelConfigRegistry, set_kernel_config_path,
                                                    shape_bucket, topology_key, tuned_tile)
from deepspeed_tpu.models.transformer import alibi_slopes
from deepspeed_tpu.ops.pallas import paged_attention as pa_mod
from deepspeed_tpu.ops.pallas.paged_attention import (_contiguity_ok, _decode_work_list, _pallas_paged,
                                                      _q_tile_choice, _resolve_kv_splits,
                                                      _resolve_q_tile, decode_kv_counts,
                                                      paged_attention_reference)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture(autouse=True)
def _fresh_registry():
    """The kernel-config registry is process-global: tests that plant
    configs must never leak them into other files' kernel calls."""
    set_kernel_config_path(None)
    yield
    set_kernel_config_path(None)


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


@pytest.mark.parametrize("q_tile", [4, 8])
@pytest.mark.parametrize("case", ["plain", "int8", "alibi", "window", "window_alibi",
                                  "int8_window", "gqa"])
def test_qtiled_parity_matrix(case, q_tile):
    """The q-tiled grid must match the gather oracle bit-for-tolerance on
    every kernel feature the per-token grid supports — ragged tile tails,
    int8 dequant-at-tile-read, alibi, sliding window, GQA — on a mixed
    prefill+decode batch."""
    import zlib

    nkv, g = (2, 4) if case == "gqa" else (2, 2)
    int8 = case.startswith("int8")
    # crc32, not hash(): PYTHONHASHSEED salting would make a tolerance-edge
    # failure unreproducible across runs
    rng, nq, kp, vp, tables, scales = _paged_setup(seed=zlib.crc32(case.encode()), nkv=nkv,
                                                   g=g, int8=int8)
    d, bs = 32, 16
    q, seq_idx, pos = _mixed_batch(rng, nq, d, bs)
    kw = dict(scales)
    if "alibi" in case:
        kw["alibi"] = tuple(alibi_slopes(nq).tolist())
    if "window" in case:
        kw["window"] = 17
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs, **kw)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                        q_tile=q_tile, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)
    # and the per-token grid agrees too (the q-tile regroup changed nothing)
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
                                  "gqa4", "f32", "f32_window_alibi"])
def test_qtiled_prefill_tile_parity_matrix(case, q_tile):
    """The tiles the shape heuristic picks for prefill (32 for many rows, 128
    for long prompts) against the gather oracle: bf16 q and pool, so both
    dots run on bf16 operands with float32 accumulation, at the tolerances
    the chip tests hold bf16 and int8 to; float32 inputs at the interpret
    matrix's. The window (50) is shorter than a tile, so its lower edge
    crosses KV blocks inside one tile."""
    import zlib

    f32 = case.startswith("f32")
    dtype = jnp.float32 if f32 else jnp.bfloat16
    nkv, g = (2, 4) if case == "gqa4" else (2, 2)
    d, bs, blocks_per_seq = 32, 16, 24
    rng, nq, kp, vp, tables, scales = _paged_setup(seed=zlib.crc32(case.encode()), nkv=nkv, g=g, d=d, bs=bs,
                                                   n_seqs=4, blocks_per_seq=blocks_per_seq,
                                                   int8=case.startswith("int8"))
    if not scales:
        kp, vp = kp.astype(dtype), vp.astype(dtype)
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


def test_qtiled_decode_only_with_pad_run():
    """Pure-decode shape: one token per sequence plus the trailing pad run
    (seq 0, pos 0 — exactly what ragged_wrapper.finalize emits). Every tile
    holds a single valid token; tiled and per-token grids must agree with
    the oracle."""
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
# flash-decode KV-split: interpret-mode parity matrix vs the gather oracle
# ---------------------------------------------------------------------------

def _decode_batch(rng, nq, d, bs, blocks_per_seq):
    """Decode-shaped batch: one token per sequence at varied live depths —
    seq 0 fully live (the long-context row the split exists for), the rest
    mid-context — plus the trailing pad run ragged_wrapper.finalize emits."""
    seq_idx = np.asarray([0, 1, 2, 0, 0], np.int32)
    pos = np.asarray([blocks_per_seq * bs - 1, bs + 3, 2 * bs + 7, 0, 0], np.int32)
    T = seq_idx.size
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.float32)
    return q, jnp.asarray(seq_idx), jnp.asarray(pos)


@pytest.mark.parametrize("kv_splits", [2, 4])
@pytest.mark.parametrize("case", ["plain", "int8", "alibi", "window", "window_alibi",
                                  "int8_window", "gqa"])
def test_kv_split_parity_matrix(case, kv_splits):
    """The KV-split decode grid (partial softmax per split + log-sum-exp
    merge) must match the gather oracle on every kernel feature the
    per-token grid supports — int8 dequant, alibi, sliding window, GQA,
    partially-live contexts, pad rows — and the per-token grid must agree
    too (the split changed the schedule, not the math)."""
    import zlib

    nkv, g = (2, 4) if case == "gqa" else (2, 2)
    int8 = case.startswith("int8")
    rng, nq, kp, vp, tables, scales = _paged_setup(seed=zlib.crc32(case.encode()), nkv=nkv,
                                                   g=g, int8=int8, blocks_per_seq=8)
    d, bs = 32, 16
    q, seq_idx, pos = _decode_batch(rng, nq, d, bs, blocks_per_seq=8)
    kw = dict(scales)
    if "alibi" in case:
        kw["alibi"] = tuple(alibi_slopes(nq).tolist())
    if "window" in case:
        kw["window"] = 21
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs, **kw)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                        q_tile=1, kv_splits=kv_splits, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)
    out1 = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                         q_tile=1, kv_splits=1, **kw)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref), rtol=2e-4, atol=2e-5)


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
    monkeypatch.setattr(pa_mod, "_decode_blocks_per_step", lambda rows, d, itemsize: request.param)
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
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                        q_tile=1, kv_splits=2, **kw)
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
    # the grids that walk the whole table run every column of every bucket row
    assert decode_kv_counts({"kernel": "paged_attn_per_token", "blocks_per_step": 1}, pos[:real],
                            [(window, 3)], bs, mb, T) == (3 * T * mb, 3 * live)


def test_kv_split_non_dividing_factor_and_single_block():
    """A split factor that does not divide the table (ceil rounding leaves
    the last split short) and a context living entirely inside split 0 must
    both merge correctly — dead splits carry (m=-inf, l=0) and vanish."""
    rng, nq, kp, vp, tables, _ = _paged_setup(seed=5, n_seqs=2, blocks_per_seq=6)
    d, bs = 32, 16
    q = jnp.asarray(rng.normal(size=(2, nq, d)), jnp.float32)
    seq_idx = jnp.asarray([0, 1], jnp.int32)
    pos = jnp.asarray([6 * bs - 1, 2], jnp.int32)  # full table; single-block
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs)
    for ks in (3, 4, 6):  # 6 blocks: 3 divides, 4 leaves a short tail, 6 = 1 block/split
        out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                            q_tile=1, kv_splits=ks)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5,
                                   err_msg=f"kv_splits={ks}")


def test_resolve_kv_splits_contract_and_registry(tmp_path):
    """kv_splits resolution: decode-shaped rows with a long table split,
    prefill tiles and short tables never do; the registry (exact (B, T)
    bucket, then the B-only sweep bucket) beats the heuristic; the
    DS_TPU_PAGED_KV_SPLITS kill switch beats everything."""
    # heuristic: long-table decode splits, tiled prefill / short table never
    assert _resolve_kv_splits(4, 4, 64) == 8
    assert _resolve_kv_splits(4, 4, 4) == 1
    assert _resolve_kv_splits(256, 4, 64, q_tile=8) == 1
    # registry override for this topology
    reg = KernelConfigRegistry(str(tmp_path / CONFIG_FILENAME))
    reg.record("paged_attention", shape_bucket(B=64), {"kv_splits": 4})
    reg.record("paged_attention", shape_bucket(B=64, T=8), {"kv_splits": 2})
    reg.save()
    set_kernel_config_path(str(tmp_path / CONFIG_FILENAME))
    assert _resolve_kv_splits(8, 8, 64) == 2      # exact (B, T) bucket wins
    assert _resolve_kv_splits(4, 4, 64) == 4      # B-only sweep bucket
    assert _resolve_kv_splits(4, 4, 32) == 8      # untouched bucket: heuristic
    # kill switch: =1 pins the single-chain grid, higher values force
    os.environ["DS_TPU_PAGED_KV_SPLITS"] = "1"
    try:
        assert _resolve_kv_splits(4, 4, 64) == 1
        os.environ["DS_TPU_PAGED_KV_SPLITS"] = "16"
        assert _resolve_kv_splits(4, 4, 64) == 16
        # forced factor still clamps to the table (8 blocks cap 16 -> 8)
        assert _resolve_kv_splits(4, 4, 8) == 8
        # and a too-short table stays single-chain even under the override
        assert _resolve_kv_splits(4, 4, 4) == 1
    finally:
        del os.environ["DS_TPU_PAGED_KV_SPLITS"]


def test_tune_paged_decode_records_reachable_bucket(tmp_path):
    """The decode sweep's winner must land under the B-only bucket the live
    ``_resolve_kv_splits`` fallback actually reads — a sweep recording an
    unreachable key is a silent no-op (the PR 10 tune_paged lesson)."""
    tuner = KernelAutotuner(str(tmp_path), steps=1, warmup=0)
    best = tuner.tune_paged_decode(n_seqs=2, max_blocks=16,
                                   candidates=[{"kv_splits": 1}, {"kv_splits": 4}])
    assert best is not None and best["kv_splits"] in (1, 4)
    path = tuner.registry.save(os.path.join(str(tmp_path), CONFIG_FILENAME))
    set_kernel_config_path(path)
    assert _resolve_kv_splits(2, 2, 16) == best["kv_splits"]
    assert _resolve_kv_splits(8, 8, 16) == best["kv_splits"]  # any decode batch size


def test_explicit_q_tile_demoted_on_noncontiguous_batch():
    """An EXPLICIT q_tile must not bypass the layout contract: the public
    wrapper demotes to the per-token grid (correct output) instead of
    letting the tiled grid overflow its static tile bound and silently
    scatter tokens into the wrong tiles."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa_mod

    rng, nq, kp, vp, tables, _ = _paged_setup(seed=11, n_seqs=2)
    d, bs = 32, 16
    T = 16
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.float32)
    seq_idx = jnp.asarray(np.arange(T) % 2, jnp.int32)  # interleaved: runs = T
    pos = jnp.asarray(rng.integers(0, 2 * bs, size=T), jnp.int32)
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs)
    assert not pa_mod._contiguity_ok(seq_idx, 2)
    # wrapper path: demotion keeps the output correct even with q_tile=8.
    # (off-TPU the wrapper reference-falls-back anyway, so exercise the
    # demotion decision directly plus the kernel at the demoted tile.)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                        q_tile=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_resolve_q_tile_contract_and_registry(tmp_path):
    """q-tile resolution: registry wins over the heuristic; a CONCRETE
    seq_idx violating the same-sequence-contiguity contract demotes tiling
    to per-token (the tiled grid would otherwise overflow its tile bound)."""
    # heuristic: prefill-ish T tiles, pure-decode-ish T does not
    assert _resolve_q_tile(256, 4) == 128
    assert _resolve_q_tile(8, 8) == 1
    # contiguity guard on concrete seq_idx: alternating tokens -> demoted
    interleaved = jnp.asarray(np.arange(64) % 2, jnp.int32)
    assert _resolve_q_tile(64, 2, interleaved) == 1
    contiguous = jnp.asarray(np.repeat([0, 1], 32), jnp.int32)
    assert _resolve_q_tile(64, 2, contiguous) == 64
    # registry override (planted for THIS topology) beats the heuristic
    reg = KernelConfigRegistry(str(tmp_path / CONFIG_FILENAME))
    reg.record("paged_attention", shape_bucket(T=256, S=4), {"q_tile": 16})
    reg.record("paged_attention", shape_bucket(T=256), {"q_tile": 8})
    reg.save()
    set_kernel_config_path(str(tmp_path / CONFIG_FILENAME))
    assert _resolve_q_tile(256, 4) == 16
    # the T-only sweep bucket reaches OTHER prefill-ish capacities...
    assert _resolve_q_tile(256, 8) == 8
    # ...but never a pure-decode shape (every tile would be 7/8 masked)
    assert _resolve_q_tile(256, 256) == 1
    # DS_TPU_PAGED_Q_TILE: operator kill switch beats registry + heuristic
    # (the serving-path outer jit compiles the tiled grid where the in-
    # wrapper ladder can't catch a Mosaic failure — =1 pins per-token)
    os.environ["DS_TPU_PAGED_Q_TILE"] = "1"
    try:
        assert _resolve_q_tile(256, 4) == 1
        os.environ["DS_TPU_PAGED_Q_TILE"] = "16"
        assert _resolve_q_tile(8, 8) == 16
    finally:
        del os.environ["DS_TPU_PAGED_Q_TILE"]


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
    (63, 1, (1, "heuristic:decode_shaped")),    # a tiny batch
    (32, 32, (1, "heuristic:decode_shaped")),
    (64, 33, (1, "heuristic:decode_shaped")),   # fewer than two tokens a row
])
def test_q_tile_heuristic_follows_tokens_per_row(T, S, want):
    """The tile follows from the static (T, S) alone, one rule name per
    outcome; no registry, no environment."""
    assert "DS_TPU_PAGED_Q_TILE" not in os.environ
    assert _q_tile_choice(T, S) == want
    assert _resolve_q_tile(T, S) == want[0]


@pytest.mark.parametrize("q_tile", [32, 128])
def test_qtiled_pad_run_behind_row0_under_window(q_tile):
    """A one-row batch deep in its context, then the pad run (seq 0 again,
    position 0): the fall in position starts a new run, so no tile mixes the
    chunk's positions with the pad's and the window-bounded kv axis (here 7
    and 13 steps of a 32-block table) still covers every row's blocks."""
    rng, nq, kp, vp, tables, _ = _paged_setup(seed=3, n_seqs=2, blocks_per_seq=32)
    d, bs, window = 32, 16, 50
    n = q_tile + 9
    seq_idx = jnp.asarray([0] * (n + 6), jnp.int32)
    pos = jnp.asarray(list(range(350, 350 + n)) + [0] * 6, jnp.int32)
    assert int(pos.max()) < 32 * bs
    q = jnp.asarray(rng.normal(size=(n + 6, nq, d)), jnp.float32)
    assert _contiguity_ok(seq_idx, 1, pos) and not _contiguity_ok(seq_idx, 1, pos[::-1])
    assert not _contiguity_ok(seq_idx, 1, pos.at[3].add(7))  # a jump forward inside a run
    ref = paged_attention_reference(q, kp, vp, tables, seq_idx, pos, bs, window=window)
    out = _pallas_paged(q, kp, vp, tables, seq_idx, pos, block_size=bs, interpret=True,
                        q_tile=q_tile, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_tuned_tile_consulted_by_every_call_site(tmp_path):
    """Plant a config file and verify each tuned kernel's resolution helper
    actually reads it — flash block_q/block_k, grouped block_k/block_n,
    paged q_tile (the 'one kernel-config registry' acceptance criterion)."""
    from deepspeed_tpu.ops.pallas.flash_attention import _resolve_tiles
    from deepspeed_tpu.ops.pallas.grouped_matmul import _resolve_gmm_tiles

    reg = KernelConfigRegistry(str(tmp_path / CONFIG_FILENAME))
    reg.record("flash_attention", shape_bucket(S=1024, d=64), {"block_q": 256, "block_k": 128})
    reg.record("grouped_matmul", "*", {"block_k": 64, "block_n": 32})
    reg.record("paged_attention", shape_bucket(T=128, S=2), {"q_tile": 4})
    reg.save()
    set_kernel_config_path(str(tmp_path / CONFIG_FILENAME))

    assert _resolve_tiles(1024, 64) == (256, 128)
    assert _resolve_tiles(1024, 64, block_q=512) == (512, 128)  # explicit beats registry
    assert _resolve_gmm_tiles(2048, 2048) == (64, 32)  # "*" bucket fallback
    assert _resolve_q_tile(128, 2) == 4
    # absent bucket -> caller defaults survive
    assert _resolve_gmm_tiles(2048, 2048, block_k=512, block_n=512) == (512, 512)


def test_kernel_config_roundtrip(tmp_path):
    """record -> save -> fresh registry load -> lookup by topology key; the
    file is reloaded by mtime and unknown topologies never leak configs."""
    path = str(tmp_path / CONFIG_FILENAME)
    reg = KernelConfigRegistry(path)
    topo = topology_key()
    reg.record("flash_attention", "S2048|d128", {"block_q": 1024, "block_k": 512, "_ms": 1.5})
    reg.save()
    assert os.path.exists(path)
    raw = json.load(open(path))
    assert raw["version"] == 1 and topo in raw["configs"]

    fresh = KernelConfigRegistry(path)
    assert fresh.lookup("flash_attention", "S2048|d128", "block_q", 512) == 1024
    assert fresh.lookup("flash_attention", "S2048|d128", "block_k", 0) == 512
    # missing bucket/kernel/param -> default
    assert fresh.lookup("flash_attention", "S4096|d128", "block_q", 777) == 777
    assert fresh.lookup("nope", "S2048|d128", "block_q", 5) == 5
    # a DIFFERENT topology's entry is invisible here
    fresh.record("paged_attention", "*", {"q_tile": 32}, topo="TPU v9|n4096")
    assert fresh.lookup("paged_attention", "*", "q_tile", 1) == 1
    # mtime reload: a second writer's update is picked up without a restart
    writer = KernelConfigRegistry(path)
    writer.record("flash_attention", "S2048|d128", {"block_q": 256})
    os.utime  # noqa: B018 — document the mtime dependency
    writer.save()
    assert fresh.lookup("flash_attention", "S2048|d128", "block_q", 0) == 256


def test_autotuner_sweep_persists_next_to_best_config(tmp_path):
    """The measured-trial sweep writes kernel_config.json into the output
    dir (next to best_config.json) and a reload through the global registry
    serves the winners to call sites."""
    out = str(tmp_path)
    tuner = KernelAutotuner(out, steps=1, warmup=0)
    # deterministic sweep: candidate b is strictly cheaper
    calls = []

    def build(cand):
        def run():
            calls.append(cand["q_tile"])
            import time

            if cand["q_tile"] == 1:
                time.sleep(0.01)
            return jnp.zeros(())

        return run

    best = tuner.sweep("paged_attention", "T256|S8", [{"q_tile": 1}, {"q_tile": 8}], build)
    assert best["q_tile"] == 8 and set(calls) == {1, 8}
    path = tuner.registry.save(os.path.join(out, CONFIG_FILENAME))
    assert os.path.basename(path) == CONFIG_FILENAME
    set_kernel_config_path(path)
    assert tuned_tile("paged_attention", "T256|S8", "q_tile", 1) == 8
    # a raising candidate costs itself, not the sweep
    def build_bad(cand):
        if cand["q_tile"] == 4:
            raise RuntimeError("over budget")
        return build(cand)

    best2 = tuner.sweep("paged_attention", "T64|S8", [{"q_tile": 4}, {"q_tile": 2}], build_bad)
    assert best2["q_tile"] == 2


@pytest.mark.slow
def test_autotuner_tune_all_cpu_smoke(tmp_path):
    """tune_all exercises the real kernel sweeps (interpret mode off-TPU,
    tiny shapes) end to end and leaves the artifact."""
    tuner = KernelAutotuner(str(tmp_path), steps=1, warmup=0)
    path = tuner.tune_all(kernels=("paged_attention", "grouped_matmul"))
    assert os.path.exists(path)
    reg = KernelConfigRegistry(path)
    # the sweep's own shape must be prefill-ish or its winner is unreachable
    swept = reg.lookup("paged_attention", shape_bucket(T=128), "q_tile", None)
    assert swept is not None
    # the e2e contract: whichever candidate won, it is reachable from the
    # LIVE call site for ANY prefill-ish block-table capacity
    set_kernel_config_path(path)
    assert _resolve_q_tile(128, 4) == swept
    assert _resolve_q_tile(128, 64) == swept


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


# ---------------------------------------------------------------------------
# bench backend stamp + cross-backend refusal
# ---------------------------------------------------------------------------

def test_bench_backend_stamp_and_cross_backend_refusal(tmp_path):
    """The BENCH_r04/r05 caveat made machine-checkable: the final JSON is
    backend+chip stamped, and compare_to_baseline REFUSES ratios across
    backends (and across chips), including legacy baselines judged by
    on_tpu, while a stampless pre-r06 baseline is refused outright."""
    import bench

    line = {"metric": "train_tokens_per_sec_per_chip", "value": 100.0,
            **bench.backend_stamp(False)}
    assert line["backend"] == "cpu" and line["chip"] == "cpu"

    p = tmp_path / "b.json"
    p.write_text(json.dumps({"value": 200.0, "backend": "tpu", "chip": "TPU v5 lite"}))
    res = bench.compare_to_baseline(line, str(p))
    assert "cross-backend" in res.get("refused", "")

    p.write_text(json.dumps({"value": 50.0, "backend": "cpu", "chip": "cpu"}))
    assert bench.compare_to_baseline(line, str(p))["ratio"] == 2.0

    # the driver's BENCH_rXX wrapper with only the on_tpu disclosure (r04/r05)
    p.write_text(json.dumps({"parsed": {"value": 100.0, "on_tpu": False}}))
    assert bench.compare_to_baseline(line, str(p))["ratio"] == 1.0
    p.write_text(json.dumps({"parsed": {"value": 100.0, "on_tpu": True}}))
    assert "cross-backend" in bench.compare_to_baseline(line, str(p)).get("refused", "")

    # stampless ancient line: refuse rather than guess
    p.write_text(json.dumps({"parsed": {"value": 100.0}}))
    assert "refused" in bench.compare_to_baseline(line, str(p))
    # unreadable baseline: refuse, never raise
    assert "refused" in bench.compare_to_baseline(line, str(tmp_path / "missing.json"))
    # truthy but non-numeric value: refuse, never raise (the headline-safety
    # invariant — a crash here would eat the whole run's final JSON)
    p.write_text(json.dumps({"value": "12.3 tok/s", "backend": "cpu", "chip": "cpu"}))
    assert "refused" in bench.compare_to_baseline(line, str(p))


# ---------------------------------------------------------------------------
# AST gate
# ---------------------------------------------------------------------------

def test_kernel_config_gate_clean():
    from tools.check_kernel_configs import TUNED_KERNELS, check, main

    assert check() == [], "tuned kernels drifted from the registry contract"
    assert main([]) == 0
    assert set(TUNED_KERNELS) == {"flash_attention.py", "paged_attention.py",
                                  "grouped_matmul.py"}


def test_kernel_config_gate_drift_catch(tmp_path):
    """The gate must catch (a) a tuned kernel regrowing a hardcoded tile
    default / dropping the registry call, and (b) a NEW kernel module with a
    hardcoded tile."""
    from tools.check_kernel_configs import check

    # (b) new kernel, hardcoded tile, no allowlist entry
    (tmp_path / "shiny_new_kernel.py").write_text(
        "def fancy(x, block_q=512):\n    return pl.pallas_call(x)\n")
    problems = check(str(tmp_path))
    assert any("shiny_new_kernel.py" in p and "block_q=512" in p for p in problems)

    # (a) a tuned module that hardcodes + skips the registry + drops the oracle
    (tmp_path / "shiny_new_kernel.py").unlink()
    (tmp_path / "flash_attention.py").write_text(
        "def flash_attention(q, k, v, block_q=1024, block_k=1024):\n"
        "    return pl.pallas_call(q)\n")
    problems = check(str(tmp_path))
    assert any("block_q=1024" in p for p in problems)
    assert any("tuned_tile" in p for p in problems)
    assert any("reference" in p for p in problems)
