"""The selection indexer's kernel (``ops/pallas/sparse_index.py``) with its
body on the interpreter against the XLA form it stands in for
(``sparse_index.block_scores``) at a small size: heads 6/2 of 16, pooling
kernel 4 at stride 2, blocks of 8, top 6, window 16, ``dense_len`` 48, key
blocks of 8 table columns so that a row's context spans several; the work
list's rule under ``numpy`` and under ``jnp``; what the step spans say was
scored. ONE engine, module-scoped, with the kernels' bodies on the
interpreter and one 64-token program."""

import functools
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import sparse_index as si  # noqa: E402
from deepspeed_tpu.models import TransformerLM, minicpm_config  # noqa: E402
from deepspeed_tpu.monitor.trace import get_tracer  # noqa: E402
from deepspeed_tpu.ops.pallas import sparse_index as kernel  # noqa: E402

CFG = types.SimpleNamespace(sparse_kernel_size=4, sparse_kernel_stride=2, sparse_topk=6, sparse_init_blocks=1,
                            sparse_window_size=16, sparse_dense_len=48)
BLOCK, NQ, NKV, D, KEY_BLOCK = 8, 6, 2, 16, 8
RULE = (BLOCK, CFG.sparse_kernel_stride, CFG.sparse_kernel_size, CFG.sparse_dense_len)


def _batch(rows, T, S, max_blocks, seed=0, spread=0.3):
    """A ragged batch of ``rows`` ``(seen, new)`` in a program of ``T`` tokens
    and ``S`` rows, tables of distinct blocks, seeded pooled keys and queries
    (``spread``: the scores' scale; small, a softmax is flat and block scores
    lie apart by more than rounding)."""
    rng = np.random.default_rng(seed)
    seq_idx, pos, valid = np.zeros(T, np.int32), np.zeros(T, np.int32), np.zeros(T, bool)
    t = 0
    for r, (seen, new) in enumerate(rows):
        seq_idx[t:t + new], pos[t:t + new], valid[t:t + new] = r, np.arange(seen, seen + new), True
        t += new
    n_blocks = S * max_blocks + 3
    tables = rng.permutation(n_blocks)[:S * max_blocks].reshape(S, max_blocks).astype(np.int32)
    p_flat = jnp.asarray(rng.standard_normal((n_blocks * BLOCK // CFG.sparse_kernel_stride, NKV, D)) * spread, jnp.float32)
    q = jnp.asarray(rng.standard_normal((T, NQ, D)), jnp.float32)
    return q, p_flat, jnp.asarray(tables), jnp.asarray(seq_idx), jnp.asarray(pos), jnp.asarray(valid)


@pytest.fixture
def small_key_blocks(monkeypatch):
    """The kernel at key blocks of 8 table columns (the interpreter takes any width)."""
    monkeypatch.setattr(si, "index_scores", functools.partial(kernel.index_scores, key_block=KEY_BLOCK))
    monkeypatch.setattr(si, "index_work", functools.partial(kernel.index_work, key_block=KEY_BLOCK))


def _both(batch):
    """``(kernel's scores, XLA form's, tile positions [n_tiles, q_tile], filled)``."""
    got, (_, _, filled, tile_pos) = si.tile_scores(CFG, BLOCK, *batch, interpret=True)
    want, _ = si.tile_scores(CFG, BLOCK, *batch)
    return np.asarray(got), np.asarray(want), np.asarray(tile_pos), np.asarray(filled)


CASES = {
    # (a) a row whose chunk crosses dense_len inside a tile (positions 44-51), beside a row past it
    "a_tile_straddles_dense_len": ([(44, 16), (100, 40)], 64, 4, 24),
    # (b) chunk ends that are no multiple of the stride (2), the block (8) or the key block (64 tokens)
    "positions_that_divide_nothing": ([(61, 13), (131, 27), (67, 1)], 64, 4, 24),
    # (c) riding one-token rows beside a chunk of whole tiles
    "a_one_token_tile_beside_full_ones": ([(150, 1), (77, 1), (96, 32)], 64, 4, 24),
    # (d) a row wholly under dense_len, a row that was not fed, and the pad run: tiles without an item
    "an_empty_and_an_all_dense_tile": ([(8, 24), (0, 0), (120, 16)], 64, 4, 24),
    # (e) a table twice as wide as any row's blocks
    "a_table_wider_than_the_rows": ([(60, 20), (70, 3)], 64, 4, 48),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_block_scores_are_the_xla_forms(case, small_key_blocks):
    """Every tile with an item: the kernel's ``[q_tile, nkv, blocks]`` scores
    within float32 rounding of ``block_scores``'; every tile without one reads
    0, and so does every slot no token fills."""
    rows, T, S, max_blocks = CASES[case]
    got, want, tile_pos, filled = _both(_batch(rows, T, S, max_blocks))
    has_item = (np.where(filled, tile_pos, -1).max(axis=1) + 1) > CFG.sparse_dense_len
    assert has_item.any() and (case != "an_empty_and_an_all_dense_tile" or (~has_item & filled.any(axis=1)).any())
    assert np.abs(got[has_item] - want[has_item]).max() < 2e-6 * np.abs(want).max()
    assert not got[~has_item].any() and not got[~filled].any()
    assert np.isfinite(got).all()


@pytest.mark.parametrize("case", list(CASES))
def test_the_selection_is_the_xla_paths_bit_for_bit_where_the_scores_are_apart(case, small_key_blocks):
    """``select_blocks`` through the kernel against the XLA path on every
    token of the batch, the dense and the padded ones among them: equal
    wherever the token's ``topk``-th and next block scores lie apart by more
    than rounding (a flat softmax: they do for nearly every token)."""
    rows, T, S, max_blocks = CASES[case]
    batch = _batch(rows, T, S, max_blocks)
    a = np.asarray(si.select_blocks(CFG, BLOCK, *batch, interpret=True))
    b = np.asarray(si.select_blocks(CFG, BLOCK, *batch))
    _, want, tile_pos, filled = _both(batch)
    # a token's margin: the gap between the last block chosen and the first left out, among the unforced
    tile_id, place, *_ = kernel.index_work(*batch[3:], want.shape[0], 8, *RULE, KEY_BLOCK)
    r = want.reshape(-1, NKV, max_blocks)[np.asarray(tile_id) * 8 + np.asarray(place)]            # [T, nkv, blocks]
    pos = np.asarray(batch[4])
    col = np.arange(max_blocks)[None, :]
    own, first = (pos // BLOCK)[:, None], (np.maximum(pos - (CFG.sparse_window_size - 1), 0) // BLOCK)[:, None]
    free = (col <= own) & ~((col < CFG.sparse_init_blocks) | (col >= first))
    ranked = np.sort(np.where(free[:, None, :], r, -np.inf), axis=-1)[..., ::-1]
    n_free = CFG.sparse_topk - (col <= own).sum(axis=1) + free.sum(axis=1)                       # topk less the forced
    apart = np.ones(r.shape[:2], bool)
    for t in range(T):
        k = int(n_free[t])
        if 0 < k < free[t].sum():
            apart[t] = ranked[t, :, k - 1] - ranked[t, :, k] > 1e-5
    apart &= np.asarray(batch[5])[:, None]
    assert apart.mean() > 0.5 * np.asarray(batch[5]).mean()
    assert (a == b)[apart].all()
    dense = (pos + 1 <= CFG.sparse_dense_len) | ~np.asarray(batch[5])
    assert (a == b)[dense].all()


def test_the_work_lists_rule_is_one_under_numpy_and_under_jnp():
    """``index_work`` on a batch's tokens, ``xp=np`` against ``xp=jnp``; and
    its key blocks by hand: a tile has them up to the last pooled key complete
    at its LAST live token, none without a token past ``dense_len``."""
    rows, T, S = [(44, 16), (100, 40), (7, 1), (200, 1)], 64, 4
    *_, seq_idx, pos, valid = _batch(rows, T, S, 32)
    n_tiles = T // 8 + S + 1
    under = [kernel.index_work(*(xp.asarray(a) for a in (seq_idx, pos, valid)), n_tiles, 8, *RULE, KEY_BLOCK, xp=xp)
             for xp in (np, jnp)]
    for a, b in zip(*under):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    _, _, last, n_kb, slots = (np.asarray(a) for a in under[0])
    # tiles: row 0's two (44-51, 52-59), row 1's five (to 107, 115, 123, 131, 139), the rows of one token, the pad run
    assert list(last[:9]) == [51, 59, 107, 115, 123, 131, 139, 7, 200] and (last[9:] == -1).all()
    # a key block holds 8 columns x 4 pooled keys; pooled key m is complete at 2 m + 3
    assert list(n_kb) == [-(-((p - 3) // 2 + 1) // 32) if p + 1 > 48 else 0 for p in last]
    assert (slots == 8).all()
    assert kernel.index_work(seq_idx, pos, valid, n_tiles, 128, *RULE)[4].tolist()[:5] == [128, 128, 8, 8, 8]


@pytest.fixture(scope="module")
def engine():
    """The tiny model's two sparse layers between two lightning ones, every
    kernel's body on the interpreter, ONE program of 64 tokens and 4 rows
    (tiles of 8: the indexer's kernel)."""
    cfg = minicpm_config("tiny", num_layers=4, first_layer=2, dtype=jnp.float32)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(3))
    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=64, max_ragged_sequence_count=4,
                              max_context=2048, token_buckets=(64, ), seq_buckets=(4, ))
    icfg = RaggedInferenceEngineConfig(kv_block_size=BLOCK, num_kv_blocks=300, kv_dtype=jnp.float32, state_manager=sm)
    icfg.modules.attention = {"name": "dense_blocked_attention", "implementation_config": {"interpret": True}}
    return cfg, InferenceEngineV2(TransformerLM(cfg), icfg, params=params)


def test_a_step_span_says_what_the_kernels_items_covered(engine):
    """``index_keys_scored`` on the step spans: between ``index_keys`` (what
    the model asked for) and the rectangle the XLA form scores, and by hand: a
    60-token chunk after 40 and a riding token at 70, in tiles of 8 under a
    table of 256 columns (two key blocks of 128 columns, 512 pooled keys each:
    every live tile covers the first alone)."""
    cfg, eng = engine
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, size=120, dtype=np.int32)
    get_tracer().reset()
    tracer = get_tracer().configure(enabled=True)
    try:
        eng.put([31], [ids[:40]])
        eng.put([32], [ids[:60]]), eng.put([32], [ids[60:70]])
        eng.put([31, 32], [ids[40:100], ids[70:71]])
        spans = [e["args"] for e in tracer.drain() if e["ph"] == "X" and e["name"] == "serving/prefill"]
        horizon = eng._sparse_span_args(np.asarray([100, 71]), np.asarray([4, 4]), 0, 4)
    finally:
        get_tracer().reset()
        eng.flush(31), eng.flush(32)
    first, last = spans[0], spans[-1]
    layers, nkv, rectangle = 2, 2, (64 // 8 + 4 + 1) * 8 * 256 * 4
    assert first["index_keys"] == first["index_keys_scored"] == 0          # 40 tokens under dense_len: no item
    # the chunk's tiles end at 47, 55, ..., 95 and 99: all but the first have a token past 48; the riding row's one
    # tile (8 slots) at 70
    assert last["index_keys_scored"] == layers * nkv * (7 + 1) * 8 * 512
    assert last["index_keys"] == layers * nkv * (sum((p - 3) // 2 + 1 for p in range(48, 100)) + (70 - 3) // 2 + 1)
    assert 0 < last["index_keys"] <= last["index_keys_scored"] <= layers * nkv * rectangle
    # a decode horizon is a program of one-token tiles: the XLA form's rectangle a step
    assert horizon["index_keys_scored"] == 4 * layers * nkv * (4 + 4 + 1) * 256 * 4 >= horizon["index_keys"] > 0


def test_the_program_with_the_kernel_selects_and_says_what_the_xla_program_does(engine):
    """The 64-token program with the indexer's kernel against the same
    program with the XLA form (the same engine configuration without the
    interpreter): a prompt that crosses ``dense_len``, the selection of the
    probed tokens and the logits."""
    cfg, eng = engine
    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=64, max_ragged_sequence_count=4,
                              max_context=2048, token_buckets=(64, ), seq_buckets=(4, ))
    icfg = RaggedInferenceEngineConfig(kv_block_size=BLOCK, num_kv_blocks=300, kv_dtype=jnp.float32, state_manager=sm)
    plain = InferenceEngineV2(TransformerLM(cfg), icfg, params=eng.params)
    ids = np.random.default_rng(2).integers(0, cfg.vocab_size, size=150, dtype=np.int32)
    outs = []
    for e in (eng, plain):
        e.put([41], [ids[:60]], sample="probe")
        e.put([42], [ids[:30]], sample="probe")
        logits, probes = e.put([41, 42], [ids[60:120], ids[30:31]], sample="probe")
        e.flush(41), e.flush(42)
        outs.append((np.asarray(logits), [np.asarray(x) for layer in probes for x in jax.tree_util.tree_leaves(layer)]))
    (logits_k, probes_k), (logits_x, probes_x) = outs
    assert np.linalg.norm(logits_k - logits_x) / np.linalg.norm(logits_x) < 1e-4
    picked = [(a, b) for a, b in zip(probes_k, probes_x) if a.dtype == bool]
    assert picked and all(a.shape == b.shape and (a == b).mean() > 0.98 for a, b in picked)
