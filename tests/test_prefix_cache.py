"""Prefix-cache subsystem: refcounted COW KV blocks + radix-tree reuse.

The one invariant threaded through allocator, tree, state manager, scheduler
and engine: a block's contents are IMMUTABLE while shared. These tests pin
it from below (allocator refcount semantics, loud double-free), from the
middle (radix match/insert/evict unit behavior, COW on partial-tail hits),
and from above (bit-identical greedy output with the cache on vs off,
including a shared prefix ending MID-BLOCK — the copy-on-write path), plus
the ``tools/check_kv_blocks.py`` structural gate that keeps raw ``.free``
calls out of the serving plane.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, PrefixCacheConfig,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.ragged import BlockedAllocator, BlockedKVCache, PrefixKVCache
from deepspeed_tpu.models import llama2


# ---------------------------------------------------------------------------
# allocator: refcounts + the loud double-free fix (ISSUE 3 satellite)
# ---------------------------------------------------------------------------

def test_allocator_double_free_raises():
    """Regression: freeing a block twice used to silently relink it at the
    free-list head and over-count ``_free`` — two later allocations would
    receive the SAME block. Now it raises loudly."""
    a = BlockedAllocator(8)
    blocks = a.allocate(3)
    a.free(blocks[0])
    with pytest.raises(ValueError, match="double free"):
        a.free(blocks[0])
    # a never-allocated id is the same corruption with a different spelling
    with pytest.raises(ValueError, match="double free"):
        a.free(7)
    # out-of-range stays its own error
    with pytest.raises(ValueError, match="invalid block id"):
        a.free(99)
    # the pool is NOT corrupted: exactly 6 blocks allocatable, all distinct
    rest = a.allocate(a.free_blocks)
    assert a.free_blocks == 0
    held = list(map(int, rest)) + [int(blocks[1]), int(blocks[2])]
    assert sorted(held) == list(range(8))


def test_allocator_refcount_sharing():
    a = BlockedAllocator(4)
    (b, ) = a.allocate(1)
    assert a.refcount(b) == 1
    a.incref(b)
    a.incref(b)
    assert a.refcount(b) == 3
    a.release(b)
    a.release(b)
    assert a.free_blocks == 3  # still held by one owner
    a.release(b)
    assert a.refcount(b) == 0 and a.free_blocks == 4
    with pytest.raises(ValueError, match="incref on free block"):
        a.incref(b)


# ---------------------------------------------------------------------------
# radix tree unit behavior (host-only: a tiny real BlockedKVCache backs it)
# ---------------------------------------------------------------------------

def _tiny_pool(num_blocks=8, block_size=4):
    return BlockedKVCache(num_layers=1, num_kv_heads=1, head_dim=2,
                          num_blocks=num_blocks, block_size=block_size,
                          dtype=jnp.float32)


class _Seq:
    """Minimal stand-in for DSSequenceDescriptor's publish surface."""

    def __init__(self, tokens, blocks, seen=None):
        self.token_history = list(tokens)
        self.kv_blocks = list(blocks)
        self.seen_tokens = len(tokens) if seen is None else seen
        self.history_valid = True


def test_radix_match_insert_and_cap():
    kv = _tiny_pool()
    pc = PrefixKVCache(kv)
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    blocks = kv.reserve(2)
    pc.publish(_Seq(toks, blocks))
    assert pc.n_cached_blocks == 2
    assert kv.refcount(blocks[0]) == 2 and kv.refcount(blocks[1]) == 2  # owner + tree

    # full-block hit on a longer prompt: 2 shared blocks, suffix uncached
    m = pc.match([1, 2, 3, 4, 5, 6, 7, 8, 9, 9, 9])
    assert m.n_cached_tokens == 8 and list(m.shared_blocks) == [int(b) for b in blocks]
    assert m.cow_src is None

    # the cap: an IDENTICAL prompt may not reuse everything — the last token
    # must be computed, so the final block comes back as a COW source
    m = pc.match(toks)
    assert m.n_cached_tokens == 7
    assert list(m.shared_blocks) == [int(blocks[0])]
    assert m.cow_src == int(blocks[1]) and m.cow_tokens == 3

    # mid-block divergence: shares 4 + 2 tokens -> 1 full block + COW tail
    m = pc.match([1, 2, 3, 4, 5, 6, 99, 98])
    assert m.shared_blocks == [int(blocks[0])]
    assert m.cow_src == int(blocks[1]) and m.cow_tokens == 2
    assert m.n_cached_tokens == 6

    # min_hit_blocks filters small hits entirely
    pc2 = PrefixKVCache(kv, min_hit_blocks=2)
    assert pc2.match([1, 2, 3, 4, 9]).n_cached_tokens == 0


def test_match_purity_no_mutation():
    """ISSUE 6 satellite: the serving gateway's router scores EVERY replica's
    tree with ``match`` per request — the contract that makes that free is
    that ``match`` is a pure read. Pin it: tree topology (chunks, blocks,
    parent links), per-node LRU stamps, the LRU clock, allocator refcounts,
    the free count and the stats dict are bit-identical before/after any mix
    of full-hit / partial-tail / miss / degenerate matches."""
    kv = _tiny_pool(num_blocks=8, block_size=4)
    pc = PrefixKVCache(kv)
    a = kv.reserve(2)
    pc.publish(_Seq([1, 2, 3, 4, 5, 6, 7, 8], a))
    held, _, _ = pc.acquire([1, 2, 3, 4, 9, 9, 9])  # COW holder + LRU touches

    def snapshot():
        nodes = []
        stack = [((), pc._root)]
        while stack:
            path, node = stack.pop()
            for chunk, child in sorted(node.children.items()):
                nodes.append((path + chunk, child.block, child.last_access))
                stack.append((path + chunk, child))
        return {
            "nodes": sorted(nodes),
            "clock": pc._clock,
            "free": kv.free_blocks,
            "refcounts": [kv.refcount(b) for b in pc.cached_block_ids()]
                         + [kv.refcount(b) for b in held],
            "stats": dict(pc.stats),
        }

    before = snapshot()
    for probe in ([1, 2, 3, 4, 5, 6, 7, 8],          # capped exact hit
                  [1, 2, 3, 4, 5, 6, 7, 8, 9, 9],    # full-block hit + suffix
                  [1, 2, 3, 4, 5, 6, 99, 98],        # mid-block COW candidate
                  [7, 7, 7, 7, 7],                   # clean miss
                  [1], []):                          # degenerate prompts
        pc.match(np.asarray(probe, np.int32))
    assert snapshot() == before, "match() mutated tree/LRU/refcount/stats state"


def test_radix_acquire_cow_and_release():
    kv = _tiny_pool()
    pc = PrefixKVCache(kv)
    owner = kv.reserve(2)
    pc.publish(_Seq([1, 2, 3, 4, 5, 6, 7, 8], owner))
    free0 = kv.free_blocks
    blocks, n_cached, shared = pc.acquire([1, 2, 3, 4, 5, 6, 99, 98])
    assert n_cached == 6 and shared == 1
    assert blocks[0] == int(owner[0]) and blocks[1] != int(owner[1])  # COW copy
    assert kv.free_blocks == free0 - 1
    assert kv.refcount(owner[0]) == 3  # owner + tree + new holder
    assert kv.refcount(owner[1]) == 2  # COW source untouched
    assert kv.refcount(blocks[1]) == 1  # private copy
    assert pc.stats["cow_copies"] == 1 and pc.stats["hits"] == 1
    kv.release(blocks)  # the new holder goes away
    assert kv.free_blocks == free0 and kv.refcount(owner[0]) == 2


def test_radix_lru_eviction_and_clear():
    kv = _tiny_pool(num_blocks=8, block_size=4)
    pc = PrefixKVCache(kv)
    a = kv.reserve(2)
    b = kv.reserve(2)
    pc.publish(_Seq([1, 2, 3, 4, 5, 6, 7, 8], a))
    pc.publish(_Seq([9, 9, 9, 9, 8, 8, 8, 8], b))
    # sequences are gone; only the tree holds everything
    kv.release(a)
    kv.release(b)
    assert kv.free_blocks == 4 and pc.evictable_blocks == 4
    # touch chain A (a live holder pins it) so chain B is LRU: B's LEAF goes
    held = pc.acquire([1, 2, 3, 4, 5, 6, 7, 8, 7])[0]
    assert pc.evict(1) == 1
    assert int(b[1]) not in pc.cached_block_ids()
    assert int(b[0]) in pc.cached_block_ids()
    # eviction never touches blocks a live holder shares (refcount > 1)
    freed = pc.evict(10)
    assert int(a[0]) in pc.cached_block_ids() and int(a[1]) in pc.cached_block_ids()
    assert freed == 1  # only b[0] was tree-only
    # clear() drops every tree reference: the pool returns to pristine once
    # the remaining acquire-holder releases too
    pc.clear()
    assert pc.n_cached_blocks == 0
    kv.release(held)
    assert kv.free_blocks == 8


def test_copy_block_moves_kv_and_int8_scales():
    """The COW primitive, both layouts: bf16/fp32 pools copy the flat-slot
    span; int8 additionally moves the per-layer strided scale slots."""
    for quantized in (False, True):
        kv = BlockedKVCache(num_layers=2, num_kv_heads=2, head_dim=2, num_blocks=4,
                            block_size=4, dtype=(jnp.int8 if quantized else jnp.float32))
        shape = kv.k_pool.shape
        rng = np.random.default_rng(0)
        kv.k_pool = jnp.asarray(rng.integers(-100, 100, size=shape), kv.dtype)
        kv.v_pool = jnp.asarray(rng.integers(-100, 100, size=shape), kv.dtype)
        if quantized:
            kv.k_scale = jnp.asarray(rng.random(kv.k_scale.shape), jnp.float32)
            kv.v_scale = jnp.asarray(rng.random(kv.v_scale.shape), jnp.float32)
        before_k = np.asarray(kv.k_pool).copy()
        kv.copy_block(1, 3)
        after_k = np.asarray(kv.k_pool)
        np.testing.assert_array_equal(after_k[:, 12:16], before_k[:, 4:8])
        np.testing.assert_array_equal(after_k[:, :12], before_k[:, :12])  # others untouched
        if quantized:
            ks = np.asarray(kv.k_scale).reshape(2, 2, 16)  # [nkv, L, NB*bs]
            np.testing.assert_array_equal(ks[:, :, 12:16], ks[:, :, 4:8])


# ---------------------------------------------------------------------------
# engine-level parity: cache on vs off is bit-identical (greedy), COW incl.
# ---------------------------------------------------------------------------

def _engine(model, params, cache_on, num_kv_blocks=64):
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                              max_ragged_sequence_count=8, max_context=64)
    icfg = RaggedInferenceEngineConfig(
        kv_block_size=8, num_kv_blocks=num_kv_blocks, kv_dtype=jnp.float32,
        state_manager=sm, use_pallas_kernels="never",
        prefix_cache=PrefixCacheConfig(enabled=cache_on))
    return InferenceEngineV2(model, icfg, params=params)


@pytest.fixture(scope="module")
def tiny_model():
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=256,
                   dtype=jnp.float32, attention_impl="reference")
    params = jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(0))
    return model, params


def test_greedy_parity_cache_on_off_with_midblock_cow(tiny_model):
    """IDENTICAL request stream, prefix_cache on vs off → bit-identical
    greedy token ids. The stream includes (a) whole-block shared prefixes,
    (b) a shared prefix ending MID-BLOCK (COW tail), and (c) an exact
    repeat of a full prompt (the cap forces a COW on the final block)."""
    model, params = tiny_model
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, 128, size=24, dtype=np.int32)  # 3 full 8-blocks
    reqs = []
    for i in range(3):  # (a) shared prefix + unique suffixes
        suf = rng.integers(0, 128, size=int(rng.integers(4, 10)), dtype=np.int32)
        reqs.append((i, np.concatenate([prefix, suf])))
    # (b) diverges 4 tokens INTO block 2 (shared prefix ends mid-block)
    reqs.append((10, np.concatenate([prefix[:20],
                                     rng.integers(0, 128, size=7, dtype=np.int32)])))
    # (c) exact repeat of request 0's prompt
    reqs.append((11, reqs[0][1].copy()))

    outs = {}
    for cache_on in (False, True):
        eng = _engine(model, params, cache_on)
        sched = DynamicSplitFuseScheduler(eng, token_budget=32)
        for uid, p in reqs:
            sched.submit(uid, p, max_new_tokens=6)
        outs[cache_on] = sched.run()
        if cache_on:
            pc = eng.prefix_cache
            assert pc.stats["hits"] >= 2
            assert pc.stats["cow_copies"] >= 1, "mid-block case must exercise COW"
            assert sched.stats["prefill_tokens_skipped"] >= 16
            assert eng.state_manager.n_tracked_sequences == 0
    assert outs[True] == outs[False], "prefix cache changed the computation"


def test_put_level_hit_trims_chunk_and_preseeds_seen(tiny_model):
    """Direct engine.put path (no scheduler): a new sequence whose first
    chunk hits the tree starts prefill AFTER the hit — seen_tokens
    pre-seeded, shared blocks in the table, logits identical to cold."""
    model, params = tiny_model
    eng = _engine(model, params, cache_on=True)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 128, size=20, dtype=np.int32)
    cold = np.asarray(eng.put([1], [prompt]))
    eng.flush(1)
    warm = np.asarray(eng.put([2], [prompt]))  # identical prompt: radix hit
    seq = eng.query(2)
    assert seq.prefix_cached_tokens > 0 and seq.shared_blocks >= 1
    assert seq.seen_tokens == prompt.size  # post-forward: whole prompt seen
    # the contract is greedy parity; logits agree to numerical noise
    assert np.argmax(cold, -1).tolist() == np.argmax(warm, -1).tolist()
    np.testing.assert_allclose(cold, warm, rtol=1e-5, atol=1e-5)
    stats = eng.query()["prefix_cache"]
    assert stats["hits"] == 1 and stats["hit_rate"] == 0.5
    eng.flush(2)
    # every surviving block is tree-held: free + tree == total
    assert (eng.free_blocks + eng.prefix_cache.n_cached_blocks
            == eng.state_manager.kv_cache.total_blocks)


def test_prefix_metrics_and_trace_span(tiny_model, tmp_path):
    """The monitor sees the subsystem: hit-rate gauge, cached-token
    counters, and a ``prefix_hit`` trace event."""
    from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics
    from deepspeed_tpu.monitor.trace import configure_tracer, get_tracer

    model, params = tiny_model
    configure_metrics(enabled=True)
    get_metrics().reset()
    trace_file = str(tmp_path / "trace.jsonl")
    configure_tracer(enabled=True, path=trace_file)
    try:
        eng = _engine(model, params, cache_on=True)
        prompt = np.arange(20, dtype=np.int32) % 128
        eng.put([1], [prompt])
        eng.put([2], [prompt])
        snap = get_metrics().snapshot()
        assert snap["counters"]["serving/prefix_lookups"] == 2
        assert snap["counters"]["serving/prefix_hits"] == 1
        assert snap["counters"]["serving/prefix_cached_tokens"] > 0
        assert snap["gauges"]["serving/prefix_hit_rate"] == 0.5
        get_tracer().flush()
        with open(trace_file) as f:
            assert any('"prefix_hit"' in line for line in f)
    finally:
        configure_metrics(enabled=False)
        get_tracer().reset()


def test_publish_race_keeps_evictable_exact(tiny_model):
    """Two same-prefix requests prefill in ONE batch: both cold-miss (the
    tree fills only after the forward), so they publish racing chains.
    The loser must stop at the divergence instead of inserting its deeper
    blocks under the winner's path — otherwise, once the winner flushes,
    an interior tree-only node is pinned by the loser's live child and
    ``evictable_blocks`` would promise blocks leaf eviction can't free."""
    model, params = tiny_model
    eng = _engine(model, params, cache_on=True)
    rng = np.random.default_rng(2)
    shared = rng.integers(0, 128, size=16, dtype=np.int32)  # 2 full 8-blocks
    pA = np.concatenate([shared, rng.integers(0, 128, size=9, dtype=np.int32)])
    pB = np.concatenate([shared, rng.integers(0, 128, size=9, dtype=np.int32)])
    eng.put([1, 2], [pA, pB], sample="greedy")
    eng.flush(1)  # winner gone; loser (uid 2) still live
    pc = eng.prefix_cache
    claimed = pc.evictable_blocks
    assert claimed > 0
    assert pc.evict(claimed + 5) == claimed, \
        "evictable_blocks promised blocks leaf eviction could not free"
    eng.flush(2)


def test_warm_cache_admission_never_overcommits(tiny_model):
    """Admission must not credit a hit's tree-only shared blocks on BOTH
    sides of the budget (subtracted from demand while still counted
    evictable in supply): with an 8-block pool whose free list is half
    tree-held, a filler plus a repeat of the cached prompt used to be
    co-admitted and then crash mid-run with KVCacheLimitExceeded — the
    exact steady state a warm cache runs in. The corrected check defers
    the repeat until capacity is real; outputs match cache-off."""
    model, params = tiny_model
    rng = np.random.default_rng(21)
    pA = rng.integers(0, 128, size=32, dtype=np.int32)
    filler = rng.integers(0, 128, size=28, dtype=np.int32)
    outs = {}
    for cache_on in (False, True):
        eng = _engine(model, params, cache_on, num_kv_blocks=8)
        s1 = DynamicSplitFuseScheduler(eng, token_budget=64)
        s1.submit(1, pA, max_new_tokens=4)
        s1.run()  # cache-on: leaves A's 4-block chain tree-only
        s2 = DynamicSplitFuseScheduler(eng, token_budget=64)
        s2.submit(2, filler, max_new_tokens=4)
        s2.submit(3, pA.copy(), max_new_tokens=8)
        outs[cache_on] = s2.run()  # must not raise
    assert outs[True] == outs[False]


def test_eviction_under_pressure_keeps_parity(tiny_model):
    """A pool too small to hold the tree + live sequences: allocation evicts
    LRU leaves instead of failing, and outputs still match cache-off."""
    model, params = tiny_model
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 128, size=int(rng.integers(20, 31)), dtype=np.int32)
               for _ in range(8)]
    outs = {}
    for cache_on in (False, True):
        eng = _engine(model, params, cache_on, num_kv_blocks=16)
        outs[cache_on] = {}
        for i, p in enumerate(prompts):
            tok = int(np.asarray(eng.put([i], [p], sample="greedy")).reshape(-1)[0])
            outs[cache_on][i] = tok
            eng.flush(i)
        if cache_on:
            assert eng.prefix_cache.stats["evictions"] > 0, \
                "pool sized to force eviction; none happened"
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# structural gate: no raw .free outside the allocator/cache modules
# ---------------------------------------------------------------------------

def test_check_kv_blocks_gate():
    from tools.check_kv_blocks import check

    assert check() == []


def test_check_kv_blocks_catches_drift(tmp_path):
    from tools.check_kv_blocks import check

    v2 = tmp_path / "v2"
    (v2 / "ragged").mkdir(parents=True)
    (v2 / "ragged" / "kv_cache.py").write_text("def f(a):\n    a.free(1)\n")  # allowlisted
    (v2 / "rogue.py").write_text("def g(alloc):\n    alloc.free([1, 2])\n")
    bad = check(str(v2))
    assert len(bad) == 1 and bad[0][0] == "rogue.py" and bad[0][1] == 2
