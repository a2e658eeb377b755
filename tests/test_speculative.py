"""Speculative decoding subsystem: drafting, batched K-token verification,
and refcount-aware KV rollback.

The load-bearing invariant: greedy parity is UNCONDITIONAL — a draft token
is committed only when it equals the target model's own argmax at that
position, so spec-on streams are bit-identical to spec-off for ANY drafter
(oracle, junk, n-gram), with the prefix cache on or off. These tests pin it
from below (``DSStateManager.rollback_to`` truncation/release/COW-guard
semantics), from the middle (the n-gram drafter, engine-level verify with
oracle and adversarial drafts), and from above (scheduler-driven parity for
both drafters, refcount churn under accept/reject storms), plus the
``tools/check_spec_rollback.py`` structural gate and the decode-horizon
overshoot bugfix (early-eos garbage must never enter the radix tree).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, PrefixCacheConfig,
                                        RaggedInferenceEngineConfig, SpeculativeConfig)
from deepspeed_tpu.inference.v2.ragged.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.speculative import (DraftModelDrafter, Drafter,
                                                    NgramDrafter, build_drafter)
from deepspeed_tpu.models import llama2


# ---------------------------------------------------------------------------
# rollback_to: the single rewind primitive
# ---------------------------------------------------------------------------

class _PCConfig:
    enabled = True
    min_hit_blocks = 1
    eviction = "lru"


def _mini_sm(num_blocks=16, bs=4, cache=True):
    return DSStateManager(1, 1, 2, max_tracked_sequences=4, num_blocks=num_blocks,
                          block_size=bs, dtype=jnp.float32,
                          prefix_cache_config=_PCConfig() if cache else None)


def _materialize(sm, seq, tokens):
    """Simulate one forward's host bookkeeping for ``tokens``."""
    tokens = np.asarray(tokens, np.int32)
    sm.note_tokens(seq, tokens)
    sm.allocate_blocks(seq, tokens.size)
    seq.pre_forward(tokens.size)
    seq.post_forward()


def test_rollback_to_truncates_and_releases_tail():
    sm = _mini_sm()
    total = sm.free_blocks
    seq, _ = sm.create_sequence_with_prefix(1, None)
    _materialize(sm, seq, np.arange(10))  # 3 blocks of 4
    assert sm.free_blocks == total - 3 and seq.seen_tokens == 10
    released = sm.rollback_to(seq, 5)
    assert released == 1 and sm.free_blocks == total - 2
    assert seq.seen_tokens == 5 and seq.token_history == [0, 1, 2, 3, 4]
    assert len(seq.kv_blocks) == 2
    # idempotent / forward guards
    with pytest.raises(ValueError, match="rollback_to"):
        sm.rollback_to(seq, 6)  # cannot rewind forward
    seq.pre_forward(2)
    with pytest.raises(RuntimeError, match="in flight"):
        sm.rollback_to(seq, 3)
    seq.post_forward()
    # full rewind returns everything
    sm.rollback_to(seq, 0)
    assert sm.free_blocks == total and seq.kv_blocks == [] and seq.token_history == []
    sm.flush_sequence(1)
    assert sm.free_blocks == total


def test_rollback_to_cow_guard_on_shared_partial_tail():
    """Rewinding INTO a block the radix tree holds must copy-on-write it:
    the sequence's next tokens scatter into the tail slots, and writing a
    shared block would corrupt the tree's (and any other holder's) view."""
    sm = _mini_sm()
    pc = sm.prefix_cache
    seq, _ = sm.create_sequence_with_prefix(1, None)
    _materialize(sm, seq, np.arange(8))  # 2 full blocks
    sm.publish_sequence(seq)
    b0, b1 = seq.kv_blocks
    assert sm.kv_cache.refcount(b1) == 2  # seq + tree
    sm.rollback_to(seq, 6)  # mid-block rewind into the published block
    assert seq.seen_tokens == 6 and seq.token_history == [0, 1, 2, 3, 4, 5]
    assert seq.kv_blocks[0] == b0 and seq.kv_blocks[1] != b1, \
        "shared partial tail must be COW-duplicated"
    assert sm.kv_cache.refcount(b1) == 1          # tree keeps its copy
    assert sm.kv_cache.refcount(seq.kv_blocks[1]) == 1  # private duplicate
    assert seq.published_blocks == 1  # publish cursor rewound with the rewind
    # the tree's chain is intact and still matches the original tokens
    assert pc.match(np.arange(9, dtype=np.int32)).n_cached_tokens == 8
    sm.flush_sequence(1)
    pc.clear()
    assert sm.free_blocks == sm.kv_cache.total_blocks


def test_rollback_on_boundary_skips_cow():
    sm = _mini_sm()
    seq, _ = sm.create_sequence_with_prefix(1, None)
    _materialize(sm, seq, np.arange(8))
    sm.publish_sequence(seq)
    blocks = list(seq.kv_blocks)
    sm.rollback_to(seq, 4)  # block-aligned: drop block 1's ref, keep block 0 as-is
    assert seq.kv_blocks == blocks[:1]
    assert sm.kv_cache.refcount(blocks[1]) == 1  # tree only — survives
    sm.flush_sequence(1)
    sm.prefix_cache.clear()
    assert sm.free_blocks == sm.kv_cache.total_blocks


# ---------------------------------------------------------------------------
# n-gram drafter unit behavior
# ---------------------------------------------------------------------------

def test_ngram_drafter_proposes_continuation():
    d = NgramDrafter(min_match=2, max_ngram=3)
    # suffix [7, 8] occurred earlier, followed by [9, 1, 2, ...]
    ctx = np.asarray([5, 6, 7, 8, 9, 1, 2, 3, 7, 8], np.int32)
    np.testing.assert_array_equal(d.draft(0, ctx, 4), [9, 1, 2, 3])
    # most RECENT earlier occurrence wins
    ctx = np.asarray([1, 2, 50, 9, 9, 1, 2, 60, 9, 1, 2], np.int32)
    assert d.draft(0, ctx, 2).tolist() == [60, 9]
    # no repeat -> no draft; short context -> no draft
    assert d.draft(0, np.asarray([1, 2, 3, 4, 5], np.int32), 4).size == 0
    assert d.draft(0, np.asarray([1], np.int32), 4).size == 0
    # min_match filters unigram coincidences out
    assert d.draft(0, np.asarray([1, 5, 2, 9, 2], np.int32), 4).size == 0
    assert NgramDrafter(min_match=1).draft(
        0, np.asarray([1, 5, 2, 9, 2], np.int32), 4).tolist() == [9, 2]
    with pytest.raises(ValueError, match="max_ngram"):
        NgramDrafter(min_match=3, max_ngram=2)


def test_build_drafter_modes():
    assert isinstance(build_drafter(SpeculativeConfig(mode="ngram")), NgramDrafter)
    with pytest.raises(ValueError, match="draft_engine"):
        build_drafter(SpeculativeConfig(mode="draft_model"))
    with pytest.raises(ValueError, match="unknown speculative mode"):
        build_drafter(SpeculativeConfig(mode="banana"))
    assert not SpeculativeConfig().enabled and SpeculativeConfig(mode="ngram").enabled


# ---------------------------------------------------------------------------
# engine-level verify: oracle accepts everything, junk accepts nothing —
# both bit-identical to sequential greedy
# ---------------------------------------------------------------------------

def _engine(model, params, cache_on=False, spec=None, num_kv_blocks=64, max_context=64):
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                              max_ragged_sequence_count=8, max_context=max_context, token_buckets=(64, ), seq_buckets=(8, ))
    icfg = RaggedInferenceEngineConfig(
        kv_block_size=8, num_kv_blocks=num_kv_blocks, kv_dtype=jnp.float32,
        state_manager=sm, use_pallas_kernels="never",
        prefix_cache=PrefixCacheConfig(enabled=cache_on))
    if spec is not None:
        icfg.speculative = spec
    return InferenceEngineV2(model, icfg, params=params)


@pytest.fixture(scope="module")
def tiny_model():
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=256,
                   dtype=jnp.float32, attention_impl="reference")
    params = jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(0))
    return model, params


def _greedy_reference(model, params, prompt, n, cache_on=False):
    """Sequential (non-speculative) greedy stream: the parity baseline."""
    eng = _engine(model, params, cache_on=cache_on)
    out = [int(np.asarray(eng.put([1], [prompt], sample="greedy")).reshape(-1)[0])]
    while len(out) < n:
        row = np.asarray(eng.decode([1], [np.asarray([out[-1]], np.int32)], 1))
        out.append(int(row[0, 0]))
    eng.flush(1)
    return out


def test_speculate_decode_oracle_and_junk_parity(tiny_model):
    model, params = tiny_model
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, size=20, dtype=np.int32)
    ref = _greedy_reference(model, params, prompt, 11)
    k = 4
    for kind in ("oracle", "junk"):
        eng = _engine(model, params, cache_on=(kind == "oracle"))
        got = [int(np.asarray(eng.put([5], [prompt], sample="greedy")).reshape(-1)[0])]
        rounds = 0
        while len(got) < 11:
            oracle = np.asarray(ref[len(got):len(got) + k], np.int32)
            drafts = oracle if kind == "oracle" else (oracle + 1) % 128
            outs = eng.speculate_decode([5], [np.asarray([got[-1]], np.int32)],
                                        [drafts], k)
            assert 1 <= len(outs[0]) <= k + 1
            got.extend(int(t) for t in outs[0])
            rounds += 1
        assert got[:11] == ref, f"{kind} drafts broke greedy parity"
        if kind == "oracle":
            assert rounds <= -(-10 // k) + 1, "oracle drafts must commit k+1/round"
            assert eng._spec_totals["accepted"] == eng._spec_totals["drafted"]
        else:
            assert rounds == 10, "junk drafts must degrade to 1 token/round"
            assert eng._spec_totals["accepted"] == 0
        # KV accounting survived the storms: seen matches the committed
        # stream (last token still pending), pool clean after flush
        assert eng.query(5).seen_tokens == prompt.size + len(got) - 1
        eng.flush(5)
        sm = eng.state_manager
        tree = eng.prefix_cache.n_cached_blocks if eng.prefix_cache else 0
        assert sm.free_blocks + tree == sm.kv_cache.total_blocks


def test_speculate_short_and_empty_drafts_pad_safely(tiny_model):
    """Drafts shorter than k pad by repeating; a pad is accepted only when
    it coincidentally IS the greedy token — parity either way."""
    model, params = tiny_model
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 128, size=16, dtype=np.int32)
    ref = _greedy_reference(model, params, prompt, 6)
    eng = _engine(model, params)
    got = [int(np.asarray(eng.put([3], [prompt], sample="greedy")).reshape(-1)[0])]
    # one real (true) draft token, padded out to k=3
    outs = eng.speculate_decode([3], [np.asarray([got[0]], np.int32)],
                                [np.asarray(ref[1:2], np.int32)], 3)
    got.extend(int(t) for t in outs[0])
    while len(got) < 6:
        outs = eng.speculate_decode([3], [np.asarray([got[-1]], np.int32)],
                                    [np.empty(0, np.int32)], 2)
        got.extend(int(t) for t in outs[0])
    assert got[:6] == ref
    eng.flush(3)


# ---------------------------------------------------------------------------
# scheduler-level parity: both drafters, cache on AND off
# ---------------------------------------------------------------------------

def _run_sched(eng, reqs, max_new=18, drafter=None, eos=None):
    sched = DynamicSplitFuseScheduler(eng, token_budget=32, drafter=drafter)
    for uid, p in reqs:
        sched.submit(uid, p, max_new_tokens=max_new, eos_token_id=eos)
    out = sched.run()
    return out, sched


def test_greedy_parity_ngram_spec_cache_matrix(tiny_model):
    """IDENTICAL request stream across {spec on/off} x {prefix cache
    on/off} → bit-identical greedy streams. Prompts carry repeated motifs
    so the n-gram drafter actually fires (drafted > 0 asserted)."""
    model, params = tiny_model
    rng = np.random.default_rng(7)
    motif = rng.integers(0, 128, size=6, dtype=np.int32)
    reqs = []
    for i in range(4):
        suf = rng.integers(0, 128, size=int(rng.integers(3, 7)), dtype=np.int32)
        # shared repeated motif: radix hits AND n-gram matches
        reqs.append((i, np.concatenate([motif, motif, suf])))
    outs = {}
    for cache_on in (False, True):
        for spec_on in (False, True):
            spec = SpeculativeConfig(mode="ngram", k=3, min_match=1) if spec_on else None
            eng = _engine(model, params, cache_on=cache_on, spec=spec)
            outs[(cache_on, spec_on)], sched = _run_sched(eng, reqs)
            if spec_on:
                assert sched.speculating and sched.spec_stats["drafted"] > 0
                assert sched.spec_stats["rounds"] > 0
            assert eng.state_manager.n_tracked_sequences == 0
    assert outs[(False, True)] == outs[(False, False)], "spec changed the stream (cache off)"
    assert outs[(True, True)] == outs[(True, False)], "spec changed the stream (cache on)"
    assert outs[(True, False)] == outs[(False, False)], "cache changed the stream"


def test_greedy_parity_draft_model_oracle_and_weak(tiny_model):
    """Draft-model path: a same-params draft engine accepts ~everything, a
    different-params one accepts ~nothing — parity both ways, and the
    oracle arm proves accept_rate > 0 end-to-end."""
    model, params = tiny_model
    weak_params = jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(9))
    rng = np.random.default_rng(11)
    reqs = [(i, rng.integers(0, 128, size=int(rng.integers(10, 20)), dtype=np.int32))
            for i in range(3)]
    eng = _engine(model, params, cache_on=True)
    baseline, _ = _run_sched(eng, reqs)
    for draft_params, expect_accepts in ((params, True), (weak_params, False)):
        deng = _engine(model, draft_params, cache_on=False)
        spec = SpeculativeConfig(mode="draft_model", k=3, draft_engine=deng)
        eng2 = _engine(model, params, cache_on=True, spec=spec)
        got, sched = _run_sched(eng2, reqs)
        assert got == baseline, "draft-model speculation broke greedy parity"
        assert sched.spec_stats["drafted"] > 0
        if expect_accepts:
            assert sched.spec_stats["accepted"] > 0
            rate = sched.spec_stats["accepted"] / sched.spec_stats["drafted"]
            assert rate > 0.9, f"same-params draft model should accept ~all, got {rate}"
        # per-request summary rides into the gateway's request record
        uid = reqs[0][0]
        summary = sched.spec_summary(uid)
        assert summary is not None and summary["drafted"] > 0
        sched.discard_result(uid)
        assert sched.spec_summary(uid) is None
        # the drafter's mirror sequences were flushed at finish
        assert deng.state_manager.n_tracked_sequences == 0


# ---------------------------------------------------------------------------
# churn invariant: refcount == live holders through accept/reject storms
# ---------------------------------------------------------------------------

class _JunkDrafter(Drafter):
    name = "junk"

    def __init__(self, seed=0):
        self.rng = np.random.default_rng(seed)

    def draft(self, uid, context, k):
        return self.rng.integers(0, 128, size=k).astype(np.int32)


class _AlternatingDrafter(Drafter):
    """Oracle rounds (accept ~all) interleaved with junk rounds (reject
    ~all): the accept/reject storm the churn invariant must survive."""

    name = "alternating"

    def __init__(self, oracle, junk):
        self.oracle, self.junk, self.n = oracle, junk, 0

    def draft_many(self, items, k):
        self.n += 1
        return (self.oracle if self.n % 2 else self.junk).draft_many(items, k)

    def finish(self, uid):
        self.oracle.finish(uid)
        self.junk.finish(uid)


def test_spec_churn_refcount_equals_live_holders(tiny_model):
    """Accept/reject storms over a prefix-cache-enabled engine: after EVERY
    scheduler step, each block's refcount equals its live holder count
    (sequences carrying it + the radix tree), and the pool returns to
    pristine after flush + eviction flush."""
    model, params = tiny_model
    deng = _engine(model, params, cache_on=False)  # oracle: same params
    drafter = _AlternatingDrafter(DraftModelDrafter(deng), _JunkDrafter())
    eng = _engine(model, params, cache_on=True, num_kv_blocks=56)
    sched = DynamicSplitFuseScheduler(
        eng, token_budget=48, speculative=SpeculativeConfig(mode="ngram", k=3),
        drafter=drafter)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 128, size=16, dtype=np.int32)
    for i in range(5):
        suf = rng.integers(0, 128, size=int(rng.integers(4, 10)), dtype=np.int32)
        sched.submit(i, np.concatenate([shared, suf]), max_new_tokens=int(rng.integers(8, 16)))
    alloc = eng.state_manager.kv_cache._allocator
    total = eng.state_manager.kv_cache.total_blocks
    pc = eng.prefix_cache
    steps = 0
    while sched.has_work:
        assert sched.step() > 0
        steps += 1
        holders = {}
        for uid in list(sched._active):
            for b in eng.query(uid).kv_blocks:
                holders[b] = holders.get(b, 0) + 1
        for b in pc.cached_block_ids():
            holders[b] = holders.get(b, 0) + 1
        for b in range(total):
            assert alloc.refcount(b) == holders.get(b, 0), \
                (f"step {steps}: block {b} refcount {alloc.refcount(b)} != "
                 f"{holders.get(b, 0)} live holders")
        assert steps < 500
    assert sched.spec_stats["accepted"] > 0, "oracle rounds must accept"
    assert sched.spec_stats["rejected"] > 0, "junk rounds must reject"
    pc.clear()
    assert eng.state_manager.free_blocks == total
    assert deng.state_manager.n_tracked_sequences == 0


# ---------------------------------------------------------------------------
# bugfix: decode-horizon overshoot at early finish never pollutes the tree
# ---------------------------------------------------------------------------

def test_early_eos_overshoot_never_enters_radix_tree(tiny_model):
    """``decode()`` reserves and materializes KV for the whole horizon; a
    request hitting eos mid-burst used to carry the post-eos garbage into
    ``token_history``, and flush would PUBLISH those full blocks into the
    radix tree — blocks keyed on junk token paths, pinned until LRU
    pressure. The fix rewinds the overshoot through ``rollback_to`` (in
    the engine at the eos site, and at scheduler finish/cancel) BEFORE any
    publish: the tree holds exactly the real-token chain and the tail
    returns to the free list immediately."""
    model, params = tiny_model
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 128, size=20, dtype=np.int32)
    ref = _greedy_reference(model, params, prompt, 12)
    # an eos that first appears at generation index >= 2 (inside the burst)
    eos_idx = next(i for i in range(2, 12) if ref[i] not in ref[:i])
    eos = ref[eos_idx]

    eng = _engine(model, params, cache_on=True)
    out, sched = _run_sched(eng, [(1, prompt)], max_new=20, eos=eos)
    assert out[1] == ref[:eos_idx + 1], "eos truncation changed the stream"
    pc = eng.prefix_cache
    bs = eng.config.kv_block_size
    real_history = list(prompt) + ref[:eos_idx]  # materialized = all but last
    full = len(real_history) // bs
    assert pc.n_cached_blocks == full, \
        f"tree holds {pc.n_cached_blocks} blocks, only {full} real full blocks exist"
    # the chain is exactly the real-token path
    node = pc._root
    for b in range(full):
        chunk = tuple(int(t) for t in real_history[b * bs:(b + 1) * bs])
        assert chunk in node.children, f"real chunk {b} missing from the tree"
        node = node.children[chunk]
    assert not node.children, "garbage children published past the real chain"
    assert eng.free_blocks + full == eng.state_manager.kv_cache.total_blocks, \
        "overshoot tail blocks did not return to the free list"


def test_spec_eos_inside_accepted_run_truncates_commit(tiny_model):
    """An eos ACCEPTED mid-run must end the commit there: without the
    truncation, acceptance carries past the eos, the post-eos KV completes
    blocks, and publish pins tree references on post-eos paths — the same
    leak the decode-path eos rewind closes. Engine level: the returned
    tokens stop at the eos and seen_tokens rewinds with them; scheduler
    level (oracle drafter, acceptance ~1.0): the stream truncates at eos
    and the radix tree holds exactly the real-token chain."""
    model, params = tiny_model
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 128, size=20, dtype=np.int32)
    ref = _greedy_reference(model, params, prompt, 12)
    eos_idx = next(i for i in range(2, 10) if ref[i] not in ref[:i])
    eos = ref[eos_idx]

    # engine level: oracle drafts would accept THROUGH the eos without the cap
    eng = _engine(model, params, cache_on=True)
    got = [int(np.asarray(eng.put([1], [prompt], sample="greedy")).reshape(-1)[0])]
    while got[-1] != eos:
        drafts = np.asarray(ref[len(got):len(got) + 4], np.int32)
        outs = eng.speculate_decode([1], [np.asarray([got[-1]], np.int32)], [drafts], 4,
                                    eos_token_ids=eos)
        got.extend(int(t) for t in outs[0])
    assert got == ref[:eos_idx + 1], "eos truncation changed the stream"
    assert eng.query(1).seen_tokens == prompt.size + eos_idx, \
        "KV materialized past the accepted eos"
    eng.flush(1)
    pc = eng.prefix_cache
    bs = eng.config.kv_block_size
    assert pc.n_cached_blocks == (prompt.size + eos_idx) // bs, \
        "post-eos blocks entered the radix tree"

    # scheduler level: same outcome end-to-end through _spec_burst
    deng = _engine(model, params, cache_on=False)
    eng2 = _engine(model, params, cache_on=True)
    sched = DynamicSplitFuseScheduler(
        eng2, token_budget=32,
        speculative=SpeculativeConfig(mode="draft_model", k=4, draft_engine=deng))
    sched.submit(1, prompt, max_new_tokens=20, eos_token_id=eos)
    out = sched.run()
    assert out[1] == ref[:eos_idx + 1]
    assert sched.spec_stats["accepted"] > 0, "oracle rounds must have accepted"
    assert eng2.prefix_cache.n_cached_blocks == (prompt.size + eos_idx) // bs
    assert (eng2.free_blocks + eng2.prefix_cache.n_cached_blocks
            == eng2.state_manager.kv_cache.total_blocks)


def test_decode_eos_rollback_engine_level(tiny_model):
    """Direct engine.decode with eos_token_ids: rows still return the full
    horizon (callers slice), but seen_tokens/KV rewind to the eos."""
    model, params = tiny_model
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 128, size=12, dtype=np.int32)
    eng_a = _engine(model, params)
    eng_b = _engine(model, params)
    t0a = np.asarray(eng_a.put([1], [prompt], sample="greedy")).reshape(-1)
    t0b = np.asarray(eng_b.put([1], [prompt], sample="greedy")).reshape(-1)
    row_a = np.asarray(eng_a.decode([1], [t0a[:1]], 8))[0]
    eos = int(row_a[3])
    row_b = np.asarray(eng_b.decode([1], [t0b[:1]], 8, eos_token_ids=[eos]))[0]
    np.testing.assert_array_equal(row_a, row_b)  # returned tokens unchanged
    assert eng_a.query(1).seen_tokens == prompt.size + 8
    j = int(np.nonzero(row_b == eos)[0][0])
    assert eng_b.query(1).seen_tokens == prompt.size + 1 + j
    eng_a.flush(1)
    eng_b.flush(1)


# ---------------------------------------------------------------------------
# observability: spec metrics/span when on, zero overhead when off
# ---------------------------------------------------------------------------

def test_spec_zero_overhead_when_config_absent(tiny_model):
    from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics

    model, params = tiny_model
    configure_metrics(enabled=True)
    get_metrics().reset()
    try:
        eng = _engine(model, params)  # no speculative block
        sched = DynamicSplitFuseScheduler(eng)
        assert not sched.speculating and sched._drafter is None
        sched.submit(0, np.arange(12, dtype=np.int32) % 128, max_new_tokens=6)
        sched.run()
        assert sched.spec_stats == {"rounds": 0, "drafted": 0, "accepted": 0,
                                    "rejected": 0, "backoffs": 0}
        assert sched._spec_by_uid == {} and sched.spec_summary(0) is None
        assert eng._spec_totals == {"drafted": 0, "accepted": 0}
        assert not any(k[0] == "verify" for k in eng._compiled), \
            "verify buckets compiled with speculation off"
        snap = get_metrics().snapshot()
        spec_keys = [k for k in list(snap.get("counters", {})) + list(snap.get("gauges", {}))
                     if "spec" in k]
        assert spec_keys == [], f"spec metrics emitted with the block absent: {spec_keys}"
    finally:
        configure_metrics(enabled=False)


def test_spec_metrics_counters_gauge_and_span(tiny_model, tmp_path):
    from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics
    from deepspeed_tpu.monitor.trace import configure_tracer, get_tracer

    model, params = tiny_model
    configure_metrics(enabled=True)
    get_metrics().reset()
    trace_file = str(tmp_path / "trace.jsonl")
    configure_tracer(enabled=True, path=trace_file)
    try:
        spec = SpeculativeConfig(mode="ngram", k=3, min_match=1)
        # the prompt holds every token of the vocabulary once, so whatever the
        # model emits first has an earlier occurrence for the n-gram drafter
        # to continue from: the first decode round IS a verify step, whatever
        # the weights (a repetitive prompt alone is not enough: the stream
        # leaves the motif at its first token and the plain decode burst
        # takes the rounds in which it would have repeated)
        eng = _engine(model, params, cache_on=True, spec=spec, max_context=160)
        prompt = np.arange(model.config.vocab_size, dtype=np.int32)
        _, sched = _run_sched(eng, [(1, prompt)], max_new=14)
        assert sched.spec_stats["rounds"] > 0
        snap = get_metrics().snapshot()
        c = snap["counters"]
        assert c["serving/spec_drafted_tokens"] > 0
        assert (c["serving/spec_accepted_tokens"] + c["serving/spec_rejected_tokens"]
                == c["serving/spec_drafted_tokens"])
        rate = snap["gauges"]["serving/spec_accept_rate"]
        assert 0.0 <= rate <= 1.0
        get_tracer().flush()
        with open(trace_file) as f:
            assert any('"serving/spec_verify"' in line for line in f), \
                "spec_verify span missing from the trace bus"
    finally:
        configure_metrics(enabled=False)
        get_tracer().reset()


# ---------------------------------------------------------------------------
# structural gate: rewinds only via DSStateManager.rollback_to
# ---------------------------------------------------------------------------

def test_check_spec_rollback_gate():
    from tools.check_spec_rollback import check

    assert check() == []


def test_check_spec_rollback_catches_drift(tmp_path):
    from tools.check_spec_rollback import check

    d = tmp_path / "v2"
    (d / "ragged").mkdir(parents=True)
    # the state-manager plane itself is allowed
    (d / "ragged" / "ragged_manager.py").write_text(
        "def ok(seq):\n    seq.seen_tokens = 0\n    del seq.token_history[2:]\n")
    (d / "ragged" / "kv_cache.py").write_text(
        "def ok(self):\n    self._allocator.release([1])\n")
    (d / "rogue.py").write_text(
        "def bad(seq, sm):\n"
        "    seq.seen_tokens = 3\n"
        "    del seq.token_history[2:]\n"
        "    seq.token_history.clear()\n"
        "    sm.kv_cache.release([1])\n")
    bad = check((str(d), ))
    assert {(rel, line) for rel, line, _why, _s in bad} == \
        {("rogue.py", 2), ("rogue.py", 3), ("rogue.py", 4), ("rogue.py", 5)}


# ---------------------------------------------------------------------------
# PR 13: token-tree verification, spec-burst backoff, speculative sampling
# ---------------------------------------------------------------------------

def test_ngram_draft_branches_top_n_distinct():
    """draft_branches returns up to ``width`` DISTINCT continuations —
    longest match first, most recent occurrence first — and branch 0 is
    exactly what the linear draft() proposes (width=1 back-compat)."""
    d = NgramDrafter(min_match=1, max_ngram=2)
    # stream: "5 -> [1,2]" most recently, "5 -> [3,4]" earlier, "5 -> [1,2]" dup
    ctx = np.asarray([5, 3, 4, 9, 5, 1, 2, 9, 5, 1, 2, 7, 5], np.int32)
    bs = d.draft_branches(0, ctx, k=2, width=3)
    assert [b.tolist() for b in bs][:2] == [[1, 2], [3, 4]]
    assert len({b.tobytes() for b in bs}) == len(bs), "duplicate branch emitted"
    lin = d.draft(0, ctx, 2)
    assert lin.tolist() == bs[0].tolist()
    # width=1 returns only the linear proposal
    assert [b.tolist() for b in d.draft_branches(0, ctx, 2, 1)] == [[1, 2]]
    # a drafter WITHOUT a branch override wraps its linear drafts
    class _Lin(Drafter):
        def draft(self, uid, context, k):
            return np.asarray([1, 2], np.int32)

    out = _Lin().draft_branches_many([(7, ctx)], 2, 4)
    assert [b.tolist() for b in out[7]] == [[1, 2]]


def test_tree_verify_deepest_path_wins_and_parity(tiny_model):
    """Engine-level token-tree verification: with the true continuation
    hidden among junk branches (NOT at branch 0), the deepest-argmax-path
    walk must find it, commit it at the canonical KV positions (the
    compaction move), and keep greedy parity bit-exact — with the prefix
    cache on and off, and with the pool pristine after flush."""
    model, params = tiny_model
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, size=20, dtype=np.int32)
    ref = _greedy_reference(model, params, prompt, 12)
    k = 3
    for cache_on in (False, True):
        eng = _engine(model, params, cache_on=cache_on)
        got = [int(np.asarray(eng.put([5], [prompt], sample="greedy")).reshape(-1)[0])]
        rounds = 0
        while len(got) < 12:
            oracle = np.asarray(ref[len(got):len(got) + k], np.int32)
            junk = (oracle + 7) % 128
            # oracle at branch index 1: a tie-broken branch-0 walk would
            # commit junk — the deepest path must win regardless of order
            outs = eng.speculate_decode([5], [np.asarray([got[-1]], np.int32)],
                                        [[junk, oracle, (oracle + 3) % 128]], k)
            got.extend(int(t) for t in outs[0])
            rounds += 1
        assert got[:12] == ref, f"tree verification broke greedy parity (cache={cache_on})"
        assert rounds <= -(-11 // k) + 1, "oracle branch must commit ~k+1/round"
        assert eng.query(5).seen_tokens == prompt.size + len(got) - 1
        eng.flush(5)
        sm = eng.state_manager
        tree = eng.prefix_cache.n_cached_blocks if eng.prefix_cache else 0
        assert sm.free_blocks + tree == sm.kv_cache.total_blocks, \
            "tree verify leaked blocks"


def test_tree_rejected_branches_never_enter_radix_tree(tiny_model):
    """The PR 9 tree-pollution regression extended to TREE drafts: junk
    sibling branches materialize KV at their flat slots every round, but
    after finish+flush the radix tree must hold EXACTLY the committed
    greedy chain — a rejected branch's tokens in the tree would poison
    prefix reuse for every later request."""
    model, params = tiny_model
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 128, size=20, dtype=np.int32)
    ref = _greedy_reference(model, params, prompt, 10)
    k = 3
    eng = _engine(model, params, cache_on=True)
    got = [int(np.asarray(eng.put([1], [prompt], sample="greedy")).reshape(-1)[0])]
    while len(got) < 10:
        oracle = np.asarray(ref[len(got):len(got) + k], np.int32)
        outs = eng.speculate_decode([1], [np.asarray([got[-1]], np.int32)],
                                    [[(oracle + 11) % 128, oracle]], k)
        got.extend(int(t) for t in outs[0])
    assert got[:10] == ref
    eng.flush(1)
    pc = eng.prefix_cache
    bs = eng.config.kv_block_size
    real_history = list(prompt) + got[:-1]  # last token pending, never materialized
    full = len(real_history) // bs
    assert pc.n_cached_blocks == full, \
        f"tree holds {pc.n_cached_blocks} blocks, only {full} real full blocks exist"
    node = pc._root
    for b in range(full):
        chunk = tuple(int(t) for t in real_history[b * bs:(b + 1) * bs])
        assert chunk in node.children, f"real chunk {b} missing from the radix tree"
        node = node.children[chunk]
    assert not node.children, "a rejected branch leaked into the radix tree"


def test_tree_accept_beats_linear_on_same_stream(tiny_model):
    """Scheduler-level: the SAME request stream under the ngram drafter at
    tree_width 4 must accept at least as many draft tokens as width 1 (any
    one of the extra hypotheses matching lifts the round), with the greedy
    output bit-identical to spec-off in both arms."""
    model, params = tiny_model
    rng = np.random.default_rng(11)
    motif = rng.integers(0, 128, size=6, dtype=np.int32)
    reqs = []
    for i in range(3):
        filler = rng.integers(0, 128, size=4, dtype=np.int32)
        reqs.append((i, np.concatenate([motif, filler, motif, motif])))
    base, _ = _run_sched(_engine(model, params), list(reqs), max_new=14)
    accepted = {}
    for width in (1, 4):
        eng = _engine(model, params)
        sched = DynamicSplitFuseScheduler(
            eng, token_budget=48,
            speculative=SpeculativeConfig(mode="ngram", k=3, min_match=1,
                                          tree_width=width, backoff_after=0))
        for uid, p in reqs:
            sched.submit(uid, p, max_new_tokens=14)
        out = sched.run()
        assert out == base, f"width={width} broke greedy parity"
        accepted[width] = sched.spec_stats["accepted"]
        assert sched.spec_stats["drafted"] > 0
    assert accepted[4] >= accepted[1], \
        f"tree acceptance {accepted[4]} < linear {accepted[1]} on the same stream"


def test_spec_backoff_parks_hopeless_drafter(tiny_model):
    """A drafter that never lands a token must stop burning verify FLOPs:
    after ``backoff_after`` consecutive zero-accept rounds the request
    stops drafting (drafter no longer consulted except re-probes), rides
    the plain decode burst, and the backoff is counted — with greedy
    parity untouched."""
    from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics

    model, params = tiny_model
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 128, size=16, dtype=np.int32)
    base, _ = _run_sched(_engine(model, params), [(0, prompt)], max_new=24)

    class _CountingJunk(_JunkDrafter):
        def __init__(self):
            super().__init__()
            self.calls = 0

        def draft(self, uid, context, k):
            self.calls += 1
            return super().draft(uid, context, k)

    configure_metrics(enabled=True)
    get_metrics().reset()
    try:
        drafter = _CountingJunk()
        eng = _engine(model, params)
        sched = DynamicSplitFuseScheduler(
            eng, token_budget=32,
            speculative=SpeculativeConfig(mode="ngram", k=2, backoff_after=3,
                                          reprobe_every=8),
            drafter=drafter)
        sched.submit(0, prompt, max_new_tokens=24)
        out = sched.run()
        assert out[0] == base[0], "backoff broke greedy parity"
        assert sched.spec_stats["backoffs"] == 1
        assert sched.spec_stats["accepted"] == 0
        # the drafter was consulted for the backoff_after rounds plus at
        # most the occasional re-probe — far fewer than one call per token
        assert drafter.calls <= 3 + 24 // 8 + 2, \
            f"drafter still consulted {drafter.calls}x after backoff"
        snap = get_metrics().snapshot()
        assert snap.get("counters", {}).get("serving/spec_disabled_total") == 1
    finally:
        configure_metrics(enabled=False)
    # an accepting drafter must NEVER back off: oracle via draft model
    deng = _engine(model, params)
    eng2 = _engine(model, params)
    sched2 = DynamicSplitFuseScheduler(
        eng2, token_budget=32,
        speculative=SpeculativeConfig(mode="ngram", k=2, backoff_after=3, reprobe_every=8),
        drafter=DraftModelDrafter(deng))
    sched2.submit(0, prompt, max_new_tokens=24)
    out2 = sched2.run()
    assert out2[0] == base[0]
    assert sched2.spec_stats["backoffs"] == 0
    assert sched2.spec_stats["accepted"] > 0


def test_speculative_sampling_matches_direct_distribution():
    """The rejection-sampling verify step is distribution-exact: over many
    seeds, the committed token at the first draft position (accept the
    draft w.p. p(d), else the normalized residual) must match the target's
    own tempered/top-p distribution — chi-square against exact
    probabilities, plus the hard structural checks (nothing outside the
    nucleus; temperature 0 IS argmax)."""
    from deepspeed_tpu.inference.v2.sampling import _filtered, spec_verify_draws

    rng = np.random.default_rng(0)
    V, k, S = 16, 3, 2000
    base = jnp.asarray(rng.normal(size=(1, k + 1, V)) * 2.0, jnp.float32)
    chunk1 = jnp.asarray(rng.integers(0, V, size=(1, k + 1)), jnp.int32)
    temps = jnp.full((S, ), 0.8, jnp.float32)
    tops = jnp.full((S, ), 0.9, jnp.float32)
    probs = np.asarray(jax.nn.softmax(_filtered(base, temps[:1], tops[:1]), -1))[0, 0]
    lg = jnp.broadcast_to(base, (S, k + 1, V))
    ch = jnp.broadcast_to(chunk1, (S, k + 1))
    fn = jax.jit(spec_verify_draws)
    counts = np.zeros(V)
    tot = 0
    for _ in range(10):
        seeds = jnp.asarray(rng.integers(0, 2**31, size=S), jnp.int32)
        acc, nxt = fn(lg, ch, temps, tops, seeds, jnp.zeros(S, jnp.int32))
        acc, nxt = np.asarray(acc), np.asarray(nxt)
        committed0 = np.where(acc[:, 0].astype(bool), int(chunk1[0, 1]), nxt[:, 0])
        np.add.at(counts, committed0, 1)
        tot += S
    emp = counts / tot
    mask = probs > 0
    assert (emp[~mask] == 0).all(), "sampled a token OUTSIDE the top-p nucleus"
    chi2 = tot * np.sum((emp[mask] - probs[mask]) ** 2 / probs[mask])
    dof = int(mask.sum()) - 1
    # p < 1e-6 rejection threshold for ~dof degrees of freedom: loose
    # enough to never flake, tight enough to catch a broken residual
    assert chi2 < dof + 12 * np.sqrt(2 * dof), \
        f"speculative sampling diverges from direct sampling (chi2={chi2:.1f}, dof={dof})"
    # temperature 0 rows are EXACT greedy, draws untouched
    acc0, nxt0 = fn(lg, ch, jnp.zeros(S, jnp.float32), tops, seeds, jnp.zeros(S, jnp.int32))
    gr = np.asarray(jnp.argmax(lg, -1))
    assert (np.asarray(nxt0) == gr).all()
    assert (np.asarray(acc0).astype(bool) == (np.asarray(ch)[:, 1:] == gr[:, :k])).all()


def test_sampled_spec_deterministic_and_budgeted(tiny_model):
    """Engine-level speculative sampling: a fixed (seed, prompt) replays
    the SAME stream across runs (position-keyed draws), a different seed
    diverges, and the KV pool stays clean — sampling changes the
    distribution lever, never the block accounting."""
    from deepspeed_tpu.inference.v2 import SamplingParams

    model, params = tiny_model
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 128, size=16, dtype=np.int32)

    def run(seed):
        eng = _engine(model, params)
        sp = SamplingParams(temperature=0.9, top_p=0.95, seed=seed)
        got = [int(np.asarray(eng.put([9], [prompt], sample="greedy",
                                      sampling=[sp])).reshape(-1)[0])]
        while len(got) < 10:
            drafts = np.asarray([got[-1]] * 2, np.int32)
            outs = eng.speculate_decode([9], [np.asarray([got[-1]], np.int32)],
                                        [drafts], 2, sampling=[sp])
            got.extend(int(t) for t in outs[0])
        eng.flush(9)
        assert eng.state_manager.free_blocks == eng.state_manager.kv_cache.total_blocks
        return got[:10]

    a, b, c = run(42), run(42), run(43)
    assert a == b, "same seed must replay the same sampled stream"
    assert a != c, "different seeds should diverge (vanishingly unlikely otherwise)"


def test_sampled_decode_scan_matches_put_loop(tiny_model):
    """The sampled multi-step decode scan and the per-token sampled put
    loop must produce the IDENTICAL stream for one (seed, prompt): draws
    are keyed by token position, not by which compiled program runs."""
    from deepspeed_tpu.inference.v2 import SamplingParams

    model, params = tiny_model
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 128, size=14, dtype=np.int32)

    def run(use_scan):
        eng = _engine(model, params)
        sp = SamplingParams(temperature=0.9, top_p=0.95, seed=123)
        got = [int(np.asarray(eng.put([2], [prompt], sample="greedy",
                                      sampling=[sp])).reshape(-1)[0])]
        if use_scan:
            rows = np.asarray(eng.decode([2], [np.asarray([got[-1]], np.int32)], 8,
                                         sampling=[sp]))
            got.extend(int(t) for t in rows[0])
        else:
            while len(got) < 9:
                t = np.asarray(eng.put([2], [np.asarray([got[-1]], np.int32)],
                                       sample="greedy", sampling=[sp])).reshape(-1)[0]
                got.append(int(t))
        eng.flush(2)
        return got[:9]

    assert run(True) == run(False)


def test_sampling_params_validation():
    from deepspeed_tpu.inference.v2 import SamplingParams

    SamplingParams().validate()
    SamplingParams(temperature=0.7, top_p=0.5, seed=1).validate()
    for bad in (SamplingParams(temperature=-0.1), SamplingParams(temperature=float("nan")),
                SamplingParams(temperature=1e6), SamplingParams(top_p=0.0),
                SamplingParams(top_p=1.5), SamplingParams(seed=2**40)):
        with pytest.raises(ValueError):
            bad.validate()


def test_tree_plus_sampling_refused(tiny_model):
    from deepspeed_tpu.inference.v2 import SamplingParams

    model, params = tiny_model
    eng = _engine(model, params)
    prompt = np.arange(12, dtype=np.int32) % 128
    first = int(np.asarray(eng.put([4], [prompt], sample="greedy")).reshape(-1)[0])
    with pytest.raises(ValueError, match="greedy-only"):
        eng.speculate_decode([4], [np.asarray([first], np.int32)],
                             [[np.asarray([1, 2], np.int32), np.asarray([3, 4], np.int32)]],
                             2, sampling=[SamplingParams(temperature=0.8, seed=0)])
    eng.flush(4)
