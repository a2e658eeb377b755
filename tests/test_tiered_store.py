"""Tiered KV-cache hierarchy (ISSUE 17): async demotion of cold prefix
blocks to a pinned host pool, promotion back on hit, optional disk tier.

The invariants pinned here, from below and from above:

  * residency is exclusive — a block is never writable in two tiers at
    once (an HBM node holds no host block, a host node holds no HBM
    block, an in-flight node holds neither);
  * refcounts equal live holders across demote/promote/COW churn, and
    every block comes home: after drain + clear the HBM pool and the
    host pool are both exactly full-free;
  * greedy token ids are bit-identical tier-on vs tier-off, including a
    hit landing MID-promotion (the match stops at the in-flight node and
    recomputes — slower, never wrong);
  * a crash inside the migration worker (chaos ``cache/demote``) loses
    exactly the demoting block — the rest of the tree still hits and the
    worker survives;
  * decode steps never block on migration: with the worker wedged,
    evict/demote/acquire all return immediately;
  * zero overhead when ``ragged.prefix_cache.host_tier`` is absent (the
    PR 5 presence-enable contract);
  * owner stamps survive demotion — host-tier block-seconds reconcile
    against the telemetry host-occupancy integral within 5%.
"""

import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        HostTierConfig, InferenceEngineV2,
                                        PrefixCacheConfig, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import CacheTelemetryConfig
from deepspeed_tpu.inference.v2.ragged.cache_telemetry import CacheTelemetry
from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
from deepspeed_tpu.inference.v2.ragged.prefix_cache import PrefixKVCache
from deepspeed_tpu.inference.v2.ragged.tiered_store import (RES_HBM, RES_HOST,
                                                            RES_IN_FLIGHT,
                                                            TieredBlockStore)
from deepspeed_tpu.models import llama2
from deepspeed_tpu.runtime.resilience import chaos


# ---------------------------------------------------------------------------
# unit harness: a tiny real device pool + tree + tier
# ---------------------------------------------------------------------------

def _tiny_pool(num_blocks=8, block_size=4):
    return BlockedKVCache(num_layers=1, num_kv_heads=1, head_dim=2,
                          num_blocks=num_blocks, block_size=block_size,
                          dtype=jnp.float32)


class _Seq:
    def __init__(self, tokens, blocks, seen=None, tenant=None):
        self.token_history = list(tokens)
        self.kv_blocks = list(blocks)
        self.seen_tokens = len(tokens) if seen is None else seen
        self.history_valid = True
        if tenant is not None:
            self.tenant = tenant


def _tiered(num_blocks=8, block_size=4, host_blocks=4, telemetry=False, **tier_kw):
    kv = _tiny_pool(num_blocks, block_size)
    tel = CacheTelemetry(kv, CacheTelemetryConfig(enabled=True,
                                                  mrc_sample_rate=1.0)) if telemetry else None
    pc = PrefixKVCache(kv, telemetry=tel)
    tier = TieredBlockStore(kv, HostTierConfig(host_blocks=host_blocks, **tier_kw),
                            telemetry=tel)
    pc.attach_tier(tier)
    return kv, pc, tier, tel


def _publish_chain(kv, pc, tokens, fill=None, tenant=None):
    """Reserve + (optionally) stamp recognizable KV + publish + release the
    owner refs, leaving a tree-only chain. Returns the block ids."""
    bs = pc.block_size
    n = len(tokens) // bs
    blocks = kv.reserve(n)
    if fill is not None:
        for i, b in enumerate(blocks):
            k0, v0, _, _ = kv.read_block(b)
            kv.write_block(b, np.full_like(np.asarray(k0), fill + i),
                           np.full_like(np.asarray(v0), fill + i + 0.25))
    pc.publish(_Seq(tokens, blocks, tenant=tenant))
    for b in blocks:
        kv.release(b)
    return [int(b) for b in blocks]


def _drain(tier, timeout=5.0):
    deadline = time.time() + timeout
    while tier.queued and time.time() < deadline:
        time.sleep(0.005)
    # queued==0 means popped, not finalized: give the in-flight item a beat
    for _ in range(int(timeout / 0.005)):
        with tier._cv:
            idle = not tier._q
        if idle and not any(n.res == RES_IN_FLIGHT
                            for n in _walk(tier._cache)):
            return
        time.sleep(0.005)


def _walk(pc):
    out, stack = [], list(pc._root.children.values())
    while stack:
        n = stack.pop()
        out.append(n)
        stack.extend(n.children.values())
    return out


# ---------------------------------------------------------------------------
# demote → promote round trip: payloads, refcounts, occupancy
# ---------------------------------------------------------------------------

def test_demote_promote_roundtrip_exact_payload():
    kv, pc, tier, _ = _tiered()
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    _publish_chain(kv, pc, toks, fill=10.0)
    assert pc.demote_cold(2) == 2
    _drain(tier)
    # demotion freed the HBM copies and parked both blocks host-side
    assert kv.free_blocks == 8
    assert pc.host_resident_blocks == 2
    assert tier.snapshot()["demotions"] == 2

    blocks, n_cached, n_shared = pc.acquire(toks + [9, 9, 9])
    assert n_cached == 8 and len(blocks) == 2
    assert n_shared == 0  # the whole hit was served by promotion
    assert pc.stats["promotions"] == 2 and pc.stats["promoted_tokens"] == 8
    for i, b in enumerate(blocks):
        k, v, _, _ = kv.read_block(b)
        np.testing.assert_array_equal(np.unique(np.asarray(k)), [10.0 + i])
        np.testing.assert_array_equal(np.unique(np.asarray(v)), [10.25 + i])
        assert kv.refcount(b) == 2  # tree + this acquire, nothing else
    # promoted blocks left the host pool (no dual residency)
    assert tier.pool.used_blocks == 0 and pc.host_resident_blocks == 0
    for b in blocks:
        kv.release(b)
    pc.clear()
    assert kv.free_blocks == 8
    tier.shutdown()


def test_match_counts_host_chain_but_does_not_pin_it():
    """``match`` (the admission probe) reports demoted coverage via
    ``host_blocks`` WITHOUT putting those ids in ``shared_blocks`` — the
    scheduler charges promoted blocks against the admission budget exactly
    like uncached tokens (they will consume fresh HBM)."""
    kv, pc, tier, _ = _tiered()
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    _publish_chain(kv, pc, toks)
    pc.demote_cold(1)  # the leaf demotes, the root-side block stays in HBM
    _drain(tier)
    m = pc.match(toks + [9, 9, 9])
    assert len(m.shared_blocks) == 1 and m.host_blocks == 1
    assert m.n_cached_tokens == 8
    tier.shutdown()


# ---------------------------------------------------------------------------
# churn/fuzz: residency exclusivity + refcount conservation
# ---------------------------------------------------------------------------

def test_residency_exclusive_and_refcounts_under_churn():
    """Randomized publish/acquire/evict/demote churn against a tiny pool.
    After every round: no node is writable in two tiers at once, every
    host block backs exactly one node, and every HBM tree node holds a
    live reference. After drain + release + clear: both pools are exactly
    full-free (nothing leaked, nothing double-freed)."""
    kv, pc, tier, _ = _tiered(num_blocks=16, block_size=4, host_blocks=8)
    rng = np.random.default_rng(17)
    held = []  # blocks acquired and not yet released
    for round_ in range(40):
        op = rng.integers(0, 4)
        toks = [int(t) for t in rng.integers(0, 30, size=8)]
        if op == 0 and kv.free_blocks >= 2:
            _publish_chain(kv, pc, toks)
        elif op == 1:
            blocks, _, _ = pc.acquire(toks + [99])
            held.extend(blocks)
            if len(held) > 6:  # bounded holders, FIFO release
                kv.release(held.pop(0))
        elif op == 2:
            pc.evict(int(rng.integers(1, 4)))
        else:
            pc.demote_cold(int(rng.integers(1, 4)))
        if round_ % 10 == 9:
            _drain(tier)
        with pc._tree_lock:
            host_blocks_seen = set()
            for n in _walk(pc):
                if n.res == RES_HBM:
                    assert n.block >= 0 and n.host_block == -1 and n.disk_id == -1
                    assert kv.refcount(n.block) >= 1, "tree node without a ref"
                elif n.res == RES_HOST:
                    assert n.block == -1 and n.host_block >= 0 and n.disk_id == -1
                    assert n.host_block not in host_blocks_seen, \
                        "host block backing two nodes"
                    host_blocks_seen.add(n.host_block)
                elif n.res == RES_IN_FLIGHT:
                    assert n.block == -1 and n.host_block == -1
    _drain(tier)
    for b in held:
        kv.release(b)
    pc.clear()
    _drain(tier)  # late finalizations cancel against the detached nodes
    assert kv.free_blocks == 16, "HBM blocks leaked through the tier"
    assert tier.pool.used_blocks == 0, "host blocks leaked"
    assert tier.snapshot()["demote_failures"] == 0
    tier.shutdown()


# ---------------------------------------------------------------------------
# blast radius: a crash mid-demotion loses exactly the demoting block
# ---------------------------------------------------------------------------

def test_crash_during_demotion_loses_only_that_block():
    kv, pc, tier, _ = _tiered(num_blocks=16, block_size=4)
    long_toks = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]
    other_toks = [21, 22, 23, 24]
    _publish_chain(kv, pc, long_toks)
    _publish_chain(kv, pc, other_toks)

    fired = threading.Event()

    def boom(_ctx):
        if not fired.is_set():
            fired.set()
            raise RuntimeError("injected: worker dies mid-copy")

    handle = chaos.inject("cache/demote", boom)
    try:
        assert pc.demote_cold(1) == 1  # the LRU leaf of the long chain
        _drain(tier)
        assert fired.is_set()
        snap = tier.snapshot()
        assert snap["demote_failures"] == 1
        # ONLY the demoting block is gone: the long chain still serves its
        # first two blocks, the unrelated chain is untouched
        m = pc.match(long_toks + [99, 99])
        assert m.n_cached_tokens == 8 and m.host_blocks == 0
        m2 = pc.match(other_toks + [99, 99])
        assert m2.n_cached_tokens == 4
        # and the worker SURVIVED: the next demotion goes through cleanly
        assert pc.demote_cold(1) == 1
        _drain(tier)
        assert tier.snapshot()["demotions"] == 1
        assert pc.host_resident_blocks == 1
    finally:
        handle.remove()
        tier.shutdown()


# ---------------------------------------------------------------------------
# decode-never-blocks: a wedged migration worker stalls NOTHING driver-side
# ---------------------------------------------------------------------------

def test_migration_never_blocks_driver_paths():
    kv, pc, tier, _ = _tiered(num_blocks=16, block_size=4, host_blocks=8,
                              queue_depth=2)
    gate = threading.Event()
    entered = threading.Event()

    def wedge(_ctx):
        entered.set()
        gate.wait(20)

    handle = chaos.inject("cache/demote", wedge)
    try:
        for base in range(0, 40, 10):
            _publish_chain(kv, pc, [base + i for i in range(8)])
        t0 = time.perf_counter()
        queued = pc.demote_cold(8)
        enqueue_s = time.perf_counter() - t0
        assert entered.wait(5)
        # bounded queue: worker holds one, queue holds <= depth; the rest of
        # the request was REFUSED, not waited for
        assert queued <= 3 and enqueue_s < 1.0
        # eviction still makes progress while the worker is wedged — full
        # queue means victims take the old drop path, and nothing waits
        t0 = time.perf_counter()
        freed = pc.evict(2)
        assert freed == 2 and time.perf_counter() - t0 < 1.0
        # acquire on an in-flight chain returns immediately: the match stops
        # at the in-flight node instead of waiting for its migration
        with pc._tree_lock:
            inflight = [n for n in _walk(pc) if n.res == RES_IN_FLIGHT]
        assert inflight
        t0 = time.perf_counter()
        pc.acquire([0, 1, 2, 3, 4, 5, 6, 7, 99])
        assert time.perf_counter() - t0 < 1.0
        assert pc.stats["promotions"] == 0  # nothing promoted from a stuck tier
    finally:
        gate.set()
        handle.remove()
        tier.shutdown()


# ---------------------------------------------------------------------------
# zero overhead when the config block is absent (the PR 5 contract)
# ---------------------------------------------------------------------------

def test_zero_overhead_when_host_tier_absent():
    before = {t.name for t in threading.enumerate()}
    kv = _tiny_pool()
    pc = PrefixKVCache(kv)
    assert pc._tier is None
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    _publish_chain(kv, pc, toks)
    pc.evict(2)  # the eviction path must not consult any tier machinery
    assert pc.demote_cold(4) == 0  # no tier: proactive demotion is a no-op
    after = {t.name for t in threading.enumerate()}
    assert "kv-tier-migrator" not in after - before
    # residency fields exist (fixed __slots__ cost) but stay at the shared
    # defaults — no per-node tier state accrues without a tier
    for n in _walk(pc):
        assert n.res is RES_HBM and n.host_block == -1 and n.disk_id == -1


def test_engine_without_host_tier_has_no_store(tiny_model):
    model, params = tiny_model
    eng = _engine(model, params, host_tier=None)
    assert eng.tiered_store is None
    assert "host_tier" not in eng.query()
    eng.shutdown()  # must be a safe no-op


# ---------------------------------------------------------------------------
# engine-level: greedy parity tier-on vs tier-off, incl. hit mid-promotion
# ---------------------------------------------------------------------------

def _engine(model, params, host_tier, num_kv_blocks=64):
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                              max_ragged_sequence_count=8, max_context=64)
    icfg = RaggedInferenceEngineConfig(
        kv_block_size=8, num_kv_blocks=num_kv_blocks, kv_dtype=jnp.float32,
        state_manager=sm, use_pallas_kernels="never",
        prefix_cache=PrefixCacheConfig(enabled=True, host_tier=host_tier))
    return InferenceEngineV2(model, icfg, params=params)


@pytest.fixture(scope="module")
def tiny_model():
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=256,
                   dtype=jnp.float32, attention_impl="reference")
    params = jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(0))
    return model, params


def test_greedy_parity_tier_on_off_with_promotion(tiny_model):
    """IDENTICAL request stream, host tier on vs off → bit-identical greedy
    ids, with demotions forced between requests so the tier arm actually
    serves hits from the host pool (promotions > 0)."""
    model, params = tiny_model
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, 128, size=24, dtype=np.int32)
    reqs = []
    for i in range(3):
        suf = rng.integers(0, 128, size=int(rng.integers(4, 10)), dtype=np.int32)
        reqs.append((i, np.concatenate([prefix, suf])))
    reqs.append((10, reqs[0][1].copy()))  # exact repeat: COW cap on the tail

    outs = {}
    for tier_on in (False, True):
        eng = _engine(model, params,
                      HostTierConfig(host_blocks=32) if tier_on else None)
        sched = DynamicSplitFuseScheduler(eng, token_budget=32)
        for uid, p in reqs:
            sched.submit(uid, p, max_new_tokens=6)
            sched.run()
            if tier_on:
                # push the whole cached tree host-side between requests:
                # every later hit must come back through promotion
                eng.prefix_cache.demote_cold(8)
                _drain(eng.tiered_store)
        outs[tier_on] = {u: t for u, t in sched.results.items()}
        if tier_on:
            assert eng.prefix_cache.stats["promotions"] > 0, \
                "tier arm never promoted — the A/B proved nothing"
            assert eng.prefix_cache.stats["hits"] >= 2
        eng.shutdown()
    assert outs[True] == outs[False], "host tier changed the computation"


def test_hit_mid_promotion_recomputes_and_readopts(tiny_model):
    """A request landing while its prefix is still IN-FLIGHT to the host
    pool must not wait and must not go wrong: the match stops at the
    in-flight node, the tokens are recomputed, and publish re-adopts the
    chunk into HBM (the queued demotion cancels itself)."""
    model, params = tiny_model
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, 128, size=20, dtype=np.int32)

    eng = _engine(model, params, HostTierConfig(host_blocks=32))
    pc, tier = eng.prefix_cache, eng.tiered_store
    gate = threading.Event()
    handle = chaos.inject("cache/demote", lambda _ctx: gate.wait(20))
    try:
        cold = np.asarray(eng.put([1], [prompt]))
        eng.flush(1)
        assert pc.demote_cold(8) >= 1
        time.sleep(0.05)  # worker pops and wedges inside the chaos hook
        with pc._tree_lock:
            assert any(n.res == RES_IN_FLIGHT for n in _walk(pc))
        # same prompt, mid-demotion: completes now, with cold-identical
        # logits (recomputed — possibly a shortened hit, never a wait)
        warm = np.asarray(eng.put([2], [prompt]))
        np.testing.assert_allclose(cold, warm, rtol=1e-5, atol=1e-5)
        eng.flush(2)
        assert pc.stats["readoptions"] >= 1, \
            "publish should have re-adopted the recomputed chunk into HBM"
    finally:
        gate.set()
        handle.remove()
    _drain(tier)
    # the wedged demotion finalizes against a re-adopted (HBM) node: cancel
    assert tier.snapshot()["demote_cancelled"] >= 1
    eng.shutdown()


# ---------------------------------------------------------------------------
# owner stamps survive demotion: host-tier seconds reconcile (ISSUE 15 bridge)
# ---------------------------------------------------------------------------

def test_host_kv_seconds_conserve_against_occupancy_integral():
    from deepspeed_tpu.serving.config import MeteringConfig
    from deepspeed_tpu.serving.metering import EngineMeterView, TenantMeter

    kv, pc, tier, tel = _tiered(num_blocks=16, block_size=4, host_blocks=8,
                                telemetry=True)
    meter = TenantMeter(MeteringConfig(enabled=True))
    pc.set_meter(EngineMeterView(meter, kv.total_blocks))
    _publish_chain(kv, pc, [1, 2, 3, 4, 5, 6, 7, 8], tenant="alice")
    _publish_chain(kv, pc, [11, 12, 13, 14, 15, 16, 17, 18], tenant="bob")
    assert pc.demote_cold(4) == 4
    _drain(tier)
    assert pc.host_resident_blocks == 4
    time.sleep(0.4)  # accrue measurable host residency
    pc.clear()  # releases every host copy → charges land on the owners
    _drain(tier)
    per = meter.host_kv_block_seconds()
    assert per.get("alice", 0.0) > 0 and per.get("bob", 0.0) > 0, per
    charged = sum(per.values())
    integral = tel.host_occupancy_integral_s()
    assert integral > 0
    assert abs(charged - integral) <= 0.05 * integral, (charged, integral)
    # the resource is its own ledger line, not folded into HBM kv_block_s
    report = meter.usage_report()
    assert report["tenants"]["alice"]["host_kv_s"] > 0
    assert report["tenants"]["alice"]["kv_block_s"] == 0.0
    rows = dict(((n, l.get("tenant")), v) for n, l, v in meter.gauge_rows())
    assert rows[("serving/tenant_host_kv_block_seconds_total", "alice")] > 0
    tier.shutdown()


# ---------------------------------------------------------------------------
# disk tier: spill past the host pool, promote back, corrupt file = miss
# ---------------------------------------------------------------------------

def test_disk_tier_spill_promote_and_corrupt_file_is_miss(tmp_path):
    kv, pc, tier, _ = _tiered(num_blocks=16, block_size=4, host_blocks=2,
                              disk_path=str(tmp_path), disk_blocks=8)
    toks = list(range(100, 116))  # 4 blocks: 2 overflow host → disk
    _publish_chain(kv, pc, toks, fill=5.0)
    assert pc.demote_cold(4) == 4
    _drain(tier)
    snap = tier.snapshot()
    assert snap["demotions"] == 4 and snap["host_evictions"] == 2
    assert snap["disk_spills"] == 2 and snap["disk_used"] == 2
    blocks, n_cached, _ = pc.acquire(toks + [9, 9, 9])
    assert n_cached == 16
    for i, b in enumerate(blocks):
        k, _, _, _ = kv.read_block(b)
        np.testing.assert_array_equal(np.unique(np.asarray(k)), [5.0 + i])
    assert tier.snapshot()["promotions_disk"] == 2
    for b in blocks:
        kv.release(b)

    # corruption: demote again, truncate one block file → that chunk reads
    # as a MISS (dropped subtree), never as wrong KV
    assert pc.demote_cold(4) == 4
    _drain(tier)
    files = sorted(tmp_path.glob("kvblock_*.npz"))
    assert files
    files[0].write_bytes(b"torn write")
    blocks2, n_cached2, _ = pc.acquire(toks + [9, 9, 9])
    assert n_cached2 < 16
    assert tier.snapshot()["disk_corrupt"] >= 1
    for b in blocks2:
        kv.release(b)
    tier.shutdown()


# ---------------------------------------------------------------------------
# eviction-starvation accounting (this PR's satellite bugfix)
# ---------------------------------------------------------------------------

def test_evict_starved_counter_and_breadcrumb():
    from deepspeed_tpu.monitor.flight import get_flight_recorder
    from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics

    configure_metrics(enabled=True)
    get_metrics().reset()
    flight = get_flight_recorder().configure(enabled=True, capacity=64)
    flight.clear()
    try:
        kv = _tiny_pool(num_blocks=8, block_size=4)
        pc = PrefixKVCache(kv)
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        blocks = kv.reserve(2)
        pc.publish(_Seq(toks, blocks))
        # the sequence still holds its refs: both tree nodes are pinned, so
        # a 2-block eviction request frees NOTHING and must say why
        assert pc.evict(2) == 0
        assert pc.stats["evict_starved"] == 1
        assert get_metrics().counter("cache/evict_starved_total").value == 1
        events = [e for e in flight.dump() if e.get("name") == "evict_starved"]
        assert events and events[-1]["reason"] == "eviction_starved"
        assert events[-1]["requested"] == 2 and events[-1]["freed"] == 0
        for b in blocks:
            kv.release(b)
        pc.clear()
        # pool-dry spelling: nothing cached at all
        assert pc.evict(1) == 0
        events = [e for e in flight.dump() if e.get("name") == "evict_starved"]
        assert events[-1]["reason"] == "pool_dry"
    finally:
        flight.configure(enabled=False)
        configure_metrics(enabled=False)


# ---------------------------------------------------------------------------
# structural gates
# ---------------------------------------------------------------------------

def test_check_kv_blocks_covers_host_pool_mutators(tmp_path):
    from tools.check_kv_blocks import check

    assert check() == []  # the real tree, with tiered_store allowlisted
    v2 = tmp_path / "v2"
    (v2 / "ragged").mkdir(parents=True)
    (v2 / "ragged" / "tiered_store.py").write_text(
        "def f(pool):\n    pool.host_free(1)\n")  # allowlisted
    (v2 / "rogue.py").write_text(
        "def g(pool):\n    pool.host_free(3)\n    pool.host_write(1, None, None)\n")
    bad = check(str(v2))
    assert [(rel, line) for rel, line, _ in bad] == [("rogue.py", 2), ("rogue.py", 3)]


# ---------------------------------------------------------------------------
# the A/B instrument itself (slow: two engines + compiles)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_host_tier_ab_hierarchy_beats_hbm_with_parity():
    from tools.serving_load import host_tier_ab

    out = host_tier_ab(n_requests=48)
    assert out["token_parity"] is True
    on, off = out["host_tier"], out["hbm_only"]
    assert on["hierarchy_hit_rate"] > off["hbm_hit_rate"], out
    assert on["promotions"] > 0
    # MRC one tier up: predicted-at-hierarchy-capacity vs measured, ≤ 0.05
    assert on["mrc_hierarchy_abs_err"] is not None
    assert on["mrc_hierarchy_abs_err"] <= 0.05, \
        (on["mrc_predicted_hierarchy"], on["measured_hierarchy_block_hit_rate"])
