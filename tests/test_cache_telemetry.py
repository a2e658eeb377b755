"""Memory & KV-cache observability plane (ISSUE 11): block-lifecycle
accounting, the SHARDS miss-ratio-curve estimator, HBM attribution, and the
metric-namespace gate.

The acceptance bars pinned here:

  * the MRC estimator's predicted hit rate at 1x capacity is within 0.05
    absolute of (i) an exact LRU stack-distance simulation on synthetic
    traces and (ii) the measured hit rate under the ``cache_pressure``
    serving_load workload;
  * telemetry fully off ⇒ zero new threads, zero telemetry objects, zero
    per-block allocations (the PR 5 zero-overhead contract);
  * refcount-class accounting (active / tree-only / free) stays exact under
    the same submit/decode/flush churn the prefix-cache fuzz runs;
  * ``tools/check_metric_names.py`` holds the approved metric prefix set
    and catches drift.
"""

import gc
import threading
import zlib
from collections import OrderedDict

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (CacheTelemetryConfig, DSStateManagerConfig,
                                        DynamicSplitFuseScheduler, InferenceEngineV2,
                                        PrefixCacheConfig, RaggedInferenceEngineConfig,
                                        SpeculativeConfig)
from deepspeed_tpu.inference.v2.ragged import MRCEstimator
from deepspeed_tpu.models import llama2
from deepspeed_tpu.monitor.flight import get_flight_recorder
from deepspeed_tpu.monitor.health import get_health
from deepspeed_tpu.monitor.memory import get_memory, hbm_report, tree_device_bytes


# ---------------------------------------------------------------------------
# MRC estimator vs exact LRU simulation (synthetic traces)
# ---------------------------------------------------------------------------

def _key(k):
    """Uniform 32-bit key for an abstract object id (the estimator samples
    by key value, so test keys must be hash-distributed like real chunk
    keys are)."""
    return zlib.crc32(str(k).encode()) & 0xFFFFFFFF


def _lru_hit_rate(trace, capacity):
    """Ground truth: an exact LRU cache of ``capacity`` slots over the same
    two-kind stream the estimator models — counted (demand) references and
    uncounted (insert) accesses both occupy/refresh slots, only counted
    ones enter the hit-rate accounting."""
    cache = OrderedDict()
    refs = hits = 0
    for key, counted in trace:
        if counted:
            refs += 1
        if key in cache:
            if counted:
                hits += 1
            cache.move_to_end(key)
        else:
            cache[key] = True
            if len(cache) > capacity:
                cache.popitem(last=False)
    return hits / refs if refs else 0.0


def _chain_trace(n_lookups, n_chains, chain_len, a=1.2, seed=0, inserts_per=2):
    """Synthetic trace in the REAL reference-stream shape: Zipf-sampled
    prefix CHAINS of ``chain_len`` block-chunk keys referenced together
    (one radix lookup), plus one-time publish-side inserts. Chain structure
    matters: it spreads each hot object's popularity across many sampled
    keys, which is exactly why SHARDS key-sampling works on this stream."""
    rng = np.random.default_rng(seed)
    ranks = (rng.zipf(a, size=n_lookups) - 1) % n_chains
    trace = []
    uniq = 10**9
    for r in ranks:
        for j in range(chain_len):
            trace.append((_key(f"{int(r)}-{j}"), True))
        for _ in range(inserts_per):
            uniq += 1
            trace.append((_key(uniq), False))  # one-time insert: capacity, not demand
    return trace


def _feed(est, trace):
    for key, counted in trace:
        if counted:
            est.record([key])
        else:
            est.note_insert([key])


def test_mrc_exact_rate_matches_lru_simulation():
    """sample_rate=1.0 degenerates to exact stack distances: the predicted
    hit rate at EVERY capacity multiplier equals the exact LRU simulation
    to the last reference."""
    cap = 64
    trace = _chain_trace(1500, 120, 4, seed=3)
    est = MRCEstimator(cap, sample_rate=1.0, max_tracked=10**6)
    _feed(est, trace)
    pred = est.predict()
    assert any(0.0 < v < 1.0 for v in pred.values()), "degenerate trace"
    for mult in est.capacity_mults:
        exact = _lru_hit_rate(trace, int(mult * cap))
        assert pred[mult] == pytest.approx(exact, abs=1e-12), \
            f"exact-rate MRC diverged from LRU sim at {mult}x"


@pytest.mark.parametrize("seed", [5, 11, 23])
def test_mrc_sampled_within_bound(seed):
    """The acceptance bar: SHARDS sampling at the production default rate
    stays within 0.05 absolute of the exact LRU simulation at every
    capacity multiplier (1x included) on chain-structured Zipf traces.
    (Measured: <= 0.004 across these seeds — the bound has real margin.)"""
    cap = 200
    trace = _chain_trace(6000, 400, 8, seed=seed)
    est = MRCEstimator(cap, sample_rate=0.25, max_tracked=10**6)
    _feed(est, trace)
    pred = est.predict()
    for mult in est.capacity_mults:
        exact = _lru_hit_rate(trace, int(mult * cap))
        assert abs(pred[mult] - exact) <= 0.05, \
            f"sampled MRC off by {abs(pred[mult] - exact):.3f} at {mult}x"


def test_mrc_insert_stream_consumes_capacity():
    """Uncounted inserts must push reusable keys deeper in the modeled
    stack: a key re-referenced across a burst of one-time inserts misses at
    a capacity smaller than the burst and hits at one larger."""
    est = MRCEstimator(10, sample_rate=1.0, capacity_mults=(1.0, 8.0))
    est.record([_key("hot")])
    est.note_insert([_key(f"cold-{i}") for i in range(30)])  # 30 distinct inserts
    est.record([_key("hot")])  # distance 30: miss at 10, hit at 80
    pred = est.predict()
    assert pred[1.0] == 0.0 and pred[8.0] == 0.5


def test_mrc_bounded_memory_and_reset():
    est = MRCEstimator(16, sample_rate=1.0, max_tracked=64)
    est.record([_key(i) for i in range(1000)])
    assert est.tracked_keys <= 64
    assert est.refs_total == 1000
    est.reset()
    assert est.tracked_keys == 0 and est.refs_total == 0
    assert all(v is None for v in est.predict().values())
    assert est.observed_hit_rate is None


# ---------------------------------------------------------------------------
# engine fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=256,
                   dtype=jnp.float32, attention_impl="reference")
    params = jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(0))
    return model, params


def _engine(model, params, telemetry=True, num_kv_blocks=40, sample_rate=1.0,
            speculative=None):
    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=128,
                              max_ragged_sequence_count=4, max_context=160)
    icfg = RaggedInferenceEngineConfig(
        kv_block_size=16, num_kv_blocks=num_kv_blocks, kv_dtype=jnp.float32,
        state_manager=sm, use_pallas_kernels="never",
        prefix_cache=PrefixCacheConfig(
            enabled=True,
            telemetry=CacheTelemetryConfig(enabled=telemetry,
                                           mrc_sample_rate=sample_rate)))
    if speculative is not None:
        icfg.speculative = speculative
    return InferenceEngineV2(model, icfg, params=params)


# ---------------------------------------------------------------------------
# refcount-class transitions under the churn-invariants fuzz
# ---------------------------------------------------------------------------

def test_refcount_classes_exact_under_churn(tiny_model):
    """The prefix-cache churn fuzz (shared-prefix submit/decode/flush storm)
    extended to the telemetry plane: at EVERY step the telemetry's
    active/tree-only/free decomposition must equal ground truth recomputed
    from allocator refcounts + the radix tree's holdings, its tree-held
    flags must mirror ``cached_block_ids``, and the lifetime counters must
    reconcile with the pool (allocated - freed == blocks off the free
    list). After full flush + clear the pool reads all-free."""
    model, params = tiny_model
    rng = np.random.default_rng(5)
    engine = _engine(model, params)
    tel = engine.cache_telemetry
    alloc = engine.state_manager.kv_cache._allocator
    pc = engine.prefix_cache
    total = engine.state_manager.free_blocks
    pool = [rng.integers(0, 128, size=48, dtype=np.int32) for _ in range(3)]

    live = {}
    next_uid = 0
    for step in range(40):
        op = rng.choice(["put", "decode", "flush"], p=[0.4, 0.4, 0.2])
        for u in [u for u in live if engine.query(u).seen_tokens > 140]:
            engine.flush(u)
            del live[u]
        if op == "put" and len(live) < 4:
            uid = next_uid; next_uid += 1
            cut = int(rng.integers(8, 49))
            prompt = np.concatenate([pool[int(rng.integers(0, 3))][:cut],
                                     rng.integers(0, 128, size=int(rng.integers(4, 30)),
                                                  dtype=np.int32)])
            tok = engine.put([uid], [prompt], sample="greedy")
            live[uid] = [int(tok[0])]
        elif op == "decode" and live:
            uids = sorted(live)
            out = np.asarray(engine.decode(
                uids, [np.asarray([live[u][-1]], np.int32) for u in uids], 8))
            for u, row in zip(uids, out):
                live[u].extend(int(t) for t in row)
        elif op == "flush" and live:
            uid = sorted(live)[int(rng.integers(0, len(live)))]
            engine.flush(uid)
            del live[uid]
        # ground truth decomposition from first principles
        tree = set(pc.cached_block_ids())
        want = {"free": 0, "tree_only": 0, "active": 0}
        for b in range(total):
            rc = alloc.refcount(b)
            if rc == 0:
                want["free"] += 1
            elif rc == 1 and b in tree:
                want["tree_only"] += 1
            else:
                want["active"] += 1
        got = tel.refcount_classes()
        assert got == want, f"step {step}: classes {got} != ground truth {want}"
        held = {b for b in range(total) if tel._tree_held[b]}
        assert held == tree, f"step {step}: tree-held flags drifted"
        assert tel.counters["allocated"] - tel.counters["freed"] \
            == total - engine.state_manager.free_blocks, \
            f"step {step}: alloc/free counters don't reconcile with the pool"
    assert tel.counters["evicted"] > 0, "fuzz never hit eviction pressure — weak run"
    assert tel.evicted_block_age_s.count == tel.counters["evicted"]
    assert tel.reuse_interval_s.count > 0 and tel.block_age_s.count > 0
    assert tel.mrc.refs_total > 0

    for uid in sorted(live):
        engine.flush(uid)
    pc.clear()
    assert tel.refcount_classes() == {"free": total, "tree_only": 0, "active": 0}


def test_evicted_tokens_and_cow_bytes_stats(tiny_model):
    """Satellite: token-granular eviction + COW byte accounting on
    ``PrefixKVCache.stats``, mirrored into the Prometheus registry."""
    from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics

    model, params = tiny_model
    configure_metrics(enabled=True)
    get_metrics().reset()
    try:
        engine = _engine(model, params, num_kv_blocks=12)
        pc = engine.prefix_cache
        bs = engine.config.kv_block_size
        rng = np.random.default_rng(9)
        base = rng.integers(0, 128, size=40, dtype=np.int32)
        engine.put([1], [base], sample="greedy")
        engine.flush(1)  # chain published, tree-only
        # partial-tail reuse: diverge mid-block -> COW copy
        probe = np.concatenate([base[:24], rng.integers(0, 128, size=12, dtype=np.int32)])
        engine.put([2], [probe], sample="greedy")
        assert pc.stats["cow_copies"] >= 1
        assert pc.stats["cow_bytes"] == pc.stats["cow_copies"] \
            * engine.state_manager.kv_cache.block_bytes()
        engine.flush(2)
        # pressure the 12-block pool until LRU leaves actually evict
        for i in range(3, 9):
            engine.put([i], [rng.integers(0, 128, size=40, dtype=np.int32)],
                       sample="greedy")
            engine.flush(i)
        assert pc.stats["evictions"] > 0
        assert pc.stats["evicted_tokens"] == pc.stats["evictions"] * bs
        snap = get_metrics().snapshot()["counters"]
        assert snap["cache/evicted_tokens"] == pc.stats["evicted_tokens"]
        assert snap["cache/cow_bytes"] == pc.stats["cow_bytes"]
    finally:
        configure_metrics(enabled=False)


# ---------------------------------------------------------------------------
# zero overhead when the telemetry block is absent (the PR 5 contract)
# ---------------------------------------------------------------------------

def test_zero_overhead_when_telemetry_off(tiny_model):
    """With ``ragged.prefix_cache.telemetry`` absent/off: no telemetry
    objects anywhere (engine, state manager, allocator hook, tree hook),
    no new threads, no flight-ring records, and serving traffic leaves all
    of that true — every hook site is one `is not None` check."""
    model, params = tiny_model
    fr = get_flight_recorder()
    threads_before = set(threading.enumerate())
    ring_before = fr.total_recorded
    engine = _engine(model, params, telemetry=False)
    assert engine.cache_telemetry is None
    assert engine.state_manager.cache_telemetry is None
    assert engine.state_manager.kv_cache._allocator.telemetry is None
    assert engine.prefix_cache._telemetry is None
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 128, size=40, dtype=np.int32)
    engine.put([1], [prompt], sample="greedy")
    engine.flush(1)
    engine.put([2], [prompt.copy()], sample="greedy")  # radix hit path
    engine.flush(2)
    assert fr.total_recorded == ring_before
    new = [t for t in set(threading.enumerate()) - threads_before if t.is_alive()]
    assert not new, f"telemetry-off engine spawned threads: {[t.name for t in new]}"


# ---------------------------------------------------------------------------
# HBM attribution (monitor/memory.py)
# ---------------------------------------------------------------------------

def test_hbm_report_sections_and_weakref_pruning(tiny_model):
    model, params = tiny_model
    engine = _engine(model, params, telemetry=False)
    sections = hbm_report()["sections"]
    assert sections.get("params", 0) >= tree_device_bytes(engine.params)
    assert sections.get("kv_block_pool", 0) \
        >= engine.state_manager.kv_cache.memory_bytes()
    before_params = sections["params"]
    del engine
    gc.collect()
    after = hbm_report()["sections"]
    # the discarded engine's weakly-owned provider pruned itself
    assert after.get("params", 0) < before_params or "params" not in after


def test_hbm_report_draft_engine_relabel(tiny_model):
    """A draft engine referenced by the target's speculative config re-files
    its bytes under ``spec_draft_engine`` — the sidecar cost is named, not
    folded into the primary params/kv rows."""
    model, params = tiny_model
    draft = _engine(model, params, telemetry=False)
    spec = SpeculativeConfig(mode="draft_model", k=2, draft_engine=draft)
    target = _engine(model, params, telemetry=False, speculative=spec)
    sections = hbm_report()["sections"]
    expect = tree_device_bytes(draft.params) + draft.state_manager.kv_cache.memory_bytes()
    assert sections.get("spec_draft_engine") == expect
    # the target still reports under the primary sections
    assert sections.get("params", 0) >= tree_device_bytes(target.params)


def test_memory_registry_unit():
    reg = get_memory()

    class Owner:
        pass

    o = Owner()
    reg.register("unit-test-a", lambda _o: {"params": 100, "other_pool": 7}, o)
    reg.register("unit-test-b", lambda _o: {"params": 11}, o)
    try:
        s = reg.sections()
        assert s["params"] >= 111 and s["other_pool"] >= 7
        rows = dict(((name, tuple(sorted(labels.items()))), v)
                    for name, labels, v in reg.gauge_rows())
        assert rows[("memory/hbm_bytes", (("section", "other_pool"),))] >= 7
    finally:
        reg.unregister("unit-test-a")
        reg.unregister("unit-test-b")


# ---------------------------------------------------------------------------
# /metrics + forensic-dump export through the health plane
# ---------------------------------------------------------------------------

def test_health_export_carries_mrc_and_memory(tiny_model, tmp_path):
    import urllib.request

    model, params = tiny_model
    h = get_health()
    h.configure(enabled=True, export_port=0, dump_dir=str(tmp_path),
                dump_on_destroy=False)
    try:
        engine = _engine(model, params)
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, 128, size=40, dtype=np.int32)
        engine.put([1], [prompt], sample="greedy")
        engine.flush(1)
        engine.put([2], [prompt.copy()], sample="greedy")
        engine.flush(2)
        body = urllib.request.urlopen(h.server.url + "/metrics", timeout=10) \
            .read().decode()
        # rows carry a per-engine label so multi-replica fleets don't collide
        assert 'dstpu_serving_mrc_hit_rate{capacity_mult="1",engine="' in body
        assert 'dstpu_cache_blocks{class="tree_only",engine="' in body
        assert "dstpu_cache_fragmentation" in body
        assert 'dstpu_memory_hbm_bytes{section="kv_block_pool"}' in body
        path = h.dump("cache_test")
        kinds = set()
        import json as _json

        with open(path) as f:
            for line in f:
                kinds.add(_json.loads(line).get("kind"))
        assert any(str(k).startswith("cache_telemetry-") for k in kinds)
        assert "memory" in kinds
    finally:
        h.shutdown()


# ---------------------------------------------------------------------------
# integration: the MRC live accuracy check (acceptance criterion)
# ---------------------------------------------------------------------------

def test_cache_pressure_mrc_accuracy():
    """The ``cache_pressure`` serving_load workload (Zipf corpus ~4x the
    block pool, sequential admission): the estimator's 1x prediction must
    land within 0.05 absolute of the measured full-block hit rate, under
    real eviction pressure."""
    from tools.serving_load import cache_pressure_bench

    out = cache_pressure_bench(n_requests=96, seed=0)
    assert out["evictions"] > 0, "the pressure workload must actually evict"
    assert out["measured_hit_rate"] is not None and out["mrc_predicted_1x"] is not None
    assert out["mrc_abs_err_1x"] <= 0.05, \
        f"MRC 1x prediction off by {out['mrc_abs_err_1x']} (measured " \
        f"{out['measured_hit_rate']}, predicted {out['mrc_predicted_1x']})"
    # the curve is monotone in capacity (more pool never hurts an LRU model)
    curve = [v for v in out["mrc"].values() if v is not None]
    assert curve == sorted(curve)
    assert out["evicted_tokens"] == out["evictions"] * out["block_size"]


# ---------------------------------------------------------------------------
# structural gate: metric-namespace discipline
# ---------------------------------------------------------------------------

def test_check_metric_names_gate():
    from tools.check_metric_names import check

    assert check() == []


def test_check_metric_names_catches_drift(tmp_path):
    from tools.check_metric_names import check

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "ok.py").write_text(
        'def f(reg, source):\n'
        '    reg.counter("cache/evictions").inc()\n'
        '    reg.gauge(f"health/stall_{source}_age").set(1)\n')
    (pkg / "bad.py").write_text(
        'def g(reg, name):\n'
        '    reg.counter("compile/events").inc()\n'          # off-prefix literal
        '    reg.gauge("serving/TTFT").set(0)\n'             # not snake_case
        '    reg.histogram(name).observe(1)\n'               # dynamic outside allowlist
        '    reg.histogram(f"{name}/x").observe(1)\n')       # dynamic prefix
    (pkg / "plumb.py").write_text(
        'def h(observe_latency):\n'
        '    observe_latency(0, "x", hist_name="data/wrong_ms")\n')
    bad = check(str(pkg))
    files = sorted(set(b[0] for b in bad))
    assert files == ["bad.py", "plumb.py"]
    assert len([b for b in bad if b[0] == "bad.py"]) == 4
    # the allowlisted plumbing modules may take dynamic names
    allowed = tmp_path / "pkg2"
    (allowed / "monitor").mkdir(parents=True)
    (allowed / "monitor" / "trace.py").write_text(
        "def f(reg, hist_name):\n    reg.histogram(hist_name).observe(1)\n")
    assert check(str(allowed)) == []
