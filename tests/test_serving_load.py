"""Continuous-batching load harness (tools/serving_load.py) — the repo's
FastGen-style rps/latency methodology (reference
``blogs/deepspeed-fastgen/README.md:139-144``). Correctness contract: both
policies drive the SAME engine greedily, so scheduling changes WHEN work
runs, never WHAT it computes — generations must match token-for-token."""

import numpy as np
import pytest

from tools.serving_load import (build_engine, make_multi_tenant_workload, make_shared_prefix_workload,
                                make_workload, run_splitfuse, run_static)


@pytest.fixture(scope="module")
def engine():
    return build_engine()


@pytest.fixture(scope="module")
def cache_engine():
    return build_engine(prefix_cache=True)


def _uniform(n, **kw):
    return make_workload(n, prompt_lo=4, prompt_hi=10, new_lo=2, new_hi=5, **kw)


def _shared_prefix(n, **kw):
    return make_shared_prefix_workload(n, n_prefixes=3, prefix_len=6, suffix_lo=1, suffix_hi=4, new_lo=2, new_hi=5, **kw)


def _multi_tenant(n, **kw):
    return make_multi_tenant_workload(n, n_tenants=3, prefix_len=6, suffix_lo=1, suffix_hi=4, new_lo=2, new_hi=5,
                                      hot_new_mult=1, **kw)


@pytest.mark.parametrize("make", [_uniform, _shared_prefix, _multi_tenant], ids=lambda f: f.__name__.strip("_"))
def test_workload_shapes_and_arrivals(make):
    wl = make(8, rate_rps=100.0, seed=3, uid_base=40)
    assert [r["uid"] for r in wl] == list(range(40, 48))
    arr = [r["arrival"] for r in wl]
    assert arr == sorted(arr) and arr[0] > 0
    assert all(4 <= r["prompt"].size <= 10 and r["prompt"].dtype == np.int32 for r in wl)
    assert all(2 <= r["max_new_tokens"] <= 5 for r in wl)
    # the same seed draws the same workload
    again = make(8, rate_rps=100.0, seed=3, uid_base=40)
    assert all(np.array_equal(a["prompt"], b["prompt"]) and a["arrival"] == b["arrival"] for a, b in zip(wl, again))
    # saturated mode: everything offered at t=0
    assert all(r["arrival"] == 0.0 for r in make(4, rate_rps=None))


def test_shared_prefix_workload_shares_inside_a_group_only():
    """A pooled prefix is shared by the requests that drew it; ``unique=True`` gives every request its own."""
    wl = _shared_prefix(40, seed=5)
    prefixes = {r["prompt"][:6].tobytes() for r in wl}
    assert 1 < len(prefixes) <= 3
    assert len({r["prompt"].tobytes() for r in wl}) > len(prefixes)  # the suffixes differ
    assert len({r["prompt"][:6].tobytes() for r in _shared_prefix(40, seed=5, unique=True)}) == 40


def test_multi_tenant_workload_names_a_tenant_a_row_and_the_hot_one_takes_its_share():
    wl = make_multi_tenant_workload(400, n_tenants=3, hot_share=0.4, hot_new_mult=2, new_lo=3, new_hi=8, seed=1)
    assert {r["tenant"] for r in wl} == {"t0", "t1", "t2", "hot"}
    hot = [r for r in wl if r["tenant"] == "hot"]
    assert 0.3 < len(hot) / len(wl) < 0.5
    # the hot tenant generates hot_new_mult times as long, the rest inside their bounds
    assert all(6 <= r["max_new_tokens"] <= 16 and r["max_new_tokens"] % 2 == 0 for r in hot)
    assert all(3 <= r["max_new_tokens"] <= 8 for r in wl if r["tenant"] != "hot")
    # a tenant's prompts open with one of ITS prefixes (two a tenant), and no other tenant's
    opens = {t: {r["prompt"][:24].tobytes() for r in wl if r["tenant"] == t} for t in ("t0", "t1", "t2", "hot")}
    assert all(1 <= len(v) <= 2 for v in opens.values())
    assert len(set.union(*opens.values())) == sum(len(v) for v in opens.values())


def test_splitfuse_and_static_generate_identical_tokens(engine):
    wl = make_workload(10, prompt_lo=6, prompt_hi=20, new_lo=3, new_hi=10,
                       rate_rps=None, seed=7, uid_base=0)
    sf_done, sf_span = run_splitfuse(engine, wl, token_budget=32)
    st_done, st_span = run_static(
        engine, [dict(r, uid=r["uid"] + 1000) for r in wl], batch_size=4)
    assert len(sf_done) == len(st_done) == 10
    assert sf_span > 0 and st_span > 0
    for r in wl:
        sf_lat, sf_toks = sf_done[r["uid"]]
        st_lat, st_toks = st_done[r["uid"] + 1000]
        assert len(sf_toks) == r["max_new_tokens"]
        assert sf_toks == st_toks, (
            f"uid {r['uid']}: splitfuse {sf_toks} != static {st_toks} — "
            "scheduling policy changed the computation")
        assert sf_lat > 0 and st_lat > 0
    # everything flushed: the engine is reusable for the next run
    assert engine.state_manager.n_tracked_sequences == 0


def test_open_loop_arrivals_respected(engine):
    """Open-loop mode: a request cannot finish before it arrives, and
    latencies are measured from ARRIVAL, not from harness start."""
    wl = make_workload(6, prompt_lo=4, prompt_hi=8, new_lo=2, new_hi=4,
                       rate_rps=50.0, seed=11, uid_base=100)
    done, span = run_splitfuse(engine, wl, token_budget=32)
    assert len(done) == 6
    assert span >= wl[-1]["arrival"]  # can't finish before the last arrival
    assert all(lat > 0 for lat, _ in done.values())
    assert engine.state_manager.n_tracked_sequences == 0


def test_shared_prefix_ab_zipf(engine, cache_engine):
    """The prefix-cache A/B acceptance (ISSUE 3): on the Zipf shared-prefix
    workload, cache-hit requests prefill ONLY their uncached suffix — >=2x
    reduction in prefill tokens computed, hit_rate > 0.5 — and the greedy
    generations stay token-identical to cache-off."""
    wl = make_shared_prefix_workload(20, n_prefixes=3, prefix_len=24, suffix_lo=4,
                                     suffix_hi=12, new_lo=3, new_hi=8,
                                     rate_rps=None, seed=5, uid_base=0)
    off_stats, on_stats = {}, {}
    off_done, _ = run_splitfuse(engine, wl, token_budget=48, stats_out=off_stats)
    on_done, _ = run_splitfuse(cache_engine, [dict(r, uid=r["uid"] + 500) for r in wl],
                               token_budget=48, stats_out=on_stats)
    for r in wl:
        assert off_done[r["uid"]][1] == on_done[r["uid"] + 500][1], \
            f"uid {r['uid']}: prefix cache changed the generation"
    pc = cache_engine.prefix_cache
    assert pc.hit_rate > 0.5, f"Zipf workload hit rate {pc.hit_rate}"
    assert off_stats["prefill_tokens_fed"] >= 2 * on_stats["prefill_tokens_fed"], \
        (off_stats, on_stats)
    assert on_stats["prefill_tokens_skipped"] == pc.stats["cached_tokens"]
    # reusable across tests: nothing tracked, pool = free + tree
    assert cache_engine.state_manager.n_tracked_sequences == 0
    assert (cache_engine.free_blocks + pc.n_cached_blocks
            == cache_engine.state_manager.kv_cache.total_blocks)


def test_shared_prefix_ab_all_unique(engine, cache_engine):
    """The adversarial control: an all-unique workload (no real reuse) must
    not change WHAT is computed — token parity holds and essentially no
    prefill is skipped (the deterministic proxy for 'throughput within
    noise of cache-off')."""
    cache_engine.prefix_cache.clear()
    wl = make_shared_prefix_workload(12, n_prefixes=3, prefix_len=24, suffix_lo=4,
                                     suffix_hi=12, new_lo=3, new_hi=6,
                                     rate_rps=None, seed=13, uid_base=2000, unique=True)
    off_stats, on_stats = {}, {}
    off_done, _ = run_splitfuse(engine, wl, token_budget=48, stats_out=off_stats)
    on_done, _ = run_splitfuse(cache_engine, [dict(r, uid=r["uid"] + 500) for r in wl],
                               token_budget=48, stats_out=on_stats)
    for r in wl:
        assert off_done[r["uid"]][1] == on_done[r["uid"] + 500][1]
    total_prompt = sum(r["prompt"].size for r in wl)
    # accidental few-token overlaps aside, the unique stream prefills ~all
    # of its prompt tokens with the cache on, exactly like cache-off
    assert off_stats["prefill_tokens_fed"] == total_prompt
    assert on_stats["prefill_tokens_fed"] >= 0.9 * total_prompt, (on_stats, total_prompt)


def test_scheduler_finished_property(engine):
    from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler

    sched = DynamicSplitFuseScheduler(engine, token_budget=32)
    rng = np.random.default_rng(0)
    sched.submit(900, rng.integers(0, 100, size=6, dtype=np.int32), max_new_tokens=2)
    sched.submit(901, rng.integers(0, 100, size=40, dtype=np.int32), max_new_tokens=8)
    assert sched.finished == frozenset()
    sched.run()
    assert sched.finished == frozenset({900, 901})
