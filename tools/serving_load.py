"""FastGen-style continuous-batching LOAD benchmark.

VERDICT r4 missing #3: the repo benched single-batch decode tok/s + TTFT,
but the reference's headline serving claim is SYSTEM throughput under load
(2.3x vLLM at the same latency, rps-vs-latency curves —
``/root/reference/blogs/deepspeed-fastgen/README.md:28,139-144``). This
harness measures exactly that, on the repo's own engine, policy vs policy:

  - **splitfuse**: :class:`DynamicSplitFuseScheduler` — decodes compose
    with chunked prefills every forward, arrivals admit continuously.
  - **static**: the classic static-batching server loop over the SAME
    engine — wait for a batch, prefill whole prompts, decode the batch to
    completion, only then admit the next batch (arrivals wait out the
    drain; heterogeneous generation lengths leave idle slots).

Both policies run the identical Poisson workload (same seed: same arrival
times, prompt lengths, generation lengths) and, being greedy over the same
engine, must produce identical tokens — scheduling changes WHEN work runs,
never WHAT it computes (asserted in tests/test_serving_load.py).

Output: one JSON line — a saturated-throughput comparison plus an
rps-vs-latency curve (p50/p95 per policy per offered rate).

PR 6 grew this harness a second face: a **closed-loop HTTP load
generator** over the serving gateway (``deepspeed_tpu/serving/``).
:func:`run_http_load` drives ``POST /v1/generate`` with a bounded worker
pool that HONORS the workload's arrival times (sleep-until-arrival — an
offered rate is a promise, not a timestamp column) and reports offered vs
achieved rate alongside client-side TTFT/TPOT percentiles and the shed
(429) rate, so a saturated point on the curve is visibly saturated instead
of silently self-pacing. :func:`gateway_latency_curves` sweeps offered
rates into latency-under-load curves and :func:`router_prefix_ab` runs the
prefix-aware-router vs random-placement A/B on the Zipf shared-prefix
workload (same engines, caches cleared between arms — strictly higher
aggregate hit rate is the acceptance bar). CLI: ``python
tools/serving_load.py gateway`` emits both as one JSON line.

PR 15 added the **multi-tenant** face: :func:`make_multi_tenant_workload`
(N Zipf-share tenants + one adversarial hot tenant, per-tenant prefix
pools, rows carry ``tenant`` → sent as ``X-Tenant-Id``) and
:func:`multi_tenant_bench` — closed-loop HTTP with the metering plane
armed, reporting the fairness index, per-tenant client-side TTFT/TPOT and
hit rates, and the hot tenant's compute share (``bench.py``'s
``tenants{...}`` block; CLI ``multi_tenant``).
"""

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_workload(n_requests, prompt_lo, prompt_hi, new_lo, new_hi, rate_rps, seed=0,
                  uid_base=0):
    """Poisson arrivals (exponential inter-arrival at ``rate_rps``), uniform
    prompt and generation lengths. ``rate_rps=None`` puts every arrival at
    t=0 (saturated / offered-load-infinity mode)."""
    rng = np.random.default_rng(seed)
    if rate_rps is None:
        arrivals = np.zeros(n_requests)
    else:
        arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, size=n_requests))
    work = []
    for i in range(n_requests):
        plen = int(rng.integers(prompt_lo, prompt_hi + 1))
        work.append({
            "uid": uid_base + i,
            "arrival": float(arrivals[i]),
            "prompt": rng.integers(0, 100, size=plen).astype(np.int32),
            "max_new_tokens": int(rng.integers(new_lo, new_hi + 1)),
        })
    return work


def make_shared_prefix_workload(n_requests, n_prefixes, prefix_len, suffix_lo, suffix_hi,
                                new_lo, new_hi, rate_rps=None, seed=0, uid_base=0,
                                zipf_a=1.2, unique=False):
    """Shared-prefix mode (the production shape prefix caching targets): a
    Zipf-sampled pool of ``n_prefixes`` system prompts, each request = one
    pooled prefix + a unique user suffix. ``unique=True`` gives every request
    its own prefix instead (the 0%-hit adversarial control for the A/B).
    Same arrival semantics as :func:`make_workload`."""
    rng = np.random.default_rng(seed)
    if rate_rps is None:
        arrivals = np.zeros(n_requests)
    else:
        arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, size=n_requests))
    pool = [rng.integers(0, 100, size=prefix_len).astype(np.int32) for _ in range(n_prefixes)]
    # Zipf ranks folded into the pool: rank 1 (the hottest system prompt)
    # dominates, the tail shares the rest — the head-heavy reuse profile of
    # real serving traffic
    ranks = (rng.zipf(zipf_a, size=n_requests) - 1) % n_prefixes
    work = []
    for i in range(n_requests):
        prefix = (rng.integers(0, 100, size=prefix_len).astype(np.int32) if unique
                  else pool[int(ranks[i])])
        suffix = rng.integers(0, 100, size=int(rng.integers(suffix_lo, suffix_hi + 1))).astype(np.int32)
        work.append({
            "uid": uid_base + i,
            "arrival": float(arrivals[i]),
            "prompt": np.concatenate([prefix, suffix]),
            "max_new_tokens": int(rng.integers(new_lo, new_hi + 1)),
        })
    return work


def make_multi_tenant_workload(n_requests, n_tenants=4, zipf_a=1.3,
                               hot_tenant="hot", hot_share=0.4,
                               n_prefixes_per_tenant=2, prefix_len=24,
                               suffix_lo=4, suffix_hi=10, new_lo=3, new_hi=8,
                               hot_new_mult=2, rate_rps=None, seed=0, uid_base=0):
    """Multi-tenant workload (the ISSUE 15 shape): ``n_tenants`` tenants
    with Zipf-skewed traffic shares plus ONE adversarial hot tenant taking
    ``hot_share`` of all requests with ``hot_new_mult``x longer generations
    — the starve-the-rest scenario the fairness observability exists to
    make visible. Each tenant owns its own small prefix pool (its few-shot
    templates), so per-tenant hit rates and cross-tenant hit attribution
    are both meaningful. Rows carry ``tenant`` (sent as ``X-Tenant-Id`` by
    the HTTP load generator); arrival semantics as :func:`make_workload`."""
    rng = np.random.default_rng(seed)
    if rate_rps is None:
        arrivals = np.zeros(n_requests)
    else:
        arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, size=n_requests))
    names = [f"t{i}" for i in range(n_tenants)]
    pools = {t: [rng.integers(0, 100, size=prefix_len).astype(np.int32)
                 for _ in range(n_prefixes_per_tenant)]
             for t in names + [hot_tenant]}
    ranks = (rng.zipf(zipf_a, size=n_requests) - 1) % n_tenants
    hot_mask = rng.random(n_requests) < hot_share
    work = []
    for i in range(n_requests):
        tenant = hot_tenant if hot_mask[i] else names[int(ranks[i])]
        prefix = pools[tenant][int(rng.integers(len(pools[tenant])))]
        suffix = rng.integers(0, 100, size=int(rng.integers(suffix_lo, suffix_hi + 1))).astype(np.int32)
        new = int(rng.integers(new_lo, new_hi + 1))
        if tenant == hot_tenant:
            new *= hot_new_mult
        work.append({
            "uid": uid_base + i,
            "arrival": float(arrivals[i]),
            "tenant": tenant,
            "prompt": np.concatenate([prefix, suffix]),
            "max_new_tokens": new,
        })
    return work


def run_splitfuse(engine, workload, token_budget=None, stats_out=None):
    """Open-loop load over DynamicSplitFuseScheduler. Returns
    ({uid: (latency_s, tokens)}, makespan_s). ``stats_out`` (a dict) receives
    the scheduler's prefill fed/skipped token counts when provided."""
    from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler

    sched = DynamicSplitFuseScheduler(engine, token_budget=token_budget)
    work = sorted(workload, key=lambda r: r["arrival"])
    n = len(work)
    done = {}
    seen_finished = set()
    i = 0
    t0 = time.time()
    while len(done) < n:
        now = time.time() - t0
        while i < n and work[i]["arrival"] <= now:
            r = work[i]
            sched.submit(r["uid"], r["prompt"], max_new_tokens=r["max_new_tokens"])
            i += 1
        if sched.has_work:
            processed = sched.step()
            if processed == 0 and i >= n:
                raise RuntimeError("splitfuse load stalled with arrivals exhausted")
        elif i < n:
            time.sleep(max(0.0, min(0.005, work[i]["arrival"] - (time.time() - t0))))
            continue
        t_now = time.time() - t0
        for uid in sched.finished - seen_finished:
            seen_finished.add(uid)
            done[uid] = t_now
    makespan = time.time() - t0
    results = sched.results
    if stats_out is not None:
        stats_out.update(sched.stats)
        if sched.speculating:
            stats_out["spec"] = dict(sched.spec_stats)
    arrival = {r["uid"]: r["arrival"] for r in work}
    return {u: (done[u] - arrival[u], results[u]) for u in done}, makespan


def run_static(engine, workload, batch_size, decode_horizon=32):
    """Classic static-batching server over the same engine mechanism: admit
    up to ``batch_size`` ARRIVED requests, prefill each whole prompt, decode
    the batch lock-step to completion, flush, repeat. Later arrivals wait
    out the entire drain — the bubble Dynamic SplitFuse removes."""
    work = sorted(workload, key=lambda r: r["arrival"])
    n = len(work)
    done = {}
    queue = []
    i = 0
    t0 = time.time()
    while len(done) < n:
        now = time.time() - t0
        while i < n and work[i]["arrival"] <= now:
            queue.append(work[i])
            i += 1
        if not queue:
            time.sleep(max(0.0, min(0.005, work[i]["arrival"] - (time.time() - t0))))
            continue
        batch = queue[:batch_size]
        del queue[:batch_size]
        gen = {}
        remaining = {}
        for r in batch:  # whole-prompt prefill, one sequence per put
            tok = engine.put([r["uid"]], [r["prompt"]], sample="greedy")
            gen[r["uid"]] = [int(np.asarray(tok).reshape(-1)[0])]
            remaining[r["uid"]] = r["max_new_tokens"] - 1
        # textbook static batching: the WHOLE batch decodes lock-step until
        # the LONGEST request finishes — already-finished slots keep burning
        # decode steps whose tokens are discarded (the idle-slot bubble that
        # Dynamic SplitFuse removes), and arrivals wait out the drain
        uids = [r["uid"] for r in batch]
        steps_left = max(remaining.values())
        while steps_left > 0:
            h = min(decode_horizon, steps_left)
            h = 1 << (h.bit_length() - 1)  # power-of-two horizons: bounded compiles
            toks = np.asarray(engine.decode(
                uids, [np.asarray([gen[u][-1]], np.int32) for u in uids], h))
            for u, row in zip(uids, toks):
                take = min(h, remaining[u])
                gen[u].extend(int(t) for t in row[:take])
                remaining[u] -= take
            steps_left -= h
        t_done = time.time() - t0
        for r in batch:
            engine.flush(r["uid"])
            done[r["uid"]] = (t_done - r["arrival"], gen[r["uid"]])
    return done, time.time() - t0


def _latency_stats(done):
    lats = np.asarray([v[0] for v in done.values()])
    return {"p50_ms": round(float(np.percentile(lats, 50)) * 1000, 1),
            "p95_ms": round(float(np.percentile(lats, 95)) * 1000, 1)}


def build_engine(on_tpu, prefix_cache=False, speculative=None, host_blocks=None):
    import jax.numpy as jnp
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, HostTierConfig,
                                            InferenceEngineV2, PrefixCacheConfig,
                                            RaggedInferenceEngineConfig)

    if on_tpu:
        cfg = TransformerConfig(vocab_size=32000, hidden_size=2048, num_layers=12,
                                num_heads=16, num_kv_heads=16, intermediate_size=5632,
                                max_seq_len=2048, norm="rmsnorm", positions="rotary",
                                mlp="swiglu", dtype=jnp.bfloat16, attention_impl="flash")
        sm = DSStateManagerConfig(max_tracked_sequences=32, max_ragged_batch_size=512,
                                  max_ragged_sequence_count=32, max_context=768)
        icfg = RaggedInferenceEngineConfig(kv_block_size=128, num_kv_blocks=224,
                                           kv_dtype="int8", state_manager=sm)
    else:
        cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                                num_kv_heads=2, intermediate_size=128, max_seq_len=256,
                                dtype=jnp.float32, attention_impl="reference")
        sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                                  max_ragged_sequence_count=8, max_context=64)
        icfg = RaggedInferenceEngineConfig(kv_block_size=8, num_kv_blocks=80,
                                           kv_dtype=jnp.float32, state_manager=sm,
                                           use_pallas_kernels="never")
    # host_blocks arms the pinned host tier (required transport for the
    # disaggregated KV handoff — install_prefix_kv adopts host-tier nodes)
    icfg.prefix_cache = PrefixCacheConfig(
        enabled=bool(prefix_cache) or host_blocks is not None,
        host_tier=(HostTierConfig(host_blocks=int(host_blocks))
                   if host_blocks else None))
    if speculative is not None:
        icfg.speculative = speculative
    return InferenceEngineV2(TransformerLM(cfg), icfg)


def serving_load_bench(on_tpu, n_requests=None, seed=0):
    """Full comparison: saturated throughput + rps/latency curve. Returns the
    result dict (also usable from bench_ladder)."""
    engine = build_engine(on_tpu)
    if on_tpu:
        n = n_requests or 64
        shape = dict(prompt_lo=128, prompt_hi=448, new_lo=32, new_hi=128)
        static_bs, budget = 16, 512
        rate_mults = (0.5, 1.0, 2.0)
    else:
        n = n_requests or 16
        shape = dict(prompt_lo=8, prompt_hi=24, new_lo=4, new_hi=12)
        static_bs, budget = 4, 32
        rate_mults = (1.0,)

    # warmup pass compiles every batch-shape bucket both policies touch, so
    # the measured passes time scheduling, not XLA compiles
    warm = make_workload(n, rate_rps=None, seed=seed, uid_base=0, **shape)
    run_splitfuse(engine, warm, token_budget=budget)
    run_static(engine, warm, static_bs)

    # --- saturated: all requests offered at t=0; throughput = N / makespan ---
    sat = make_workload(n, rate_rps=None, seed=seed, uid_base=10_000, **shape)
    sf_done, sf_span = run_splitfuse(engine, sat, token_budget=budget)
    st_done, st_span = run_static(
        engine, [dict(r, uid=r["uid"] + 10_000) for r in sat], static_bs)
    sf_rps, st_rps = n / sf_span, n / st_span
    result = {
        "config": "fastgen_splitfuse_vs_static",
        "n_requests": n,
        "saturated": {"splitfuse_rps": round(sf_rps, 2), "static_rps": round(st_rps, 2),
                      "speedup": round(sf_rps / st_rps, 3)},
        "curve": [],
    }

    # --- open-loop curve: offered rates around splitfuse's saturated rps ---
    for mi, mult in enumerate(rate_mults):
        rate = sf_rps * mult
        wl = make_workload(n, rate_rps=rate, seed=seed + 1 + mi,
                           uid_base=50_000 + 20_000 * mi, **shape)
        sf_d, sf_s = run_splitfuse(engine, wl, token_budget=budget)
        st_d, st_s = run_static(
            engine, [dict(r, uid=r["uid"] + 10_000) for r in wl], static_bs)
        result["curve"].append({
            "offered_rps": round(rate, 2),
            "splitfuse": dict(rps=round(n / sf_s, 2), **_latency_stats(sf_d)),
            "static": dict(rps=round(n / st_s, 2), **_latency_stats(st_d)),
        })
    return result


def shared_prefix_ab(on_tpu, n_requests=None, seed=0):
    """Prefix-cache A/B on the Zipf shared-prefix workload: the same request
    stream runs cache-off then cache-on (greedy → token-identical, asserted
    in tests/test_serving_load.py), plus an all-unique control where a 0%
    hit rate must cost nothing. Cache-on prefills only the uncached suffix —
    the ``prefill_tokens_fed`` reduction is the mechanism behind the TTFT /
    throughput win, counted exactly at the feed site."""
    if on_tpu:
        n = n_requests or 48
        shape = dict(n_prefixes=6, prefix_len=384, suffix_lo=16, suffix_hi=96,
                     new_lo=16, new_hi=64)
        budget = 512
    else:
        n = n_requests or 20
        shape = dict(n_prefixes=3, prefix_len=24, suffix_lo=4, suffix_hi=12,
                     new_lo=3, new_hi=8)
        budget = 48

    result = {"config": "prefix_cache_ab", "n_requests": n, "workloads": {}}
    for wl_name, unique in (("zipf_shared", False), ("all_unique", True)):
        wl = make_shared_prefix_workload(n, rate_rps=None, seed=seed, uid_base=0,
                                         unique=unique, **shape)
        line = {}
        for cache_on in (False, True):
            engine = build_engine(on_tpu, prefix_cache=cache_on)
            # warmup compiles the shape buckets so the measured pass times
            # scheduling + (with the cache) skipped prefill, not XLA
            run_splitfuse(engine, [dict(r, uid=r["uid"] + 90_000) for r in wl],
                          token_budget=budget)
            if cache_on:
                engine.prefix_cache.clear()
                engine.prefix_cache.stats.update({k: 0 for k in engine.prefix_cache.stats})
            stats = {}
            done, span = run_splitfuse(engine, wl, token_budget=budget, stats_out=stats)
            key = "cache_on" if cache_on else "cache_off"
            line[key] = {"rps": round(n / span, 2), **_latency_stats(done),
                         "prefill_tokens_fed": stats["prefill_tokens_fed"],
                         "prefill_tokens_skipped": stats["prefill_tokens_skipped"]}
            if cache_on:
                pc = engine.prefix_cache
                line[key]["hit_rate"] = round(pc.hit_rate, 3)
                line[key]["cached_tokens"] = pc.stats["cached_tokens"]
                line[key]["cow_copies"] = pc.stats["cow_copies"]
                line[key]["evictions"] = pc.stats["evictions"]
            line.setdefault("tokens", {})[key] = {u: t for u, (_, t) in sorted(done.items())}
        parity = line["tokens"]["cache_on"] == line["tokens"]["cache_off"]
        del line["tokens"]  # bulky; the bit that matters is the verdict
        line["token_parity"] = parity
        off, on = line["cache_off"], line["cache_on"]
        line["prefill_reduction"] = round(off["prefill_tokens_fed"] /
                                          max(1, on["prefill_tokens_fed"]), 2)
        result["workloads"][wl_name] = line
    return result


def cache_pressure_bench(on_tpu, n_requests=None, seed=0, corpus_mult=4.0):
    """Cache-pressure workload + the MRC estimator's live accuracy check
    (ISSUE 11): a Zipf shared-prefix corpus deliberately sized at
    ``corpus_mult``x the KV block pool, so the radix tree runs under real
    eviction pressure, driven ONE REQUEST AT A TIME (the router_prefix_ab
    discipline: each request's prefix is published before the next looks
    up, so hit accounting measures CACHE behavior, not racing admissions —
    which is also the reference-stream model the estimator assumes).

    Reports the measured full-block hit rate vs the estimator's predicted
    hit rate at 1x capacity (``mrc_abs_err_1x`` is the acceptance metric:
    within 0.05 absolute, asserted in tests/test_cache_telemetry.py), the
    full predicted curve at {0.5x..8x}, the block-lifecycle snapshot
    (block age, eviction-victim age, fragmentation), and the process HBM
    attribution while the engine is live."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.inference.v2 import (CacheTelemetryConfig, DSStateManagerConfig,
                                            DynamicSplitFuseScheduler, InferenceEngineV2,
                                            PrefixCacheConfig, RaggedInferenceEngineConfig)
    from deepspeed_tpu.monitor.memory import hbm_report

    if on_tpu:
        n = n_requests or 128
        cfg = TransformerConfig(vocab_size=32000, hidden_size=1024, num_layers=6,
                                num_heads=8, num_kv_heads=8, intermediate_size=2816,
                                max_seq_len=2048, norm="rmsnorm", positions="rotary",
                                mlp="swiglu", dtype=jnp.bfloat16, attention_impl="flash")
        sm = DSStateManagerConfig(max_tracked_sequences=16, max_ragged_batch_size=512,
                                  max_ragged_sequence_count=16, max_context=768)
        block, pool = 128, 96
        shape = dict(prefix_len=512, suffix_lo=16, suffix_hi=64, new_lo=8, new_hi=32)
        budget = 512
    else:
        n = n_requests or 96
        cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                                num_kv_heads=2, intermediate_size=128, max_seq_len=256,
                                dtype=jnp.float32, attention_impl="reference")
        sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                                  max_ragged_sequence_count=8, max_context=64)
        block, pool = 8, 48
        shape = dict(prefix_len=40, suffix_lo=4, suffix_hi=10, new_lo=3, new_hi=6)
        budget = 64
    # corpus sized at corpus_mult x the pool: reuse only survives eviction
    # for the Zipf head, exactly the regime the MRC exists to size
    pool_tokens = pool * block
    n_prefixes = max(2, int(round(corpus_mult * pool_tokens / shape["prefix_len"])))
    icfg = RaggedInferenceEngineConfig(
        kv_block_size=block, num_kv_blocks=pool,
        kv_dtype="int8" if on_tpu else jnp.float32, state_manager=sm,
        use_pallas_kernels="auto" if on_tpu else "never",
        prefix_cache=PrefixCacheConfig(
            enabled=True,
            # the CPU smoke trace is a few hundred chunk refs over a 48-block
            # pool — SHARDS sampling noise at that scale swamps the signal,
            # so the smoke tracks every chunk (the sampled path is validated
            # against exact LRU in tests/test_cache_telemetry.py); at TPU
            # scale the trace is long enough for the production sample rate
            telemetry=CacheTelemetryConfig(enabled=True,
                                           mrc_sample_rate=0.25 if on_tpu else 1.0)))
    engine = InferenceEngineV2(TransformerLM(cfg), icfg)
    tel = engine.cache_telemetry
    wl = make_shared_prefix_workload(n, n_prefixes=n_prefixes, rate_rps=None,
                                     seed=seed, uid_base=0, zipf_a=1.2, **shape)
    # warmup compiles the shape buckets on an all-unique stream, then the
    # measured pass starts from a cold, zeroed cache
    warm = make_shared_prefix_workload(max(4, n // 8), n_prefixes=n_prefixes,
                                       rate_rps=None, seed=seed + 7, uid_base=90_000,
                                       unique=True, **shape)
    sched = DynamicSplitFuseScheduler(engine, token_budget=budget)
    for r in warm:
        sched.submit(r["uid"], r["prompt"], max_new_tokens=r["max_new_tokens"])
        sched.run()
    engine.prefix_cache.clear()
    engine.prefix_cache.stats.update({k: 0 for k in engine.prefix_cache.stats})
    tel.reset()

    t0 = time.time()
    for r in wl:  # strictly sequential: publish-before-next-lookup
        sched.submit(r["uid"], r["prompt"], max_new_tokens=r["max_new_tokens"])
        sched.run()
    span = time.time() - t0

    pc = engine.prefix_cache
    snap = tel.snapshot()
    measured = tel.mrc.observed_hit_rate
    predicted_1x = tel.mrc.predict().get(1.0)
    result = {
        "config": "cache_pressure",
        "n_requests": n,
        "corpus_mult": corpus_mult,
        "n_prefixes": n_prefixes,
        "pool_blocks": pool,
        "block_size": block,
        "rps": round(n / span, 2),
        # the live accuracy check: the estimator's 1x prediction vs the real
        # cache's full-block hit rate over the SAME reference stream
        "measured_hit_rate": round(measured, 4) if measured is not None else None,
        "mrc_predicted_1x": round(predicted_1x, 4) if predicted_1x is not None else None,
        "mrc_abs_err_1x": (round(abs(measured - predicted_1x), 4)
                           if measured is not None and predicted_1x is not None else None),
        "mrc": snap["mrc"],
        "request_hit_rate": round(pc.hit_rate, 4),
        "evictions": pc.stats["evictions"],
        "evicted_tokens": pc.stats["evicted_tokens"],
        "cow_copies": pc.stats["cow_copies"],
        "cow_bytes": pc.stats["cow_bytes"],
        "telemetry": snap,
        # HBM attribution while the engine is live: the bench's memory{...}
        "memory": hbm_report(),
    }
    return result


def host_tier_ab(on_tpu, n_requests=None, seed=0, corpus_mult=10.0):
    """Tiered KV-cache A/B (ISSUE 17): the cache_pressure Zipf corpus sized
    at ``corpus_mult``x (~10x) the HBM block pool, run once HBM-only and once
    with the pinned host tier armed, one request at a time. The tier arm's
    eviction victims demote to host instead of dropping, so a re-referenced
    Zipf-head prefix that HBM alone would have lost comes back as a
    promoted hit. Reports the hierarchy hit rate vs the HBM-only hit rate
    (acceptance: strictly above, with greedy token parity), promotion
    latency p50/p99, and TTFT split by how the prefix was served
    (promoted hit vs outright miss) — the user-visible cost of an H2D
    restore vs recomputing the prefill."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.inference.v2 import (CacheTelemetryConfig, DSStateManagerConfig,
                                            DynamicSplitFuseScheduler, HostTierConfig,
                                            InferenceEngineV2, PrefixCacheConfig,
                                            RaggedInferenceEngineConfig)

    if on_tpu:
        n = n_requests or 128
        cfg = TransformerConfig(vocab_size=32000, hidden_size=1024, num_layers=6,
                                num_heads=8, num_kv_heads=8, intermediate_size=2816,
                                max_seq_len=2048, norm="rmsnorm", positions="rotary",
                                mlp="swiglu", dtype=jnp.bfloat16, attention_impl="flash")
        sm = DSStateManagerConfig(max_tracked_sequences=16, max_ragged_batch_size=512,
                                  max_ragged_sequence_count=16, max_context=768)
        # host = 3x pool: hierarchy capacity lands exactly on the MRC's 4.0x
        # multiplier, so the curve's prediction is directly comparable
        block, pool, host_blocks = 128, 96, 288
        shape = dict(prefix_len=512, suffix_lo=16, suffix_hi=64, new_lo=8, new_hi=32)
        budget = 512
    else:
        n = n_requests or 64
        cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                                num_kv_heads=2, intermediate_size=128, max_seq_len=256,
                                dtype=jnp.float32, attention_impl="reference")
        sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                                  max_ragged_sequence_count=8, max_context=64)
        block, pool, host_blocks = 8, 48, 144  # hierarchy = 4.0x the HBM pool
        shape = dict(prefix_len=40, suffix_lo=4, suffix_hi=10, new_lo=3, new_hi=6)
        budget = 64
    pool_tokens = pool * block
    n_prefixes = max(2, int(round(corpus_mult * pool_tokens / shape["prefix_len"])))
    wl = make_shared_prefix_workload(n, n_prefixes=n_prefixes, rate_rps=None,
                                     seed=seed, uid_base=0, zipf_a=1.2, **shape)
    result = {"config": "host_tier_ab", "n_requests": n, "corpus_mult": corpus_mult,
              "n_prefixes": n_prefixes, "pool_blocks": pool, "block_size": block,
              "host_blocks": host_blocks}
    tokens_by_arm = {}
    for arm, tier_on in (("hbm_only", False), ("host_tier", True)):
        pc_cfg = PrefixCacheConfig(
            enabled=True,
            telemetry=CacheTelemetryConfig(enabled=True,
                                           mrc_sample_rate=0.25 if on_tpu else 1.0),
            host_tier=(HostTierConfig(host_blocks=host_blocks) if tier_on else None))
        icfg = RaggedInferenceEngineConfig(
            kv_block_size=block, num_kv_blocks=pool,
            kv_dtype="int8" if on_tpu else jnp.float32, state_manager=sm,
            use_pallas_kernels="auto" if on_tpu else "never", prefix_cache=pc_cfg)
        engine = InferenceEngineV2(TransformerLM(cfg), icfg)
        sched = DynamicSplitFuseScheduler(engine, token_budget=budget)
        pc = engine.prefix_cache
        # warmup compiles shape buckets on an all-unique stream, then the
        # measured pass starts from a cold cache (cache_pressure discipline)
        warm = make_shared_prefix_workload(max(4, n // 8), n_prefixes=n_prefixes,
                                           rate_rps=None, seed=seed + 7,
                                           uid_base=90_000, unique=True, **shape)
        for r in warm:
            sched.submit(r["uid"], r["prompt"], max_new_tokens=r["max_new_tokens"])
            sched.run()
        pc.clear()
        pc.stats.update({k: 0 for k in pc.stats})
        if engine.cache_telemetry is not None:
            engine.cache_telemetry.reset()

        ttft_by_class = {"promoted_hit": [], "hbm_hit": [], "miss": []}
        t0 = time.time()
        for r in wl:  # strictly sequential: publish-before-next-lookup
            h0, p0 = pc.stats["hits"], pc.stats["promotions"]
            sched.submit(r["uid"], r["prompt"], max_new_tokens=r["max_new_tokens"])
            t_req = time.perf_counter()
            # step until the first generated token lands: TTFT under the
            # same split-fuse budget the throughput arm uses
            while sched.has_work and not sched.new_tokens(r["uid"], 0):
                sched.step()
            ttft_ms = (time.perf_counter() - t_req) * 1e3
            sched.run()
            cls = ("promoted_hit" if pc.stats["promotions"] > p0
                   else "hbm_hit" if pc.stats["hits"] > h0 else "miss")
            ttft_by_class[cls].append(ttft_ms)
        span = time.time() - t0

        line = {"rps": round(n / span, 2),
                "hit_rate": round(pc.hit_rate, 4),
                "cached_tokens": pc.stats["cached_tokens"],
                "evictions": pc.stats["evictions"],
                "requests_by_class": {c: len(v) for c, v in ttft_by_class.items()},
                "ttft_miss_ms": _percentiles(ttft_by_class["miss"]),
                "ttft_hbm_hit_ms": _percentiles(ttft_by_class["hbm_hit"])}
        if tier_on:
            # the headline: what fraction of lookups ANY tier could serve
            line["hierarchy_hit_rate"] = round(pc.hit_rate, 4)
            line["demotions"] = pc.stats["demotions_queued"]
            line["promotions"] = pc.stats["promotions"]
            line["promoted_tokens"] = pc.stats["promoted_tokens"]
            line["ttft_promoted_hit_ms"] = _percentiles(ttft_by_class["promoted_hit"])
            tel = engine.cache_telemetry
            if tel is not None:
                tiers = tel.snapshot().get("tiers", {})
                plat = tiers.get("promote_latency_s") or {}
                line["promote_p50_ms"] = (round(plat["p50"] * 1e3, 3)
                                          if plat.get("p50") is not None else None)
                line["promote_p99_ms"] = (round(plat["p99"] * 1e3, 3)
                                          if plat.get("p99") is not None else None)
                line["host_occupancy_integral_s"] = tiers.get(
                    "host_occupancy_integral_s")
                # the MRC's live accuracy check, one tier up (ISSUE 17
                # acceptance): the curve's prediction at the HIERARCHY's
                # capacity multiplier vs the measured hierarchy (HBM+host)
                # block hit rate over the same reference stream
                mult = (pool + host_blocks) / pool
                pred = tel.mrc.predict().get(mult)
                meas = tel.mrc.observed_hit_rate
                line["mrc_hierarchy_mult"] = mult
                line["mrc_predicted_hierarchy"] = (round(pred, 4)
                                                   if pred is not None else None)
                line["measured_hierarchy_block_hit_rate"] = (
                    round(meas, 4) if meas is not None else None)
                line["mrc_hierarchy_abs_err"] = (
                    round(abs(meas - pred), 4)
                    if meas is not None and pred is not None else None)
            line["tier"] = engine.tiered_store.snapshot()
        else:
            line["hbm_hit_rate"] = round(pc.hit_rate, 4)
        tokens_by_arm[arm] = {u: t for u, t in sorted(sched.results.items())}
        result[arm] = line
        engine.shutdown()
    result["token_parity"] = tokens_by_arm["hbm_only"] == tokens_by_arm["host_tier"]
    result["hit_rate_gain"] = round(result["host_tier"]["hit_rate"]
                                    - result["hbm_only"]["hit_rate"], 4)
    return result


def speculative_ab(on_tpu, n_requests=None, seed=0, k=4, mode="ngram", min_match=None,
                   tree_width=1):
    """Speculative-decoding A/B on the Zipf shared-prefix workload: the same
    request stream runs spec-off then spec-on (greedy → token-identical,
    asserted here and in tests/test_speculative.py). Decode tok/s counts
    GENERATED tokens over the run's wall clock — prefill is identical across
    arms, so the delta is the decode plane. The acceptance rate is the
    lever: each verify forward commits ``accepted + 1`` tokens for one host
    round-trip, so higher acceptance directly multiplies tokens-per-step;
    the tradeoff knob is ``k`` (bigger K amortizes more per accepted run,
    wastes more verify compute when acceptance is low)."""
    from deepspeed_tpu.inference.v2 import SpeculativeConfig

    if on_tpu:
        n = n_requests or 32
        shape = dict(n_prefixes=4, prefix_len=256, suffix_lo=16, suffix_hi=64,
                     new_lo=48, new_hi=96)
        budget = 512
        min_match = 2 if min_match is None else min_match
    else:
        n = n_requests or 12
        shape = dict(n_prefixes=3, prefix_len=24, suffix_lo=4, suffix_hi=10,
                     new_lo=18, new_hi=28)
        budget = 48
        # the CPU smoke model's greedy streams are short and only weakly
        # periodic — a unigram trigger keeps the drafter firing so the A/B
        # measures a real acceptance rate instead of drafting silence
        min_match = 1 if min_match is None else min_match

    wl = make_shared_prefix_workload(n, rate_rps=None, seed=seed, uid_base=0, **shape)
    result = {"config": "speculative_ab", "n_requests": n, "k": k, "mode": mode,
              "min_match": min_match, "tree_width": int(tree_width)}
    tokens = {}
    for spec_on in (False, True):
        spec = SpeculativeConfig(mode=mode, k=k, min_match=min_match,
                                 tree_width=int(tree_width)) if spec_on else None
        engine = build_engine(on_tpu, prefix_cache=True, speculative=spec)
        # warmup compiles every bucket (incl. the verify bucket) so the
        # measured pass times scheduling + speculation, not XLA
        run_splitfuse(engine, [dict(r, uid=r["uid"] + 90_000) for r in wl],
                      token_budget=budget)
        engine.prefix_cache.clear()
        engine.prefix_cache.stats.update({s: 0 for s in engine.prefix_cache.stats})
        stats = {}
        done, span = run_splitfuse(engine, wl, token_budget=budget, stats_out=stats)
        gen_tokens = sum(len(t) for _, t in done.values())
        key = "spec_on" if spec_on else "spec_off"
        result[key] = {"decode_tok_s": round(gen_tokens / span, 1),
                       "rps": round(n / span, 2), **_latency_stats(done)}
        tokens[key] = {u: t for u, (_, t) in sorted(done.items())}
        if spec_on:
            sp = stats.get("spec", {})
            result["accept_rate"] = round(sp.get("accepted", 0) / max(1, sp.get("drafted", 0)), 3)
            result["spec_rounds"] = sp.get("rounds", 0)
            result["drafted_tokens"] = sp.get("drafted", 0)
            result["accepted_tokens"] = sp.get("accepted", 0)
    result["token_parity"] = tokens["spec_on"] == tokens["spec_off"]
    result["decode_tok_s_off"] = result["spec_off"]["decode_tok_s"]
    result["decode_tok_s_on"] = result["spec_on"]["decode_tok_s"]
    result["speedup"] = round(result["decode_tok_s_on"] /
                              max(1e-9, result["decode_tok_s_off"]), 3)
    return result


def speculative_sweep(on_tpu, ks=None, widths=None, modes=("ngram", ), n_requests=None,
                      seed=0):
    """K × tree-width sweep over the Zipf shared-prefix workload with
    per-drafter-mode accept-rate reporting: one shared spec-off baseline,
    then one spec-on arm per (mode, k, width) cell — the grid that answers
    "is the extra verify compute of deeper drafts / wider trees paying for
    itself on THIS traffic". Greedy token parity is asserted in every cell
    (each arm replays the identical request stream)."""
    from deepspeed_tpu.inference.v2 import SpeculativeConfig

    ks = tuple(ks or ((2, 4, 8) if on_tpu else (2, 4)))
    widths = tuple(widths or ((1, 2, 4) if on_tpu else (1, 2)))
    if on_tpu:
        n = n_requests or 16
        shape = dict(n_prefixes=4, prefix_len=256, suffix_lo=16, suffix_hi=64,
                     new_lo=48, new_hi=96)
        budget, min_match = 512, 2
    else:
        n = n_requests or 8
        shape = dict(n_prefixes=3, prefix_len=24, suffix_lo=4, suffix_hi=10,
                     new_lo=14, new_hi=22)
        budget, min_match = 48, 1
    wl = make_shared_prefix_workload(n, rate_rps=None, seed=seed, uid_base=0, **shape)

    def run_arm(spec):
        engine = build_engine(on_tpu, prefix_cache=True, speculative=spec)
        run_splitfuse(engine, [dict(r, uid=r["uid"] + 90_000) for r in wl],
                      token_budget=budget)  # warmup: compile every bucket
        engine.prefix_cache.clear()
        engine.prefix_cache.stats.update({s: 0 for s in engine.prefix_cache.stats})
        stats = {}
        done, span = run_splitfuse(engine, wl, token_budget=budget, stats_out=stats)
        gen = sum(len(t) for _, t in done.values())
        return ({u: t for u, (_, t) in sorted(done.items())},
                round(gen / span, 1), stats.get("spec", {}))

    base_tokens, base_tok_s, _ = run_arm(None)
    grid = []
    for mode in modes:
        for k in ks:
            for w in widths:
                toks, tok_s, sp = run_arm(SpeculativeConfig(
                    mode=mode, k=k, min_match=min_match, tree_width=w))
                grid.append({
                    "mode": mode, "k": int(k), "tree_width": int(w),
                    "accept_rate": round(sp.get("accepted", 0) / max(1, sp.get("drafted", 0)), 3),
                    "drafted": sp.get("drafted", 0), "accepted": sp.get("accepted", 0),
                    "rounds": sp.get("rounds", 0), "backoffs": sp.get("backoffs", 0),
                    "decode_tok_s": tok_s,
                    "speedup": round(tok_s / max(1e-9, base_tok_s), 3),
                    "token_parity": toks == base_tokens,
                })
    by_mode = {m: max((c["accept_rate"] for c in grid if c["mode"] == m), default=0.0)
               for m in modes}
    return {"config": "speculative_sweep", "n_requests": n,
            "decode_tok_s_off": base_tok_s, "grid": grid,
            "best_accept_rate_by_mode": by_mode,
            "all_parity": all(c["token_parity"] for c in grid)}


# ---------------------------------------------------------------------------
# gateway plane: closed-loop HTTP load generation + router A/B
# ---------------------------------------------------------------------------
def _percentiles(vals, keys=(50, 99)):
    if not vals:
        return {f"p{k}_ms": None for k in keys}
    arr = np.asarray(vals)
    return {f"p{k}_ms": round(float(np.percentile(arr, k)), 1) for k in keys}


def _http_generate(host, port, r, stream, timeout_s, slo_class):
    """One ``POST /v1/generate`` with client-side TTFT/TPOT timestamps."""
    import http.client

    body = {"prompt": np.asarray(r["prompt"]).tolist(),
            "max_new_tokens": int(r["max_new_tokens"]), "stream": bool(stream)}
    # a per-row slo_class (mixed-class workloads, e.g. control_ab) beats the
    # call-level default
    cls = r.get("slo_class") or slo_class
    if cls:
        body["slo_class"] = cls
    rec = {"uid": r["uid"], "status": None, "tokens": [], "ttft_ms": None,
           "tpot_ms": None, "latency_ms": None, "error": None,
           "request_id": None, "retry_after": None, "tenant": r.get("tenant"),
           "slo_class": cls}
    t_send = time.time()
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    try:
        # a client-supplied id keyed on the workload uid: request-log lines
        # and trace spans join back to the workload row by inspection; a
        # workload row carrying a tenant sends it as X-Tenant-Id (the
        # metering identity)
        headers = {"Content-Type": "application/json",
                   "X-Request-Id": f"load-{r['uid']}"}
        if r.get("tenant"):
            headers["X-Tenant-Id"] = str(r["tenant"])
        conn.request("POST", "/v1/generate", json.dumps(body), headers)
        resp = conn.getresponse()
        rec["status"] = resp.status
        rec["request_id"] = resp.getheader("X-Request-Id")
        rec["retry_after"] = resp.getheader("Retry-After")
        if resp.status != 200:
            payload = json.loads(resp.read() or b"{}")
            rec["error"] = payload.get("error")
            return rec
        if not stream:
            payload = json.loads(resp.read())
            rec["tokens"] = payload["tokens"]
            rec["error"] = payload.get("error")
            rec["ttft_ms"] = payload.get("ttft_ms")  # server-side (no frames)
            rec["tpot_ms"] = payload.get("tpot_ms")
            return rec
        # incremental SSE read: the response closes when the stream ends
        # (HTTP/1.0 semantics), so readline() yields frames as they arrive —
        # client-side token timestamps are the honest TTFT/TPOT
        token_times = []
        ev_lines = []
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.rstrip(b"\r\n")
            if line:
                ev_lines.append(line)
                continue
            if not ev_lines:
                continue
            datas = [ln[5:].lstrip() for ln in ev_lines if ln.startswith(b"data:")]
            ev_lines = []
            if not datas:
                continue
            ev = json.loads(b"\n".join(datas))
            if "token" in ev:
                token_times.append(time.time())
                rec["tokens"].append(ev["token"])
            elif ev.get("done"):
                rec["error"] = ev.get("error")
        if token_times:
            rec["ttft_ms"] = (token_times[0] - t_send) * 1e3
            if len(token_times) > 1:
                rec["tpot_ms"] = ((token_times[-1] - token_times[0])
                                  / (len(token_times) - 1) * 1e3)
        return rec
    except Exception as e:  # noqa: BLE001 — the harness reports, never dies
        rec["error"] = f"{type(e).__name__}: {e}"
        return rec
    finally:
        conn.close()
        rec["latency_ms"] = (time.time() - t_send) * 1e3


def run_http_load(host, port, workload, concurrency=8, stream=True,
                  timeout_s=120.0, slo_class=None):
    """Closed-loop HTTP load over a running gateway: ``concurrency`` workers
    pull arrival-ordered requests, SLEEP until each one's arrival time
    (offered rate honored, not merely timestamped), then drive the request
    to completion before pulling the next. When the pool saturates, later
    requests launch behind schedule — disclosed as ``send_lag_ms_p50`` and
    the offered-vs-achieved gap, which is exactly the honesty the open-loop
    curves lacked. Returns aggregate + per-request records."""
    work = sorted(workload, key=lambda r: r["arrival"])
    records = [None] * len(work)
    cursor = [0]
    lock = threading.Lock()
    t0 = time.time()

    def worker():
        while True:
            with lock:
                i = cursor[0]
                if i >= len(work):
                    return
                cursor[0] += 1
            r = work[i]
            delay = r["arrival"] - (time.time() - t0)
            if delay > 0:
                time.sleep(delay)
            t_send = time.time()
            rec = _http_generate(host, port, r, stream, timeout_s, slo_class)
            rec["send_lag_ms"] = max(0.0, (t_send - t0 - r["arrival"]) * 1e3)
            records[i] = rec

    threads = [threading.Thread(target=worker, daemon=True,
                                name=f"dstpu-loadgen-{i}")
               for i in range(min(concurrency, len(work)))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    makespan = time.time() - t0
    recs = [r for r in records if r is not None]
    done = [r for r in recs if r["status"] == 200 and r["error"] is None]
    shed = [r for r in recs if r["status"] == 429]
    errors = [r for r in recs
              if not (r["status"] == 200 and r["error"] is None) and r["status"] != 429]
    last_arrival = work[-1]["arrival"] if work else 0.0
    agg = {
        "n_requests": len(work),
        "completed": len(done),
        "shed": len(shed),
        "errors": len(errors),
        # offered = what the arrival schedule asked for; achieved = what the
        # system absorbed — divergence means saturation, not a faster clock
        "offered_rps": (round((len(work) - 1) / last_arrival, 2)
                        if last_arrival > 0 else None),
        "achieved_rps": round(len(done) / makespan, 2) if makespan > 0 else None,
        "shed_rate": round(len(shed) / len(work), 3) if work else 0.0,
        "ttft": _percentiles([r["ttft_ms"] for r in done if r["ttft_ms"]]),
        "tpot": _percentiles([r["tpot_ms"] for r in done if r["tpot_ms"]]),
        "latency": _percentiles([r["latency_ms"] for r in done if r["latency_ms"]]),
        "send_lag_ms_p50": (round(float(np.percentile(
            [r["send_lag_ms"] for r in recs], 50)), 1) if recs else None),
    }
    return agg, recs


def build_gateway(n_replicas=2, prefix_cache=True, on_tpu=False, host_blocks=None,
                  **cfg_kwargs):
    """N fresh replicas (identical deterministic params — greedy outputs are
    placement-invariant) under one started gateway."""
    from deepspeed_tpu.serving import GatewayConfig, ServingGateway

    engines = [build_engine(on_tpu, prefix_cache=prefix_cache,
                            host_blocks=host_blocks)
               for _ in range(n_replicas)]
    cfg = GatewayConfig(enabled=True, port=0, **cfg_kwargs)
    return ServingGateway(engines, cfg).start()


def gateway_latency_curves(on_tpu, n_requests=None, seed=0, n_replicas=2):
    """Latency-under-load through the full HTTP plane: a saturated
    calibration pass, then an offered-rate sweep around it — TTFT/TPOT
    p50/p99 + shed rate per point. Engines are the small smoke config
    regardless of backend (two production-sized replicas do not share one
    chip's HBM); the headline serving numbers stay with bench_serving."""
    n = n_requests or (32 if on_tpu else 12)
    shape = dict(prompt_lo=8, prompt_hi=24, new_lo=4, new_hi=10)
    gw = build_gateway(n_replicas=n_replicas, prefix_cache=True)
    # the 2x point must shed, not queue unboundedly: bound the default class
    for cls in gw.config.slo_classes.values():
        cls.max_queue_depth = max(4, n // 2)
    try:
        warm = make_workload(n, rate_rps=None, seed=seed, uid_base=0, **shape)
        run_http_load(gw.config.host, gw.port, warm)  # compile the buckets
        sat = make_workload(n, rate_rps=None, seed=seed, uid_base=10_000, **shape)
        sat_agg, _ = run_http_load(gw.config.host, gw.port, sat)
        result = {"config": "gateway_http_load", "n_requests": n,
                  "n_replicas": n_replicas, "engine_config": "cpu_smoke",
                  "saturated": sat_agg, "curve": []}
        base = sat_agg["achieved_rps"] or 1.0
        for mi, mult in enumerate((0.5, 1.0, 2.0)):
            wl = make_workload(n, rate_rps=base * mult, seed=seed + 1 + mi,
                               uid_base=50_000 + 20_000 * mi, **shape)
            agg, _ = run_http_load(gw.config.host, gw.port, wl)
            result["curve"].append({"offered_mult": mult, **agg})
        return result
    finally:
        gw.stop()


def router_prefix_ab(on_tpu, n_requests=None, seed=0, n_replicas=2, gateway=None):
    """Prefix-aware router vs random placement, same engines, same Zipf
    shared-prefix workload (ISSUE 6 acceptance): the radix-overlap oracle
    keeps each hot prefix on ONE replica, so the fleet pays one cold miss
    per prefix instead of one per (prefix, replica) pair — strictly higher
    AGGREGATE hit rate. Between arms every tree is cleared and its stats
    zeroed; greedy + identical params make the generations
    placement-invariant, reported as ``token_parity``. The load runs with
    ONE closed-loop worker so each request's prefix is published before the
    next routes — hit accounting measures PLACEMENT, not racing admissions
    (both arms, same discipline, so the comparison stays apples-to-apples
    and deterministic under the fixed seeds)."""
    n = n_requests or (48 if on_tpu else 24)
    shape = dict(n_prefixes=4, prefix_len=24, suffix_lo=4, suffix_hi=10,
                 new_lo=3, new_hi=6)
    own = gateway is None
    gw = gateway or build_gateway(n_replicas=n_replicas, prefix_cache=True)
    n_replicas = len(gw.replicas)
    try:
        # compile the shape buckets on an all-unique stream so neither arm
        # pays XLA inside its measured window
        warm = make_shared_prefix_workload(n // 2, rate_rps=None, seed=seed + 7,
                                           uid_base=90_000, unique=True, **shape)
        run_http_load(gw.config.host, gw.port, warm, stream=False)
        out = {"config": "router_prefix_ab", "n_requests": n,
               "n_replicas": n_replicas, "zipf_a": 1.2,
               # cache-hit prefill trims produce chunk shapes the unique-mode
               # warmup never saw, so the FIRST arm pays residual XLA
               # compiles: compare hit rates across arms, not wall-clock
               "note": "arms run sequentially; rps/ttft not arm-comparable",
               "arms": {}}
        tokens = {}
        for ai, policy in enumerate(("random", "prefix")):
            for eng in gw.engines:
                eng.prefix_cache.clear()
                eng.prefix_cache.stats.update({k: 0 for k in eng.prefix_cache.stats})
            gw.router.policy = policy
            wl = make_shared_prefix_workload(n, rate_rps=None, seed=seed,
                                             uid_base=1000 * (ai + 1), **shape)
            agg, recs = run_http_load(gw.config.host, gw.port, wl, stream=False,
                                      concurrency=1)
            hits = sum(e.prefix_cache.stats["hits"] for e in gw.engines)
            lookups = sum(e.prefix_cache.stats["lookups"] for e in gw.engines)
            cached = sum(e.prefix_cache.stats["cached_tokens"] for e in gw.engines)
            out["arms"][policy] = {
                "aggregate_hit_rate": round(hits / lookups, 3) if lookups else 0.0,
                "hits": hits, "lookups": lookups, "cached_tokens": cached,
                "achieved_rps": agg["achieved_rps"],
                "ttft_p50_ms": agg["ttft"]["p50_ms"],
            }
            tokens[policy] = {r["uid"] - 1000 * (ai + 1): list(r["tokens"])
                              for r in recs if r["status"] == 200}
        out["token_parity"] = tokens["random"] == tokens["prefix"]
        out["prefix_beats_random"] = (out["arms"]["prefix"]["aggregate_hit_rate"]
                                      > out["arms"]["random"]["aggregate_hit_rate"])
        return out
    finally:
        if own:
            gw.stop()
        else:  # a borrowed gateway gets its configured policy back
            gw.router.policy = gw.config.router


def multi_tenant_bench(on_tpu, n_requests=None, seed=0, n_replicas=2,
                       n_tenants=4, hot_share=0.4):
    """Multi-tenant closed-loop HTTP load with tenant metering armed (the
    ``bench.py`` ``tenants{...}`` block): N Zipf-share tenants plus one
    adversarial hot tenant, per-tenant CLIENT-side TTFT/TPOT, the meter's
    fairness index, per-tenant prefix hit rates (cached / prompt tokens),
    shed attribution and KV/compute spend — the dashboard that makes a hot
    tenant starving the rest visible BEFORE item 4's quota enforcement
    exists to act on it."""
    from deepspeed_tpu.serving import MeteringConfig

    n = n_requests or (48 if on_tpu else 18)
    gw = build_gateway(n_replicas=n_replicas, prefix_cache=True,
                       metering=MeteringConfig(enabled=True,
                                               top_k=n_tenants + 1))
    try:
        warm = make_multi_tenant_workload(max(6, n // 3), n_tenants=n_tenants,
                                          hot_share=hot_share, seed=seed + 7,
                                          uid_base=90_000)
        run_http_load(gw.config.host, gw.port, warm, stream=False)  # compile buckets
        wl = make_multi_tenant_workload(n, n_tenants=n_tenants, hot_share=hot_share,
                                        seed=seed, uid_base=0)
        agg, recs = run_http_load(gw.config.host, gw.port, wl, stream=False)
        usage = gw.meter.usage_report()
        per_tenant = {}
        ledgers = dict(usage["tenants"])
        by_tenant_recs = {}
        for r in recs:
            by_tenant_recs.setdefault(r.get("tenant"), []).append(r)
        for tenant, led in sorted(ledgers.items()):
            rs = [r for r in by_tenant_recs.get(tenant, ())
                  if r["status"] == 200 and r["error"] is None]
            prompt_tokens = led["uncached_tokens"] + led["cached_tokens"]
            per_tenant[tenant] = {
                "requests": led["requests"], "completed": led["completed"],
                "shed": led["shed"],
                "hit_rate": (round(led["cached_tokens"] / prompt_tokens, 3)
                             if prompt_tokens else 0.0),
                "hit_tokens_cross": led["hit_tokens_cross"],
                "served_tokens": led["served_tokens"],
                "compute_s": led["compute_total_s"],
                "kv_block_s": led["kv_block_s"],
                "queue_s": round(sum(led["queue_s"].values()), 6),
                "ttft": _percentiles([r["ttft_ms"] for r in rs if r["ttft_ms"]]),
                "tpot": _percentiles([r["tpot_ms"] for r in rs if r["tpot_ms"]]),
            }
        hot = per_tenant.get("hot", {})
        rest_ttfts = [r["ttft_ms"] for t, rows in by_tenant_recs.items()
                      if t != "hot" for r in rows
                      if r["status"] == 200 and r["error"] is None and r["ttft_ms"]]
        return {
            "config": "multi_tenant",
            "n_requests": n, "n_tenants": n_tenants, "hot_share": hot_share,
            "n_replicas": n_replicas,
            "achieved_rps": agg["achieved_rps"], "shed_rate": agg["shed_rate"],
            "fairness_index": usage["fairness_index"],
            "starvations": usage["starvations"],
            "tenants_seen": usage["tenants_seen"],
            "hot_tenant_compute_share": (
                round(hot.get("compute_s", 0.0) /
                      max(1e-9, sum(t["compute_s"] for t in per_tenant.values())), 3)
                if per_tenant else None),
            "rest_ttft_p99_ms": (round(float(np.percentile(rest_ttfts, 99)), 1)
                                 if rest_ttfts else None),
            "per_tenant": per_tenant,
        }
    finally:
        gw.stop()


# ---------------------------------------------------------------------------
# request-scoped tracing: log consumption, p99 attribution, overhead A/B
# ---------------------------------------------------------------------------
_STAGES = ("ingress_ms", "queue_ms", "prefill_ms", "decode_ms")


def read_request_log(path):
    """Parse a request-summary JSONL log (rotated siblings ``path.N``
    included, oldest first) into a record list. The rotation chain is
    contiguous (``.1`` is newest rotation), so walk until the first gap —
    no hardcoded bound on how many rotations a config retained."""
    rotated = []
    i = 1
    while os.path.exists(f"{path}.{i}"):
        rotated.append(f"{path}.{i}")
        i += 1
    records = []
    for p in rotated[::-1] + [path]:
        if not os.path.exists(p):
            continue
        with open(p) as f:
            for line in f:
                line = line.strip()
                if line:
                    records.append(json.loads(line))
    return records


def attribution_table(records):
    """The p99-attribution table: where completed requests spent their time
    (per-stage p50/p99), the single p99-TTFT request's own breakdown (the
    forensic 'this one was slow BECAUSE...'), and the fraction of records
    whose stage sum reconstructs end-to-end latency within 10% (the
    honesty check on the breakdown itself)."""
    done = [r for r in records if r.get("finish_reason") in ("length", "eos")]
    out = {"n_records": len(records), "n_completed": len(done),
           "by_reason": {}, "stages_p50_ms": {}, "stages_p99_ms": {},
           "p99_request": None, "breakdown_ok_frac": None, "ttft_p99_ms": None}
    for r in records:
        k = r.get("finish_reason") or "unknown"
        out["by_reason"][k] = out["by_reason"].get(k, 0) + 1
    if not done:
        return out
    for st in _STAGES:
        vals = [r[st] for r in done if r.get(st) is not None]
        if vals:
            out["stages_p50_ms"][st] = round(float(np.percentile(vals, 50)), 2)
            out["stages_p99_ms"][st] = round(float(np.percentile(vals, 99)), 2)
    with_ttft = [r for r in done if r.get("ttft_ms")]
    if with_ttft:
        ttfts = [r["ttft_ms"] for r in with_ttft]
        out["ttft_p99_ms"] = round(float(np.percentile(ttfts, 99)), 2)
        worst = max(with_ttft, key=lambda r: r["ttft_ms"])
        out["p99_request"] = {k: worst.get(k) for k in
                              ("request_id", "slo_class", "route_choice",
                               "prefix_hit_tokens", "prompt_tokens",
                               "ttft_ms", "slo_verdict") + _STAGES}
    ok = 0
    checked = 0
    for r in done:
        parts = [r.get(st) for st in _STAGES]
        if r.get("e2e_ms") and all(p is not None for p in parts):
            checked += 1
            if abs(sum(parts) - r["e2e_ms"]) <= max(0.1 * r["e2e_ms"], 2.0):
                ok += 1
    out["breakdown_ok_frac"] = round(ok / checked, 3) if checked else None
    # migrated/fallback rows (ISSUE 20 satellite): the broker's cost is in
    # the summary records themselves now — surface it alongside the stages
    migrated = [r for r in records if r.get("handoff_state") == "migrated"]
    fallback = [r for r in records if r.get("handoff_state") == "fallback"]
    if migrated or fallback:
        hand = [r["handoff_ms"] for r in migrated + fallback
                if r.get("handoff_ms") is not None]
        waits = [r["resume_wait_ms"] for r in migrated
                 if r.get("resume_wait_ms") is not None]
        out["handoff"] = {
            "migrated": len(migrated), "fallbacks": len(fallback),
            "handoff_ms_p50": (round(float(np.percentile(hand, 50)), 2)
                               if hand else None),
            "handoff_ms_p99": (round(float(np.percentile(hand, 99)), 2)
                               if hand else None),
            "resume_wait_ms_p50": (round(float(np.percentile(waits, 50)), 2)
                                   if waits else None),
            "resume_wait_ms_p99": (round(float(np.percentile(waits, 99)), 2)
                                   if waits else None),
        }
    return out


def tracing_overhead_ab(on_tpu, n_requests=None, seed=0, n_replicas=2):
    """Trace-on vs trace-off A/B over the same closed-loop saturated
    workload: identical engines/config except the ``tracing`` block, so the
    throughput delta IS the tracing tax (the zero-overhead-off claim,
    measured rather than asserted). The trace-on arm also yields the
    p99-attribution table from its request log."""
    from deepspeed_tpu.serving import RequestTraceConfig

    n = n_requests or (32 if on_tpu else 12)
    shape = dict(prompt_lo=8, prompt_hi=24, new_lo=4, new_hi=10)
    out = {"config": "request_tracing_ab", "n_requests": n,
           # arms run sequentially in one process: on CPU smoke the SECOND
           # arm can ride XLA caching the first paid for, so small negative
           # overhead is order noise — judge the tax on TPU steady-state
           "note": "arms sequential; cpu-smoke rps is order-noisy", "arms": {}}
    import shutil

    log_dir = tempfile.mkdtemp(prefix="dstpu_reqlog_")
    log_path = os.path.join(log_dir, "requests.jsonl")
    try:
        for arm in ("trace_off", "trace_on"):
            cfg_kwargs = {}
            if arm == "trace_on":
                cfg_kwargs["tracing"] = RequestTraceConfig(enabled=True,
                                                           log_path=log_path)
            gw = build_gateway(n_replicas=n_replicas, prefix_cache=True,
                               on_tpu=False, **cfg_kwargs)
            try:
                warm = make_workload(n, rate_rps=None, seed=seed, uid_base=0, **shape)
                run_http_load(gw.config.host, gw.port, warm)  # compile buckets
                wl = make_workload(n, rate_rps=None, seed=seed, uid_base=10_000, **shape)
                agg, _ = run_http_load(gw.config.host, gw.port, wl)
                out["arms"][arm] = {"achieved_rps": agg["achieved_rps"],
                                    "completed": agg["completed"],
                                    "ttft_p50_ms": agg["ttft"]["p50_ms"]}
            finally:
                gw.stop()
        off, on = out["arms"]["trace_off"], out["arms"]["trace_on"]
        if off["achieved_rps"] and on["achieved_rps"]:
            out["overhead_pct"] = round(
                (off["achieved_rps"] - on["achieved_rps"]) / off["achieved_rps"] * 100, 2)
        records = read_request_log(log_path)

        def measured(r):  # the warmup pass logged too: keep the 10k-base uids
            rid = str(r.get("request_id", ""))
            return rid.startswith("load-") and rid[5:].isdigit() and int(rid[5:]) >= 10_000

        out["attribution"] = attribution_table([r for r in records if measured(r)])
        return out
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def disagg_ab(on_tpu, n_requests=None, seed=0):
    """Disaggregated prefill/decode A/B (ISSUE 18): a decode-heavy
    FOREGROUND stream measured while a BACKGROUND stream of pure long
    prefills (``max_new_tokens=1`` — prefill completes the request) hammers
    the fleet, through the full HTTP plane twice:

      * ``colocated`` — two ``mixed`` replicas; background prefill chunks
        share SplitFuse forwards with foreground decodes on BOTH replicas,
        so every foreground token pays the arbitration (the interference
        PR 7's stage attribution measures);
      * ``disagg``    — ``("prefill", "decode")`` pools; the background
        never leaves the prefill replica, and foreground requests migrate
        their KV to the decode replica through the host-tier handoff and
        decode in prefill-free forwards.

    Both arms arm the host tier (the disagg arm NEEDS it as transport; the
    baseline gets it too so capacity is equal). The headline is foreground
    TPOT p50/p99 — the per-token decode interval the pool split exists to
    protect — plus greedy token parity across arms and the handoff ledger's
    migration stats (p50 latency, fallback rate, volume)."""
    n_fg = n_requests or (24 if on_tpu else 12)
    n_bg = 2 * n_fg
    # foreground: decode-heavy, prompt + new inside the cpu-smoke
    # max_context=64; background: the longest prefill the context takes,
    # one token out (prefill IS the request)
    fg_shape = dict(prompt_lo=16, prompt_hi=28, new_lo=12, new_hi=20)
    bg_shape = dict(prompt_lo=40, prompt_hi=60, new_lo=1, new_hi=1)
    concurrency = 8
    host_blocks = 160
    result = {"config": "disagg_ab", "n_foreground": n_fg, "n_background": n_bg,
              "n_replicas": 2, "engine_config": "cpu_smoke",
              "host_blocks": host_blocks}
    tokens_by_arm = {}
    for arm in ("colocated", "disagg"):
        kwargs = {}
        if arm == "disagg":
            from deepspeed_tpu.serving import DisaggConfig

            kwargs["disagg"] = DisaggConfig(enabled=True,
                                            roles=("prefill", "decode"))
        gw = build_gateway(n_replicas=2, prefix_cache=True,
                           host_blocks=host_blocks, on_tpu=on_tpu, **kwargs)
        try:
            warm = (make_workload(n_fg, rate_rps=None, seed=seed + 7,
                                  uid_base=90_000, **fg_shape)
                    + make_workload(n_bg, rate_rps=None, seed=seed + 8,
                                    uid_base=95_000, **bg_shape))
            run_http_load(gw.config.host, gw.port, warm,
                          concurrency=concurrency)
            # one merged closed-loop run: the background is load, not a
            # separate phase — arrival order interleaves the two streams
            fg = make_workload(n_fg, rate_rps=None, seed=seed, uid_base=0,
                               **fg_shape)
            bg = make_workload(n_bg, rate_rps=None, seed=seed + 1,
                               uid_base=500_000, **bg_shape)
            _agg, recs = run_http_load(gw.config.host, gw.port, fg + bg,
                                       concurrency=concurrency)
            fg_done = [r for r in recs if r["uid"] < 500_000
                       and r["status"] == 200 and r["error"] is None]
            bg_done = [r for r in recs if r["uid"] >= 500_000
                       and r["status"] == 200 and r["error"] is None]
            line = {"fg_completed": len(fg_done), "bg_completed": len(bg_done),
                    "errors": len(recs) - len(fg_done) - len(bg_done),
                    "fg_ttft": _percentiles([r["ttft_ms"] for r in fg_done
                                             if r["ttft_ms"]]),
                    "fg_tpot": _percentiles([r["tpot_ms"] for r in fg_done
                                             if r["tpot_ms"]]),
                    "fg_latency": _percentiles([r["latency_ms"] for r in fg_done
                                                if r["latency_ms"]])}
            if arm == "disagg":
                st = gw.disagg.state()
                line.update({"pools": st["pools"], "migrated": st["migrated"],
                             "fallbacks": st["fallbacks"],
                             "blocks_moved": st["handoff"]["blocks_moved"],
                             "handoff_p50_ms": st["handoff"]["handoff_p50_ms"],
                             "handoff_p99_ms": st["handoff"]["handoff_p99_ms"],
                             "handoff_fallback_rate":
                                 st["handoff"]["handoff_fallback_rate"]})
            tokens_by_arm[arm] = {r["uid"]: list(r["tokens"])
                                  for r in fg_done + bg_done}
            result[arm] = line
        finally:
            gw.stop()
    common = sorted(set(tokens_by_arm["colocated"]) & set(tokens_by_arm["disagg"]))
    result["token_parity"] = bool(common) and all(
        tokens_by_arm["colocated"][u] == tokens_by_arm["disagg"][u]
        for u in common)
    co_p99 = result["colocated"]["fg_tpot"].get("p99_ms")
    dg_p99 = result["disagg"]["fg_tpot"].get("p99_ms")
    result["tpot_p99_improved"] = (co_p99 is not None and dg_p99 is not None
                                   and dg_p99 < co_p99)
    return result


def timeline_rounds(on_tpu, n_requests=None, seed=0, out_dir=None):
    """Two captured timeline rounds for ``tools/trace_explain.py`` (ISSUE
    20): the SAME disagg foreground workload through the full HTTP plane
    twice — once clean (``base``), once with a deterministic 100%-rate
    150 ms chaos stall AT ``serving/handoff`` (``stalled``), which lands
    between the broker's export and verify, so the regression lives inside
    every migrated request's ``broker_verify`` segment. The measured round
    is foreground-only at concurrency 1: sequential requests have no
    queueing neighbors, so the seeded stall's milliseconds land in the
    stalled request's OWN broker segment instead of bleeding into other
    requests' queue/prefill/resume waits (warmup still drives both pools
    with the mixed workload to pin compile buckets). Each arm writes one
    round file (``{"meta": backend stamp, "timelines": [...]}``, measured
    rids only) and the summary runs the differential explain across them:
    the dominant stage must be the stalled broker stage, not a neighbor."""
    from bench import backend_stamp
    from deepspeed_tpu.runtime.resilience.chaos import ChaosSchedule, ChaosSpec
    from deepspeed_tpu.serving import (DisaggConfig, RequestTraceConfig,
                                       TimelineConfig)
    from tools.trace_explain import explain, load_round

    n_fg = n_requests or (16 if on_tpu else 8)
    n_bg = n_fg
    fg_shape = dict(prompt_lo=16, prompt_hi=28, new_lo=12, new_hi=20)
    bg_shape = dict(prompt_lo=40, prompt_hi=60, new_lo=1, new_hi=1)
    out_dir = out_dir or os.path.join(tempfile.gettempdir(),
                                      "dstpu_timeline_rounds")
    os.makedirs(out_dir, exist_ok=True)
    result = {"config": "timeline_rounds", "n_foreground": n_fg,
              "n_background": n_bg, "out_dir": out_dir, "rounds": {}}
    for arm in ("base", "stalled"):
        gw = build_gateway(
            n_replicas=2, prefix_cache=True, host_blocks=160, on_tpu=on_tpu,
            disagg=DisaggConfig(enabled=True, roles=("prefill", "decode")),
            tracing=RequestTraceConfig(enabled=True),
            timeline=TimelineConfig(enabled=True, last_n=1024))
        sched = None
        try:
            warm = (make_workload(n_fg, rate_rps=None, seed=seed + 7,
                                  uid_base=90_000, **fg_shape)
                    + make_workload(n_bg, rate_rps=None, seed=seed + 8,
                                    uid_base=95_000, **bg_shape))
            run_http_load(gw.config.host, gw.port, warm, concurrency=8)
            if arm == "stalled":
                # armed AFTER warmup: the measured rounds differ by exactly
                # the seeded stall, nothing else
                sched = ChaosSchedule(seed + 11, [
                    ChaosSpec("stall", "serving/handoff", rate=1.0,
                              duration_s=0.15)]).install()
            fg = make_workload(n_fg, rate_rps=None, seed=seed, uid_base=0,
                               **fg_shape)
            run_http_load(gw.config.host, gw.port, fg, concurrency=1)
            want = {f"load-{r['uid']}" for r in fg}
            timelines = [t for t in gw.timeline.recent()
                         if t.get("request_id") in want]
            path = os.path.join(out_dir, f"timeline_{arm}.json")
            with open(path, "w") as f:
                json.dump({"meta": {**backend_stamp(on_tpu), "arm": arm},
                           "timelines": timelines}, f, default=repr)
            migrated = [t for t in timelines if t.get("migrated")]
            result["rounds"][arm] = {
                "path": path, "n_timelines": len(timelines),
                "migrated": len(migrated),
                "migrated_coverage_ok_frac":
                    (round(sum(bool(t["coverage_ok"]) for t in migrated)
                           / len(migrated), 3) if migrated else None),
                "chaos_stalls": (sched.counts().get("stall", 0)
                                 if sched is not None else 0),
            }
        finally:
            if sched is not None:
                sched.uninstall()
            gw.stop()
    report = explain(load_round(result["rounds"]["base"]["path"]),
                     load_round(result["rounds"]["stalled"]["path"]))
    result["explain"] = {
        "refused": report["refused"],
        "delta_e2e_ms": report.get("delta_e2e_ms"),
        "dominant_stage": report.get("dominant_stage"),
        "dominant_cause": report.get("dominant_cause"),
        "broker_verify_delta_ms": (report.get("by_stage", {})
                                   .get("broker_verify", {}).get("delta_ms")),
    }
    return result


def control_ab(on_tpu, n_requests=None, seed=0, n_replicas=2):
    """Controller-on vs controller-off A/B (ISSUE 19): the same
    prefill-storm workload — an interactive foreground stream measured
    while a batch stream of long pure prefills floods the queues — through
    the full HTTP plane twice. Identical gateways/SLO classes except the
    ``control`` block, so the delta IS the feedback loop:

      * ``control_off`` — static admission limits; under the storm the
        interactive queue runs deep and TTFT blows through its target;
      * ``control_on``  — the admission policy watches the per-class
        SLO-miss counters and tightens the interactive queue depth live,
        trading shed (429, retryable) for conformance of what it admits.

    The headline is the interactive SLO-miss rate among COMPLETED requests
    (same server-side TTFT-vs-target rule the miss counters use), plus
    greedy token parity over the uids both arms completed, plus the on-arm
    decision ledger (every tighten/relax with its sensor justification).
    The TTFT target itself is calibrated, not hardcoded: 2x the p50 of an
    uncontended interactive pass on this host."""
    from deepspeed_tpu.serving import ControlConfig, SLOClassConfig

    n_fg = n_requests or (24 if on_tpu else 12)
    n_bg = 2 * n_fg
    fg_shape = dict(prompt_lo=8, prompt_hi=16, new_lo=4, new_hi=8)
    bg_shape = dict(prompt_lo=40, prompt_hi=60, new_lo=1, new_hi=1)
    concurrency = 8
    result = {"config": "control_ab", "n_interactive": n_fg, "n_batch": n_bg,
              "n_replicas": n_replicas, "engine_config": "cpu_smoke"}

    # calibration: what does interactive TTFT look like UNCONTENDED on this
    # host? (no slo_class sent — the calibration gateway carries defaults)
    gw = build_gateway(n_replicas=n_replicas, prefix_cache=True, on_tpu=on_tpu)
    try:
        warm = make_workload(n_fg, rate_rps=None, seed=seed + 3,
                             uid_base=700_000, **fg_shape)
        run_http_load(gw.config.host, gw.port, warm, concurrency=2,
                      stream=False)  # compile buckets
        cal = make_workload(n_fg, rate_rps=None, seed=seed + 4,
                            uid_base=710_000, **fg_shape)
        _, cal_recs = run_http_load(gw.config.host, gw.port, cal,
                                    concurrency=2, stream=False)
        ttfts = [r["ttft_ms"] for r in cal_recs
                 if r["status"] == 200 and r["ttft_ms"]]
    finally:
        gw.stop()
    # 3x the uncontended p50 with a generous floor: the target must sit
    # ABOVE the host's prompt-service floor (boundary noise is not a miss)
    # and BELOW the storm's queueing delay (hundreds of ms) — the miss
    # counter should answer "queued behind the storm?", nothing subtler
    target_ms = round(max(3.0 * float(np.percentile(ttfts, 50)), 25.0), 1) \
        if ttfts else 100.0
    result["ttft_target_ms"] = target_ms

    classes = {"interactive": SLOClassConfig(priority=0, max_queue_depth=16,
                                             ttft_target_ms=target_ms),
               "batch": SLOClassConfig(priority=1, max_queue_depth=64)}
    tokens_by_arm = {}
    for arm in ("control_off", "control_on"):
        cfg_kwargs = {"slo_classes": dict(classes)}
        if arm == "control_on":
            cfg_kwargs["control"] = ControlConfig(
                enabled=True, interval_s=0.05, window_s=1.0,
                policies=("admission",), sustain_ticks=2,
                max_actuations_per_window=8, cooldown_s=0.2,
                slo_miss_tighten=0.3, slo_miss_relax=0.05,
                min_queue_depth=1, min_window_completions=3)
        gw = build_gateway(n_replicas=n_replicas, prefix_cache=True,
                           on_tpu=on_tpu, **cfg_kwargs)
        try:
            warm = (make_workload(n_fg, rate_rps=None, seed=seed + 7,
                                  uid_base=900_000, **fg_shape)
                    + make_workload(n_bg, rate_rps=None, seed=seed + 8,
                                    uid_base=950_000, **bg_shape))
            run_http_load(gw.config.host, gw.port, warm,
                          concurrency=concurrency, stream=False)
            fg = make_workload(n_fg, rate_rps=None, seed=seed, uid_base=0,
                               **fg_shape)
            for r in fg:
                r["slo_class"] = "interactive"
            bg = make_workload(n_bg, rate_rps=None, seed=seed + 1,
                               uid_base=500_000, **bg_shape)
            for r in bg:
                r["slo_class"] = "batch"
            _agg, recs = run_http_load(gw.config.host, gw.port, fg + bg,
                                       concurrency=concurrency, stream=False)
            fg_done = [r for r in recs if r["uid"] < 500_000
                       and r["status"] == 200 and r["error"] is None]
            fg_shed = [r for r in recs if r["uid"] < 500_000
                       and r["status"] == 429]
            misses = [r for r in fg_done
                      if r["ttft_ms"] and r["ttft_ms"] > target_ms]
            line = {"fg_completed": len(fg_done), "fg_shed": len(fg_shed),
                    "fg_miss_rate": (round(len(misses) / len(fg_done), 3)
                                     if fg_done else None),
                    "fg_ttft": _percentiles([r["ttft_ms"] for r in fg_done
                                             if r["ttft_ms"]])}
            if arm == "control_on":
                st = gw.controller.state()
                applied = [d for d in gw.controller.decisions.recent()
                           if d["applied"]]
                line.update({
                    "actuations": st["applied"], "deferred": st["deferred"],
                    "ticks": st["ticks"], "errors": st["errors"],
                    "depth_overrides": st["overrides"],
                    "decision_actions": sorted({d["action"] for d in applied}),
                    "decisions_justified": all(d.get("sensors")
                                               for d in applied)})
            tokens_by_arm[arm] = {r["uid"]: list(r["tokens"]) for r in recs
                                  if r["status"] == 200 and r["error"] is None}
            result[arm] = line
        finally:
            gw.stop()
    common = sorted(set(tokens_by_arm["control_off"])
                    & set(tokens_by_arm["control_on"]))
    result["token_parity"] = bool(common) and all(
        tokens_by_arm["control_off"][u] == tokens_by_arm["control_on"][u]
        for u in common)
    off_miss = result["control_off"]["fg_miss_rate"]
    on_miss = result["control_on"]["fg_miss_rate"]
    result["slo_miss_improved"] = (off_miss is not None and on_miss is not None
                                   and on_miss < off_miss)
    return result


def gateway_bench(on_tpu, seed=0):
    """The bench.py serving-block entry: latency-under-load curves + the
    router A/B + the request-tracing attribution/overhead block, one dict."""
    return {"load": gateway_latency_curves(on_tpu, seed=seed),
            "router_ab": router_prefix_ab(on_tpu, seed=seed),
            "tracing": tracing_overhead_ab(on_tpu, seed=seed)}


def main():
    import jax

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = any(d.platform == "tpu" for d in jax.devices())

    # arm the live-health plane for the whole run (serving heartbeats wrap
    # every put/decode): a wedged device forward trips the watchdog instead
    # of the tool hanging silently, and the final JSON reports the counters.
    # DS_TPU_SERVING_HEALTH=0 runs bare; the deadline is generous because a
    # cold compile of a new shape bucket legitimately takes a while.
    health = None
    if os.environ.get("DS_TPU_SERVING_HEALTH", "1") != "0":
        from deepspeed_tpu.monitor.health import get_health

        health = get_health().configure(
            enabled=True,
            deadlines={"serving": float(os.environ.get("DS_TPU_SERVING_DEADLINE_S", "300"))})

    if "shared_prefix" in sys.argv[1:]:
        out = shared_prefix_ab(on_tpu)
    elif "speculative_sweep" in sys.argv[1:]:
        out = speculative_sweep(on_tpu)
    elif "speculative" in sys.argv[1:]:
        out = {"ab": speculative_ab(on_tpu), "sweep": speculative_sweep(on_tpu)}
    elif "gateway" in sys.argv[1:]:
        out = gateway_bench(on_tpu)
    elif "cache_pressure" in sys.argv[1:]:
        out = cache_pressure_bench(on_tpu)
    elif "host_tier" in sys.argv[1:]:
        out = host_tier_ab(on_tpu)
    elif "disagg" in sys.argv[1:]:
        out = disagg_ab(on_tpu)
    elif "control_ab" in sys.argv[1:]:
        out = control_ab(on_tpu)
    elif "timeline" in sys.argv[1:]:
        out = timeline_rounds(on_tpu)
    elif "multi_tenant" in sys.argv[1:]:
        out = multi_tenant_bench(on_tpu)
    else:
        out = serving_load_bench(on_tpu)
    out["on_tpu"] = on_tpu

    if health is not None:
        from deepspeed_tpu.monitor.metrics import get_metrics

        reg = get_metrics()
        out["health"] = {
            "stalls": health.stall_count,
            "stall_serving_total": int(reg.counter("health/stall_serving_total").value),
            "dumps_total": int(reg.counter("health/dumps_total").value),
            "last_dump": health.last_dump_path,
        }
        health.shutdown()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
