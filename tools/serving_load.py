"""The tests' and the chaos drills' CPU twin of a serving deployment.

What is here is what a tier-1 test or ``tools/chaos_drill.py`` calls: a small
engine (:func:`build_engine`) and a gateway of such engines
(:func:`build_gateway`), three seeded workload makers, two drive loops over one
engine (:func:`run_splitfuse`, and :func:`run_static` as the policy it is held
against: greedy over the same engine, so the two must give the same tokens),
a closed-loop HTTP client (:func:`run_http_load`) that honours the workload's
arrival times and counts every terminal, the request log's reader and its
attribution table, and three scenarios that each carry one test
(:func:`cache_pressure_bench`, :func:`host_tier_ab`, :func:`router_prefix_ab`).

Nothing here measures the chip: the cells of ``BENCHMARK.json`` and
``benchmark/run.py`` do that. A time this file reports is a host time of a
toy on the CPU and is never a device metric.
"""

import json
import os
import threading
import time

import numpy as np


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


def _twin_engine(block, pool, prefix_cache, speculative=None):
    """The twin: two layers, 64 wide, float32, the reference attention, over a
    pool of ``pool`` KV blocks of ``block`` tokens."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                            num_kv_heads=2, intermediate_size=128, max_seq_len=256,
                            dtype=jnp.float32, attention_impl="reference")
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                              max_ragged_sequence_count=8, max_context=64)
    icfg = RaggedInferenceEngineConfig(kv_block_size=block, num_kv_blocks=pool,
                                       kv_dtype=jnp.float32, state_manager=sm,
                                       use_pallas_kernels="never", prefix_cache=prefix_cache)
    if speculative is not None:
        icfg.speculative = speculative
    return InferenceEngineV2(TransformerLM(cfg), icfg)


def build_engine(prefix_cache=False, speculative=None, host_blocks=None):
    from deepspeed_tpu.inference.v2 import HostTierConfig, PrefixCacheConfig

    # host_blocks arms the pinned host tier (required transport for the
    # disaggregated KV handoff — install_prefix_kv adopts host-tier nodes)
    return _twin_engine(8, 80, PrefixCacheConfig(
        enabled=bool(prefix_cache) or host_blocks is not None,
        host_tier=(HostTierConfig(host_blocks=int(host_blocks))
                   if host_blocks else None)), speculative)


def cache_pressure_bench(n_requests=96, seed=0, corpus_mult=4.0):
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
    from deepspeed_tpu.inference.v2 import (CacheTelemetryConfig, DynamicSplitFuseScheduler,
                                            PrefixCacheConfig)
    from deepspeed_tpu.monitor.memory import hbm_report

    block, pool = 8, 48
    shape = dict(prefix_len=40, suffix_lo=4, suffix_hi=10, new_lo=3, new_hi=6)
    budget = 64
    # corpus sized at corpus_mult x the pool: reuse only survives eviction
    # for the Zipf head, exactly the regime the MRC exists to size
    pool_tokens = pool * block
    n_prefixes = max(2, int(round(corpus_mult * pool_tokens / shape["prefix_len"])))
    # the trace is a few hundred chunk refs over a 48-block pool: SHARDS
    # sampling noise at that scale swamps the signal, so every chunk is
    # tracked (the sampled path is validated against exact LRU in
    # tests/test_cache_telemetry.py)
    engine = _twin_engine(block, pool, PrefixCacheConfig(
        enabled=True, telemetry=CacheTelemetryConfig(enabled=True, mrc_sample_rate=1.0)))
    tel = engine.cache_telemetry
    wl = make_shared_prefix_workload(n_requests, n_prefixes=n_prefixes, rate_rps=None,
                                     seed=seed, uid_base=0, zipf_a=1.2, **shape)
    # warmup compiles the shape buckets on an all-unique stream, then the
    # measured pass starts from a cold, zeroed cache
    warm = make_shared_prefix_workload(max(4, n_requests // 8), n_prefixes=n_prefixes,
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
        "n_requests": n_requests,
        "corpus_mult": corpus_mult,
        "n_prefixes": n_prefixes,
        "pool_blocks": pool,
        "block_size": block,
        "rps": round(n_requests / span, 2),
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
        # HBM attribution while the engine is live
        "memory": hbm_report(),
    }
    return result



def host_tier_ab(n_requests=64, seed=0, corpus_mult=10.0):
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
    from deepspeed_tpu.inference.v2 import (CacheTelemetryConfig, DynamicSplitFuseScheduler,
                                            HostTierConfig, PrefixCacheConfig)

    # host = 3x pool: hierarchy capacity lands exactly on the MRC's 4.0x
    # multiplier, so the curve's prediction is directly comparable
    block, pool, host_blocks = 8, 48, 144
    shape = dict(prefix_len=40, suffix_lo=4, suffix_hi=10, new_lo=3, new_hi=6)
    budget = 64
    pool_tokens = pool * block
    n_prefixes = max(2, int(round(corpus_mult * pool_tokens / shape["prefix_len"])))
    wl = make_shared_prefix_workload(n_requests, n_prefixes=n_prefixes, rate_rps=None,
                                     seed=seed, uid_base=0, zipf_a=1.2, **shape)
    result = {"config": "host_tier_ab", "n_requests": n_requests, "corpus_mult": corpus_mult,
              "n_prefixes": n_prefixes, "pool_blocks": pool, "block_size": block,
              "host_blocks": host_blocks}
    tokens_by_arm = {}
    for arm, tier_on in (("hbm_only", False), ("host_tier", True)):
        engine = _twin_engine(block, pool, PrefixCacheConfig(
            enabled=True,
            telemetry=CacheTelemetryConfig(enabled=True, mrc_sample_rate=1.0),
            host_tier=(HostTierConfig(host_blocks=host_blocks) if tier_on else None)))
        sched = DynamicSplitFuseScheduler(engine, token_budget=budget)
        pc = engine.prefix_cache
        # warmup compiles shape buckets on an all-unique stream, then the
        # measured pass starts from a cold cache (cache_pressure discipline)
        warm = make_shared_prefix_workload(max(4, n_requests // 8), n_prefixes=n_prefixes,
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

        line = {"rps": round(n_requests / span, 2),
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


def build_gateway(n_replicas=2, prefix_cache=True, host_blocks=None, **cfg_kwargs):
    """N fresh replicas (identical deterministic params — greedy outputs are
    placement-invariant) under one started gateway."""
    from deepspeed_tpu.serving import GatewayConfig, ServingGateway

    engines = [build_engine(prefix_cache=prefix_cache, host_blocks=host_blocks)
               for _ in range(n_replicas)]
    cfg = GatewayConfig(enabled=True, port=0, **cfg_kwargs)
    return ServingGateway(engines, cfg).start()


def router_prefix_ab(n_requests=24, seed=0, n_replicas=2, gateway=None):
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
    shape = dict(n_prefixes=4, prefix_len=24, suffix_lo=4, suffix_hi=10,
                 new_lo=3, new_hi=6)
    own = gateway is None
    gw = gateway or build_gateway(n_replicas=n_replicas, prefix_cache=True)
    n_replicas = len(gw.replicas)
    try:
        # compile the shape buckets on an all-unique stream so neither arm
        # pays XLA inside its measured window
        warm = make_shared_prefix_workload(n_requests // 2, rate_rps=None, seed=seed + 7,
                                           uid_base=90_000, unique=True, **shape)
        run_http_load(gw.config.host, gw.port, warm, stream=False)
        out = {"config": "router_prefix_ab", "n_requests": n_requests,
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
            wl = make_shared_prefix_workload(n_requests, rate_rps=None, seed=seed,
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


# ---------------------------------------------------------------------------
# request-scoped tracing: the request log's reader and the attribution table
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
