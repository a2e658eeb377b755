"""Tenant-scoped resource metering & fairness observability (ISSUE 15):
what these pin, layer by layer —

  * tenant identity: ``X-Tenant-Id`` sanitized with the request-id charset
    discipline, defaulted when absent, threaded gateway → admission →
    replica → scheduler → sequence → published radix-tree nodes, and
    surfaced in the SSE meta frame, the request-log record, and
    ``GET /v1/usage``;
  * CONSERVATION (the acceptance bar): per-tenant compute-seconds sum to
    the goodput ledger's serving active categories within 5%, and summed
    KV-block-seconds match cache telemetry's independent occupancy
    integral within 5%, under multi-tenant closed-loop HTTP load;
  * hit attribution via tenant-stamped published blocks: a cross-tenant
    hit books ``hit_tokens_cross`` for the consumer and ``served_tokens``
    for the publisher; eviction pressure lands on the publisher;
  * per-tenant shed attribution (the admission satellite);
  * fairness index + latched starvation instants;
  * bounded cardinality: ``/metrics`` never exceeds top_k + 1 distinct
    tenant label values, and the meter's memory folds past
    ``max_tracked_tenants`` into ``other``;
  * zero overhead with the block absent: no meter, no engine views, no
    stamp arrays, no scheduler observer, no threads (the PR 5 bar);
  * the ``tools/check_tenant_labels.py`` AST gate (tier-1) + drift catch.
"""

import http.client
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.monitor.flight import get_flight_recorder
from deepspeed_tpu.monitor.health import get_health
from deepspeed_tpu.monitor.trace import get_tracer
from deepspeed_tpu.serving import (DEFAULT_TENANT, GatewayConfig, MeteringConfig,
                                   RequestTraceConfig, ServingGateway, TenantMeter,
                                   parse_sse, sanitize_tenant_id)
from tools.serving_load import (build_engine, build_gateway,
                                make_multi_tenant_workload, run_http_load)


@pytest.fixture(autouse=True)
def _reset_trace_bus():
    """Tracer/flight are process singletons: leave them disarmed and empty
    so this module's enables never leak into other test files."""
    yield
    get_tracer().reset()
    get_flight_recorder().configure(enabled=False)
    get_flight_recorder().clear()


@pytest.fixture(scope="module")
def metered_gw(tmp_path_factory):
    """One prefix-cache replica under a metered + traced gateway (single
    replica so cross-tenant hits land on one radix tree deterministically)."""
    log = str(tmp_path_factory.mktemp("usage") / "usage.jsonl")
    g = build_gateway(
        n_replicas=1, prefix_cache=True,
        tracing=RequestTraceConfig(enabled=True),
        metering=MeteringConfig(enabled=True, usage_log_path=log,
                                ledger_snapshot_every=4))
    yield g, log
    g.stop()


def _post(port, body, headers=None, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/generate", json.dumps(body),
                 {"Content-Type": "application/json", **(headers or {})})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


# ---------------------------------------------------------------------------
# identity hygiene + config parsing
# ---------------------------------------------------------------------------
def test_sanitize_tenant_id():
    assert sanitize_tenant_id("acme-corp_1.2") == "acme-corp_1.2"
    assert sanitize_tenant_id('ev il"t\n{}') == "evilt"
    assert sanitize_tenant_id("x" * 200) == "x" * 64  # RID_MAX_LEN bound
    assert sanitize_tenant_id(None) == DEFAULT_TENANT
    assert sanitize_tenant_id("") == DEFAULT_TENANT
    assert sanitize_tenant_id('"\n{}') == DEFAULT_TENANT  # nothing usable
    # the meter's sentinel names are escaped: a client can never collide
    # with the aggregate bucket or the disclosed residual
    assert sanitize_tenant_id("other") == "x-other"
    assert sanitize_tenant_id("untenanted") == "x-untenanted"


def test_metering_config_parsing():
    # presence-enables, like tracing/health
    cfg = GatewayConfig.from_ds_config(
        {"serving": {"gateway": {"metering": {"top_k": 3}}}})
    assert cfg.metering.enabled and cfg.metering.top_k == 3
    assert not GatewayConfig.from_ds_config(
        {"serving": {"gateway": {}}}).metering.enabled
    with pytest.raises(ValueError, match="unknown keys"):
        GatewayConfig.from_dict({"enabled": True, "metering": {"nope": 1}})
    with pytest.raises(ValueError, match="top_k"):
        GatewayConfig.from_dict({"enabled": True, "metering": {"top_k": 0}})
    with pytest.raises(ValueError, match="max_tracked_tenants"):
        GatewayConfig.from_dict({"enabled": True,
                                 "metering": {"top_k": 8, "max_tracked_tenants": 2}})


# ---------------------------------------------------------------------------
# tenant identity end-to-end
# ---------------------------------------------------------------------------
def test_tenant_identity_e2e(metered_gw):
    gw, log = metered_gw
    st, data = _post(gw.port, {"prompt": list(range(1, 13)), "max_new_tokens": 3},
                     headers={"X-Tenant-Id": 'acme "hostile suffix'})
    assert st == 200
    events = parse_sse(data)
    meta = events[0]
    assert meta["tenant"] == "acmehostilesuffix"  # sanitized, echoed in meta
    # the meter charged the sanitized tenant
    usage = gw.meter.usage_report()
    assert "acmehostilesuffix" in usage["tenants"]
    led = usage["tenants"]["acmehostilesuffix"]
    assert led["requests"] >= 1 and led["generated_tokens"] >= 3
    assert led["uncached_tokens"] >= 1
    # absent header -> the default tenant is charged
    st, _ = _post(gw.port, {"prompt": list(range(20, 30)), "max_new_tokens": 2})
    assert st == 200
    assert DEFAULT_TENANT in gw.meter.usage_report()["tenants"]
    # request-log record carries the tenant (tracing plane)
    recs = gw.reqtrace.last_summaries()
    assert any(r.get("tenant") == "acmehostilesuffix" for r in recs), recs[-3:]


def test_usage_endpoint_and_log(metered_gw):
    gw, log = metered_gw
    for i in range(5):  # cross the ledger_snapshot_every=4 cadence
        st, _ = _post(gw.port, {"prompt": list(range(30 + i, 42 + i)),
                                "max_new_tokens": 2},
                      headers={"X-Tenant-Id": f"logged-{i % 2}"})
        assert st == 200
    st, data = _get(gw.port, "/v1/usage")
    assert st == 200
    usage = json.loads(data)
    assert usage["fairness_index"] is None or 0.0 < usage["fairness_index"] <= 1.0
    assert "logged-0" in usage["tenants"]
    assert usage["tenants"]["logged-0"]["kv_block_s"] > 0.0
    assert usage["tenants"]["logged-0"]["compute_total_s"] > 0.0
    # the usage JSONL got per-request records AND a ledger snapshot line
    kinds = [json.loads(ln)["kind"] for ln in open(log) if ln.strip()]
    assert "request" in kinds and "ledger" in kinds, kinds
    reqs = [json.loads(ln) for ln in open(log) if ln.strip()]
    assert any(r.get("tenant", "").startswith("logged-") for r in reqs
               if r["kind"] == "request")


def test_usage_endpoint_404_when_metering_absent():
    g = build_gateway(n_replicas=1, prefix_cache=False)
    try:
        st, data = _get(g.port, "/v1/usage")
        assert st == 404
        assert json.loads(data)["error"] == "metering_disabled"
    finally:
        g.stop()


# ---------------------------------------------------------------------------
# hit attribution + eviction pressure via tenant-stamped published blocks
# ---------------------------------------------------------------------------
def test_cross_tenant_hit_attribution(metered_gw):
    gw, _ = metered_gw
    prefix = list(range(60, 84))  # 3 full blocks at block_size=8
    # tenant A publishes the prefix (sequential: publish-before-next-lookup)
    st, _ = _post(gw.port, {"prompt": prefix + [99, 98], "max_new_tokens": 2},
                  headers={"X-Tenant-Id": "publisher"})
    assert st == 200
    # tenant B hits A's published blocks
    st, _ = _post(gw.port, {"prompt": prefix + [97, 96], "max_new_tokens": 2},
                  headers={"X-Tenant-Id": "consumer"})
    assert st == 200
    usage = gw.meter.usage_report()
    pub = usage["tenants"]["publisher"]
    con = usage["tenants"]["consumer"]
    assert con["hit_tokens_cross"] > 0         # consumer's savings were cross-tenant
    assert con["cached_tokens"] > 0
    assert pub["served_tokens"] >= con["hit_tokens_cross"]  # publisher credited
    assert pub["published_blocks"] >= 3
    # self-hit: the publisher re-sends its own prefix
    st, _ = _post(gw.port, {"prompt": prefix + [95, 94], "max_new_tokens": 2},
                  headers={"X-Tenant-Id": "publisher"})
    assert st == 200
    pub2 = gw.meter.usage_report()["tenants"]["publisher"]
    assert pub2["hit_tokens_self"] > 0


def test_eviction_pressure_attributed_to_publisher():
    from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler

    engine = build_engine(prefix_cache=True)
    meter = TenantMeter(MeteringConfig(enabled=True))
    engine.set_tenant_meter(meter)
    sched = DynamicSplitFuseScheduler(engine)
    rng = np.random.default_rng(0)
    uid = 0
    # hog publishes until the pool is under pressure, then eviction runs
    # (20 rounds x ~5 published full blocks > the 80-block pool)
    for i in range(20):
        sched.submit(uid, rng.integers(0, 100, 40), max_new_tokens=2,
                     tenant="hog")
        sched.run()
        uid += 1
    assert engine.prefix_cache.stats["evictions"] > 0
    kv = meter.kv_block_seconds()
    assert kv.get("hog", 0.0) > 0.0
    with meter._lock:
        led = meter._tenants["hog"]
        assert led.evicted_blocks > 0          # pressure lands on the publisher
        assert led.published_blocks > 0


# ---------------------------------------------------------------------------
# per-tenant shed attribution (admission satellite)
# ---------------------------------------------------------------------------
def test_shed_attributed_per_tenant():
    from deepspeed_tpu.serving import SLOClassConfig

    g = build_gateway(
        n_replicas=1, prefix_cache=False,
        slo_classes={"interactive": SLOClassConfig(max_queue_depth=1)},
        metering=MeteringConfig(enabled=True))
    try:
        g.replicas[0].pause()  # queue builds, nothing drains
        st1, _ = g.submit([1, 2, 3], max_new_tokens=2, tenant="burster")
        assert st1 == 200
        st2, err = g.submit([4, 5, 6], max_new_tokens=2, tenant="burster")
        assert st2 == 429 and err["reason"] == "queue_depth"
        st3, _ = g.submit([7, 8, 9], max_new_tokens=2, tenant="victim")
        assert st3 == 429  # same full queue — but the ledger tells them apart
        usage = g.meter.usage_report()
        ten = {**usage["tenants"]}
        if usage["other"] is not None:
            ten["other"] = usage["other"]
        assert ten["burster"]["shed"] == 1
        assert ten["burster"]["shed_reasons"] == {"queue_depth": 1}
        assert ten["victim"]["shed"] == 1
        assert ten["burster"]["requests"] == 1  # the admitted one
    finally:
        g.replicas[0].resume()
        g.stop()


# ---------------------------------------------------------------------------
# fairness index + starvation instants
# ---------------------------------------------------------------------------
def test_fairness_index_equal_vs_skewed():
    meter = TenantMeter(MeteringConfig(enabled=True))
    for t in ("a", "b", "c", "d"):
        meter.on_compute(t, "decode", 1.0, tokens=10)
        meter.on_admitted(t, 100, 0)
        meter.charge_kv(t, 1.0)
    fair = meter.fairness_index()
    assert fair == pytest.approx(1.0, abs=1e-9)
    skew = TenantMeter(MeteringConfig(enabled=True))
    skew.on_compute("hog", "decode", 10.0, tokens=100)
    skew.on_admitted("hog", 1000, 0)
    for t in ("a", "b", "c"):
        skew.on_compute(t, "decode", 0.1, tokens=1)
        skew.on_admitted(t, 10, 0)
    assert skew.fairness_index() < 0.5 < fair


def test_starvation_instant_latched():
    tr = get_tracer()
    tr.configure(enabled=False)
    fr = get_flight_recorder()
    fr.configure(enabled=True)
    meter = TenantMeter(MeteringConfig(enabled=True, starvation_factor=3.0,
                                       starvation_min_wait_s=0.01))
    # healthy tenants: small waits fill the global window
    for i in range(24):
        meter.on_queue_wait("healthy", "interactive", 0.001, rid=f"h-{i}")
    # the starved tenant's p99 detaches from the global p99
    for i in range(16):
        meter.on_queue_wait("starved", "interactive", 0.5, rid=f"s-{i}")
    with meter._lock:
        led = meter._tenants["starved"]
        assert led.starvations == 1            # LATCHED: one instant per episode
        assert led.starved
        assert meter._tenants["healthy"].starvations == 0
    events = [e for e in fr.dump() if e.get("name") == "tenant_starvation"]
    assert events and events[0]["tenant"] == "starved"
    # recovery re-arms the latch
    for i in range(64):
        meter.on_queue_wait("starved", "interactive", 0.001, rid=f"r-{i}")
    with meter._lock:
        assert not meter._tenants["starved"].starved


# ---------------------------------------------------------------------------
# bounded cardinality: top-K + `other` on /metrics, folded ledgers
# ---------------------------------------------------------------------------
def test_topk_bound_and_fold():
    meter = TenantMeter(MeteringConfig(enabled=True, top_k=3,
                                       max_tracked_tenants=6))
    for i in range(10):  # 10 distinct tenants, spend descending
        t = f"tenant-{i}"
        meter.on_admitted(t, 10, 0)
        meter.on_compute(t, "decode", 10.0 - i, tokens=5)
    rows = meter.gauge_rows()
    tenant_labels = {lab["tenant"] for _, lab, _ in rows if "tenant" in lab}
    assert len(tenant_labels) <= 4             # top_k + the `other` aggregate
    assert "other" in tenant_labels
    assert "tenant-0" in tenant_labels         # the biggest spender exported
    # ledger memory is bounded too: only 6 tracked, the rest folded
    with meter._lock:
        assert len(meter._tenants) == 6
    assert meter.stats["folded_other"] > 0  # one count per folded hook call
    # nothing silently dropped: every request is in SOME ledger
    usage = meter.usage_report()
    total = sum(s["requests"] for s in usage["tenants"].values()) \
        + (usage["other"]["requests"] if usage["other"] else 0)
    assert total == 10


def test_topk_bound_e2e_on_metrics_scrape(metered_gw):
    gw, _ = metered_gw
    # invent more tenants than top_k (8 default): the scrape stays bounded
    for i in range(12):
        st, _ = _post(gw.port, {"prompt": list(range(100 + i, 110 + i)),
                                "max_new_tokens": 2},
                      headers={"X-Tenant-Id": f"cardinality-{i}"})
        assert st == 200
    h = get_health()
    h.configure(enabled=True, export_port=0)
    try:
        h.set_gauge_provider("tenant_meter", gw.meter.gauge_rows)
        text = urllib.request.urlopen(h.server.url + "/metrics",
                                      timeout=10).read().decode()
        spend_lines = [ln for ln in text.splitlines()
                       if ln.startswith("dstpu_serving_tenant_compute_seconds_total{")]
        assert spend_lines, text[:2000]
        labels = set()
        for ln in text.splitlines():
            if 'tenant="' in ln:
                labels.add(ln.split('tenant="', 1)[1].split('"', 1)[0])
        assert len(labels) <= gw.config.metering.top_k + 1, sorted(labels)
        assert "dstpu_serving_tenant_fairness_index" in text
    finally:
        h.shutdown()


def test_dropped_view_settles_and_stops_accruing():
    """A detached engine view (gateway stop) settles its in-flight
    residency charges and stops contributing — no phantom block-seconds
    growing with wall clock after detach."""
    clock = [0.0]
    meter = TenantMeter(MeteringConfig(enabled=True), clock=lambda: clock[0])
    view = meter.engine_view(8)
    view.on_allocate([0, 1, 2])
    view.stamp([0, 1, 2], "a")
    clock[0] = 2.0
    meter.drop_view(view)
    kv = meter.kv_block_seconds()
    assert kv["a"] == pytest.approx(6.0)       # 3 blocks x 2s, settled
    clock[0] = 100.0                            # time passes after detach...
    assert meter.kv_block_seconds()["a"] == pytest.approx(6.0)  # ...no accrual
    with meter._lock:
        assert view not in meter._views


def test_other_row_kv_includes_rest_tenants():
    """The aggregated `other` export row carries ALL KV beyond the top-K
    (rest tenants' charges + in-flight partials), so the exported family
    sums to the pool total."""
    clock = [0.0]
    meter = TenantMeter(MeteringConfig(enabled=True, top_k=1),
                        clock=lambda: clock[0])
    view = meter.engine_view(8)
    view.on_allocate([0, 1, 2, 3])
    view.stamp([0, 1], "big")
    view.stamp([2, 3], "small")
    meter.on_compute("big", "decode", 10.0)    # `big` wins the top-1 cut
    meter.on_compute("small", "decode", 1.0)
    clock[0] = 1.0
    rows = {(n, lab.get("tenant")): v for n, lab, v in meter.gauge_rows()}
    fam = "serving/tenant_kv_block_seconds_total"
    assert rows[(fam, "big")] == pytest.approx(2.0)
    assert rows[(fam, "other")] == pytest.approx(2.0)  # small's in-flight KV
    usage = meter.usage_report()
    assert usage["other"]["kv_block_s"] == pytest.approx(2.0)
    # shed reasons survive the fold into `other`
    meter.on_shed("small", "interactive", "queue_depth")
    assert meter.usage_report()["other"]["shed_reasons"] == {"queue_depth": 1}


# ---------------------------------------------------------------------------
# CONSERVATION (the acceptance bar): compute vs goodput, KV vs occupancy
# integral, under multi-tenant closed-loop HTTP load
# ---------------------------------------------------------------------------
def test_metering_conservation_under_multi_tenant_load():
    import jax.numpy as jnp
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.inference.v2 import (CacheTelemetryConfig,
                                            DSStateManagerConfig,
                                            InferenceEngineV2, PrefixCacheConfig,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.monitor.goodput import configure_goodput, get_goodput

    configure_goodput(enabled=True)
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, num_kv_heads=2, intermediate_size=128,
                            max_seq_len=256, dtype=jnp.float32,
                            attention_impl="reference")
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                              max_ragged_sequence_count=8, max_context=64)
    icfg = RaggedInferenceEngineConfig(
        kv_block_size=8, num_kv_blocks=80, kv_dtype=jnp.float32,
        state_manager=sm, use_pallas_kernels="never",
        prefix_cache=PrefixCacheConfig(
            enabled=True,
            telemetry=CacheTelemetryConfig(enabled=True, mrc_sample_rate=1.0)))
    engine = InferenceEngineV2(TransformerLM(cfg), icfg)
    gw = ServingGateway([engine], GatewayConfig(
        enabled=True, port=0, metering=MeteringConfig(enabled=True))).start()
    try:
        wl = make_multi_tenant_workload(16, n_tenants=3, seed=5, uid_base=0)
        agg, _ = run_http_load(gw.config.host, gw.port, wl, stream=False,
                               concurrency=4)
        assert agg["completed"] == 16, agg
        usage = gw.meter.usage_report()
        tenants = dict(usage["tenants"])
        if usage["other"] is not None:
            tenants["other"] = usage["other"]

        # (a) compute conservation: Σ tenants' compute-seconds == the
        # goodput ledger's serving ACTIVE categories, within 5%
        rep = get_goodput().serving_ledger("0").report()
        active = sum(rep["categories"][c]
                     for c in ("prefill_active", "decode_active", "spec_verify"))
        meter_compute = sum(s["compute_total_s"] for s in tenants.values())
        assert active > 0
        assert abs(meter_compute - active) <= 0.05 * active, \
            (meter_compute, active, rep["categories"])

        # (b) KV conservation: Σ tenants' KV-block-seconds (+ the disclosed
        # untenanted residual) == cache telemetry's independent occupancy
        # integral, within 5%
        integral = engine.cache_telemetry.occupancy_integral_s()
        kv = gw.meter.kv_block_seconds()
        meter_kv = sum(kv.values())
        assert integral > 0
        assert abs(meter_kv - integral) <= 0.05 * integral, (meter_kv, integral)
        # the tenanted share dominates: warmup-free run, every request owned
        assert kv.get("untenanted", 0.0) <= 0.2 * meter_kv
        # every workload tenant got charged something
        for t in {r["tenant"] for r in wl}:
            assert tenants[t]["compute_total_s"] > 0, tenants.keys()
            assert tenants[t]["kv_block_s"] > 0
    finally:
        gw.stop()
        get_goodput().shutdown()


# ---------------------------------------------------------------------------
# zero overhead with the block absent (the PR 1/5 bar)
# ---------------------------------------------------------------------------
def test_zero_overhead_when_metering_absent():
    fr = get_flight_recorder()
    ring_before = fr.total_recorded
    engine = build_engine(prefix_cache=True)
    g = ServingGateway([engine], GatewayConfig(enabled=True))
    assert g.meter is None                     # no plane object at all
    threads_before = {t.name for t in threading.enumerate()}
    g.start()
    try:
        # no engine-side attachment: no views, no stamp arrays, no hooks
        assert engine.state_manager.tenant_meter is None
        assert engine.state_manager.kv_cache._allocator.meter is None
        assert engine.prefix_cache._meter is None
        # no observer on the scheduler (tracing is off too)
        assert g.replicas[0]._scheduler.step_observer is None
        st, req = g.submit([1, 2, 3, 4, 5], max_new_tokens=3, tenant="ignored")
        assert st == 200
        assert req.tenant == "ignored"         # identity still carried (cheap)
        assert req.stream.wait_done(timeout=60)
        new = {t.name for t in threading.enumerate()} - threads_before
        assert not any("meter" in n.lower() or "tenant" in n.lower()
                       for n in new), new
        assert fr.total_recorded == ring_before  # nothing on the flight ring
        assert "metering" not in g.state()
        st, data = _get(g.port, "/v1/usage")
        assert st == 404
    finally:
        g.stop()


# ---------------------------------------------------------------------------
# the check_tenant_labels AST gate (tier-1) + drift catch
# ---------------------------------------------------------------------------
def test_check_tenant_labels_gate():
    from tools.check_tenant_labels import check

    assert check() == [], check()


def test_check_tenant_labels_catches_violations(tmp_path):
    from tools.check_tenant_labels import check

    pkg = tmp_path / "pkg"
    (pkg / "monitor").mkdir(parents=True)
    (pkg / "monitor" / "rogue.py").write_text(
        "def gauge_rows():\n"
        "    return [('serving/rogue_rows', {'tenant': 'acme'}, 1.0)]\n")
    (pkg / "monitor" / "rogue2.py").write_text(
        "def emit(reg, t):\n"
        "    reg.counter(f'serving/tenant_{t}_total').inc()\n")
    (pkg / "serving").mkdir()
    (pkg / "serving" / "metering.py").write_text(
        "def gauge_rows():\n"
        "    return [('serving/tenant_ok', {'tenant': 'a'}, 1.0)]\n")
    bad = check(str(pkg))
    files = {rel for rel, *_ in bad}
    assert os.path.join("monitor", "rogue.py") in files    # labelled row
    assert os.path.join("monitor", "rogue2.py") in files   # tenant-named metric
    assert not any("metering.py" in rel for rel in files)  # the aggregator is allowed
