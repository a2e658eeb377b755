"""Serving gateway (deepspeed_tpu/serving/): the HTTP/SSE request plane.

What these pin, layer by layer: SSE framing round-trips exactly; greedy
token streams through the full HTTP plane are identical to direct
``InferenceEngineV2``+scheduler runs (prefix cache on AND off — the
gateway schedules WHEN, never changes WHAT); per-SLO-class bounded queues
shed with HTTP 429 at the configured depth while readiness (``/readyz``,
the ``ready`` healthz field) reflects it so an LB can drain without
killing; a slow stream consumer cannot stall the replica decode loop (the
per-request queue is bounded and push never blocks); the prefix-affinity
router strictly beats random placement on the Zipf shared-prefix workload;
a constructed-but-never-started gateway costs zero threads; and the
``tools/check_gateway_api.py`` AST gate keeps the request plane on the
engine's public API (tier-1, every CI pass).
"""

import http.client
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler
from deepspeed_tpu.monitor.health import get_health
from deepspeed_tpu.monitor.metrics import get_metrics
from deepspeed_tpu.serving import (GatewayConfig, ServingGateway, SLOClassConfig,
                                   TokenStream, parse_sse, sse_frame)
from tools.serving_load import (build_engine, build_gateway, make_workload,
                                router_prefix_ab, run_http_load)


@pytest.fixture(scope="module")
def direct_engine():
    return build_engine()


@pytest.fixture(scope="module")
def gw():
    """Two prefix-cache replicas under one started gateway."""
    g = build_gateway(n_replicas=2, prefix_cache=True)
    yield g
    g.stop()


def _post(port, body, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/generate", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _wl(n, seed=0, uid_base=0, new_lo=3, new_hi=6):
    return make_workload(n, prompt_lo=6, prompt_hi=20, new_lo=new_lo, new_hi=new_hi,
                         rate_rps=None, seed=seed, uid_base=uid_base)


# ---------------------------------------------------------------------------
# zero overhead when never started (FIRST: nothing else has started a server)
# ---------------------------------------------------------------------------
def test_constructed_gateway_is_inert(direct_engine):
    """Construction allocates bookkeeping only: no threads, no HTTP socket,
    no metrics flip, no health-plane registration, engine untouched."""
    before = set(threading.enumerate())
    metrics_enabled = get_metrics().enabled
    g = ServingGateway([direct_engine], GatewayConfig())
    assert set(threading.enumerate()) == before
    assert g.port is None and g.url is None and not g.ready
    assert get_metrics().enabled == metrics_enabled
    assert get_health().ready() is True  # no provider registered
    assert direct_engine.query()["tracked"] == 0
    status, payload = g.submit([1, 2, 3])
    assert status == 503 and payload["error"] == "not_ready"
    assert set(threading.enumerate()) == before  # still nothing spawned
    with pytest.raises(ValueError, match="disabled by config"):
        g.start()  # the enabled knob is live, not documentation
    assert set(threading.enumerate()) == before


def test_config_defaults_off_and_ds_config_parse():
    cfg = GatewayConfig()
    assert not cfg.enabled and cfg.port == 0
    for cls in cfg.slo_classes.values():  # every knob defaults to OFF
        assert cls.max_queue_depth == 0 and cls.max_queue_uncached_tokens == 0
        assert cls.ttft_target_ms == 0.0 and cls.tpot_target_ms == 0.0
    # absent block -> off; present block -> presence-enables
    assert not GatewayConfig.from_ds_config({}).enabled
    parsed = GatewayConfig.from_ds_config({"serving": {"gateway": {
        "router": "least_loaded",
        "slo_classes": {"rt": {"max_queue_depth": 3, "ttft_target_ms": 50}},
        "default_slo_class": "rt"}}})
    assert parsed.enabled and parsed.router == "least_loaded"
    assert parsed.slo_classes["rt"].max_queue_depth == 3
    with pytest.raises(ValueError, match="unknown keys"):
        GatewayConfig.from_dict({"prot": 80})
    with pytest.raises(ValueError, match="default_slo_class"):
        GatewayConfig.from_dict({"default_slo_class": "nope"})


# ---------------------------------------------------------------------------
# SSE framing
# ---------------------------------------------------------------------------
def test_sse_frame_roundtrip():
    frames = [{"meta": True, "uid": 7}, {"token": 123, "index": 0},
              {"token": 4, "index": 1, "note": 'quote " and \n newline'},
              {"done": True, "finish_reason": "length", "n_tokens": 2}]
    body = b"".join(sse_frame(f) for f in frames)
    assert parse_sse(body) == frames
    assert parse_sse(body.decode()) == frames  # str or bytes
    # spec: multi-data-line events join with \n
    assert parse_sse('data: {"a":\ndata: 1}\n\n') == [{"a": 1}]


def test_http_stream_and_nonstream_agree(gw):
    prompt = list(range(2, 14))
    st, body = _post(gw.port, {"prompt": prompt, "max_new_tokens": 5})
    assert st == 200
    events = parse_sse(body)
    assert events[0]["meta"] and events[0]["replica"] in ("0", "1")
    toks = [e["token"] for e in events if "token" in e]
    assert [e["index"] for e in events if "token" in e] == list(range(5))
    final = events[-1]
    assert final["done"] and final["n_tokens"] == 5 and final["error"] is None
    assert final["finish_reason"] == "length" and final["dropped"] == 0
    assert final["ttft_ms"] > 0

    st2, body2 = _post(gw.port, {"prompt": prompt, "max_new_tokens": 5,
                                 "stream": False})
    assert st2 == 200
    out = json.loads(body2)
    assert out["tokens"] == toks  # greedy: byte-identical across modes
    # per-SLO-class metrics rode the registry
    reg = get_metrics()
    assert reg.histogram("gateway/ttft_ms_interactive").count > 0
    assert reg.counter("gateway/requests_interactive_total").value > 0
    assert reg.counter("gateway/tokens_streamed_total").value >= 10


def test_http_validation_statuses(gw):
    assert _post(gw.port, {"prompt": []})[0] == 400
    assert _post(gw.port, {"prompt": [1, 2], "max_new_tokens": 0})[0] == 400
    assert _post(gw.port, {"prompt": [1, 2], "slo_class": "nope"})[0] == 400
    assert _post(gw.port, {"prompt": [1, 2], "max_new_tokens": 10**6})[0] == 400
    st, body = _post(gw.port, {"prompt": "not a token list"})
    assert st == 400 and json.loads(body)["error"] == "invalid_request"
    conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=10)
    conn.request("POST", "/v1/generate", "{not json")
    assert conn.getresponse().status == 400
    conn.close()


# ---------------------------------------------------------------------------
# greedy parity: gateway vs direct engine, prefix cache off AND on
# ---------------------------------------------------------------------------
def test_gateway_token_parity_with_direct_engine(direct_engine, gw):
    """The gateway is a scheduling/transport layer: greedy token streams
    through HTTP must be identical to a direct scheduler run over a bare
    engine — with the prefix cache off (1 fresh replica) and on (the shared
    2-replica gateway, whose radix trees may already be warm)."""
    wl = _wl(8, seed=21, uid_base=300)
    sched = DynamicSplitFuseScheduler(direct_engine, token_budget=32)
    for r in wl:
        sched.submit(r["uid"], r["prompt"], max_new_tokens=r["max_new_tokens"])
    direct = sched.run()

    # cache-off gateway over the SAME (drained) engine: reuses its compiled
    # buckets, and parity against its own direct run is the tightest check
    off_gw = ServingGateway([direct_engine], GatewayConfig(enabled=True)).start()
    try:
        for which, g in (("cache_off", off_gw), ("cache_on", gw)):
            for r in wl:
                st, body = _post(g.port, {"prompt": np.asarray(r["prompt"]).tolist(),
                                          "max_new_tokens": r["max_new_tokens"],
                                          "stream": False})
                assert st == 200, (which, body)
                got = json.loads(body)["tokens"]
                assert got == direct[r["uid"]], \
                    f"{which}: uid {r['uid']} diverged from the direct engine"
    finally:
        off_gw.stop()
    # everything drained: engines reusable, nothing tracked, and the
    # replicas discarded finished generations (no per-request growth)
    for eng in gw.engines:
        assert eng.query()["tracked"] == 0
    for r in gw.replicas:
        assert r._scheduler.results == {}


# ---------------------------------------------------------------------------
# backpressure: bounded class queue sheds 429 at depth; readiness reflects it
# ---------------------------------------------------------------------------
def test_backpressure_sheds_429_at_depth(direct_engine):
    cfg = GatewayConfig(
        enabled=True,
        slo_classes={"interactive": SLOClassConfig(max_queue_depth=2)})
    threads_before = set(threading.enumerate())
    g = ServingGateway([direct_engine], cfg).start()
    try:
        g.replicas[0].pause()  # queue builds: nothing is pulled
        reqs = []
        for i in range(2):
            st, req = g.submit([1, 2, 3, 4, 5 + i], max_new_tokens=3)
            assert st == 200
            reqs.append(req)
        assert g.admission.depth() == 2
        assert not g.ready  # at the shed threshold: LB should drain us
        st3, body3 = _post(g.port, {"prompt": [9, 9, 9], "max_new_tokens": 3,
                                    "stream": False})
        assert st3 == 429
        payload = json.loads(body3)
        assert payload["error"] == "shed" and payload["reason"] == "queue_depth"
        assert get_metrics().counter("gateway/shed_interactive_total").value >= 1
        assert g.admission.stats["shed"] >= 1

        g.replicas[0].resume()  # drain: the two admitted requests complete
        for req in reqs:
            assert req.stream.wait_done(timeout=60)
            assert len(req.stream.all_tokens()) == 3
        assert g.ready
    finally:
        g.stop()
    # stop() tore down everything IT started (module-scope gateway threads
    # from other tests survive untouched)
    leaked = [t for t in set(threading.enumerate()) - threads_before if t.is_alive()]
    assert not leaked, [t.name for t in leaked]


@pytest.mark.parametrize("stream", [True, False], ids=["sse", "blocking"])
def test_load_client_counts_every_terminal_once(direct_engine, stream):
    """The client the drills and the tests trust (``run_http_load``): against a
    class queue bounded at two and a replica that pulls nothing until ten are
    shed, two requests wait and complete, ten are shed at once, and each of
    the twelve is one record of exactly one kind in the aggregate."""
    cfg = GatewayConfig(enabled=True, slo_classes={"interactive": SLOClassConfig(max_queue_depth=2)})
    g = ServingGateway([direct_engine], cfg).start()

    def resume_when_ten_are_shed():
        deadline = time.time() + 60
        while g.admission.stats["shed"] < 10 and time.time() < deadline:
            time.sleep(0.005)
        g.replicas[0].resume()

    try:
        g.replicas[0].pause()
        resume = threading.Thread(target=resume_when_ten_are_shed)
        resume.start()
        wl = _wl(12, seed=4, uid_base=7000)
        agg, recs = run_http_load(g.config.host, g.port, wl, concurrency=6, stream=stream)
        resume.join()
    finally:
        g.stop()
    assert len(recs) == agg["n_requests"] == 12
    assert sorted(r["uid"] for r in recs) == [r["uid"] for r in wl]
    done = [r for r in recs if r["status"] == 200]
    shed = [r for r in recs if r["status"] == 429]
    assert (agg["completed"], agg["shed"], agg["errors"]) == (len(done), len(shed), 0) == (2, 10, 0)
    assert agg["shed_rate"] == round(10 / 12, 3)
    want = {r["uid"]: r["max_new_tokens"] for r in wl}
    for r in done:
        assert r["error"] is None and len(r["tokens"]) == want[r["uid"]] and r["ttft_ms"] > 0
    for r in shed:  # a miss with its reason and a hint, answered while the replica still slept: never a hang
        assert r["error"] == "shed" and r["tokens"] == [] and r["ttft_ms"] is None
        assert r["retry_after"] is not None and r["latency_ms"] < min(d["latency_ms"] for d in done)
        assert r["request_id"] == f"load-{r['uid']}"


# ---------------------------------------------------------------------------
# abandonment: timeouts/disconnects release engine-side resources
# ---------------------------------------------------------------------------
def test_scheduler_cancel_releases_active_request(direct_engine):
    """`DynamicSplitFuseScheduler.cancel`: an active request is finished in
    place — engine sequence flushed, lifetime KV reservation released — so
    an abandoned client cannot hold blocks against live traffic."""
    rng = np.random.default_rng(5)
    sched = DynamicSplitFuseScheduler(direct_engine, token_budget=32)
    sched.submit(7001, rng.integers(0, 100, size=10, dtype=np.int32), max_new_tokens=30)
    sched.submit(7002, rng.integers(0, 100, size=8, dtype=np.int32), max_new_tokens=3)
    sched.step()
    assert direct_engine.query()["tracked"] == 2
    assert sched.cancel(7001)
    assert direct_engine.query()["tracked"] == 1
    assert 7001 in sched.finished  # tokens-so-far stay readable
    assert not sched.cancel(9999)
    out = sched.run()
    assert len(out[7002]) == 3
    assert direct_engine.query()["tracked"] == 0


def test_timeout_cancels_abandoned_request(direct_engine):
    """A request whose client times out is torn down (admission queue or
    replica), returns 504, and leaves the engine clean for live traffic."""
    cfg = GatewayConfig(enabled=True, request_timeout_s=0.4)
    g = ServingGateway([direct_engine], cfg).start()
    try:
        g.replicas[0].pause()  # the request can never be served in time
        st, body = _post(g.port, {"prompt": [1, 2, 3, 4, 5, 6],
                                  "max_new_tokens": 4, "stream": False}, timeout=30)
        out = json.loads(body)
        assert st == 504 and out["error"] == "request_timeout"
        assert g.admission.depth() == 0  # cancelled out of the class queue
        g.replicas[0].resume()
        st2, body2 = _post(g.port, {"prompt": [2, 3, 4, 5, 6, 7],
                                    "max_new_tokens": 3, "stream": False})
        assert st2 == 200 and len(json.loads(body2)["tokens"]) == 3
        assert direct_engine.query()["tracked"] == 0
    finally:
        g.stop()


# ---------------------------------------------------------------------------
# slow consumer: bounded stream, non-blocking push, decode loop unaffected
# ---------------------------------------------------------------------------
def test_token_stream_bounded_nonblocking():
    st = TokenStream(capacity=4)
    assert st.push([1, 2, 3]) == 3
    assert st.push([4, 5, 6]) == 1  # bounded: overflow counted, never blocks
    assert st.dropped == 2
    got, done = st.get(timeout=0.01)
    assert got == [1, 2, 3, 4] and not done
    st.finish(reason="length")
    st.finish(reason="error", error="late")  # terminal state latches once
    got, done = st.get(timeout=0.01)
    assert got == [] and done
    assert st.finish_reason == "length" and st.error is None


def test_slow_consumer_does_not_stall_decode(gw):
    """A client that never reads its SSE stream must not stop OTHER
    requests from being served: the replica pushes into the bounded
    per-request queue and moves on."""
    conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=60)
    conn.request("POST", "/v1/generate",
                 json.dumps({"prompt": list(range(10)), "max_new_tokens": 6}),
                 {"Content-Type": "application/json"})
    # deliberately do NOT read the response; the handler thread owns it
    t0 = time.time()
    st, body = _post(gw.port, {"prompt": list(range(5, 17)), "max_new_tokens": 4,
                               "stream": False})
    assert st == 200 and len(json.loads(body)["tokens"]) == 4
    assert time.time() - t0 < 30
    # the lagging stream is complete and loss-free once finally read
    resp = conn.getresponse()
    events = parse_sse(resp.read())
    conn.close()
    assert [e["token"] for e in events if "token" in e] != []
    assert events[-1]["done"] and events[-1]["n_tokens"] == 6
    assert events[-1]["dropped"] == 0


# ---------------------------------------------------------------------------
# router: prefix affinity strictly beats random placement (ISSUE acceptance)
# ---------------------------------------------------------------------------
def test_router_prefix_affinity_beats_random(gw):
    out = router_prefix_ab(n_requests=16, seed=3, gateway=gw)
    assert out["token_parity"], "placement changed the generations"
    arms = out["arms"]
    assert arms["prefix"]["aggregate_hit_rate"] > arms["random"]["aggregate_hit_rate"], arms
    assert out["prefix_beats_random"]
    assert gw.router.policy == "prefix"  # borrowed gateway got its policy back


def test_router_liveness_excludes_dead_replica(gw):
    """The routing oracle never places onto a replica whose driver is not
    serving — here simulated by an un-started replica object."""
    from deepspeed_tpu.serving import EngineReplica, ReplicaRouter

    dead = EngineReplica("dead", gw.engines[0], gw.admission, gw.config)
    router = ReplicaRouter([dead] + gw.replicas, policy="prefix")
    assert dead not in router.live()
    for _ in range(8):
        assert router.select(list(range(12))) is not dead
    none_router = ReplicaRouter([dead], policy="prefix")
    assert none_router.select([1, 2, 3]) is None
    assert none_router.stats["no_live_replica"] == 1


# ---------------------------------------------------------------------------
# readiness: /healthz `ready` field + /readyz on the MONITOR exporter
# ---------------------------------------------------------------------------
def test_healthz_ready_field_and_readyz_drain(gw):
    h = get_health()
    h.configure(enabled=True, export_port=0)
    try:
        # explicit (the singleton provider may have been cleared by another
        # gateway's stop() earlier in the module)
        h.set_ready_provider(lambda: gw.ready)
        url = h.server.url
        hz = json.loads(urllib.request.urlopen(url + "/healthz", timeout=10).read())
        assert hz["ready"] is True
        rz = urllib.request.urlopen(url + "/readyz", timeout=10)
        assert rz.status == 200

        gw.drain()  # alive but not taking traffic: LB must pull us
        hz = json.loads(urllib.request.urlopen(url + "/healthz", timeout=10).read())
        assert hz["ready"] is False
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url + "/readyz", timeout=10)
        assert ei.value.code == 503
        # draining sheds new work with 503 while in-flight finishes
        assert gw.submit([1, 2, 3])[0] == 503
        gw.drain(False)
        assert urllib.request.urlopen(url + "/readyz", timeout=10).status == 200
        # a raising provider fails CLOSED (sick oracle -> out of rotation)
        h.set_ready_provider(lambda: 1 / 0)
        assert h.ready() is False
        # ownership-checked clear: a STALE owner shutting down must not
        # clobber the newer registration (in-process gateway rollover)
        newer = lambda: True  # noqa: E731
        h.set_ready_provider(newer)
        h.clear_ready_provider(lambda: False)  # not the registered object
        assert h.ready() is True
        h.clear_ready_provider(newer)
        assert h.ready() is True  # no provider -> default ready
    finally:
        h.shutdown()


# ---------------------------------------------------------------------------
# serving heartbeats: a wedged replica trips the PR 5 stall watchdog
# ---------------------------------------------------------------------------
def test_wedged_replica_trips_watchdog(gw):
    """The replica driver beats ``serving:<name>`` while it has work (the
    family ``serving`` deadline applies via the prefix fallback): a step
    that wedges goes stale and trips the watchdog with a forensic dump —
    the gateway needs no bespoke monitoring thread of its own."""
    h = get_health()
    h.configure(enabled=True, deadlines={"serving": 0.15}, watchdog_poll_s=0.02)
    stalls0 = h.stall_count
    gate = threading.Event()
    originals = [(r, r._scheduler.step) for r in gw.replicas]
    for r, orig in originals:  # wedge whichever replica the router picks
        r._scheduler.step = (lambda o: lambda: (gate.wait(timeout=30) and False) or o())(orig)
    try:
        st, req = gw.submit(list(range(8)), max_new_tokens=3)
        assert st == 200
        deadline = time.time() + 20
        while h.stall_count == stalls0 and time.time() < deadline:
            time.sleep(0.02)
        assert h.stall_count > stalls0, "wedged replica never tripped the watchdog"
        gate.set()  # un-wedge: the request still completes and beats re-arm
        assert req.stream.wait_done(timeout=60)
        assert len(req.stream.all_tokens()) == 3
    finally:
        gate.set()
        for r, orig in originals:
            r._scheduler.step = orig
        h.shutdown()


# ---------------------------------------------------------------------------
# the check_gateway_api AST gate (tier-1)
# ---------------------------------------------------------------------------
def test_check_gateway_api_gate():
    """The request plane touches only public engine API — structurally
    enforced on every CI pass."""
    from tools.check_gateway_api import check
    assert check() == []


def test_check_gateway_api_catches_reach_in(tmp_path):
    from tools.check_gateway_api import check
    bad = tmp_path / "rogue.py"
    bad.write_text(
        "def place(engine, req):\n"
        "    engine.state_manager.flush_sequence(req.uid)\n"   # named internal
        "    engine._state_manager.allocate(1)\n"              # private reach-in
        "    return engine.max_context\n")                     # public: fine
    violations = check(str(tmp_path))
    assert len(violations) == 2
    whys = sorted(v[3] for v in violations)
    assert "engine internal 'state_manager'" in whys[0]
    assert "private attribute '_state_manager'" in whys[1]
    good = tmp_path / "clean.py"
    bad.unlink()
    good.write_text(
        "class R:\n"
        "    def load(self):\n"
        "        return self._inflight + self.engine.available_blocks\n")
    assert check(str(tmp_path)) == []


# ---------------------------------------------------------------------------
# PR 13: sampling through the gateway — validation at the door, seeded
# determinism end to end
# ---------------------------------------------------------------------------
def test_sampling_params_rejected_with_400(gw):
    """Out-of-range temperature/top_p/seed must be a 400 at the gateway
    door (error=invalid_sampling), never a replica-side failure."""
    for bad in ({"temperature": -0.5}, {"temperature": "hot"}, {"temperature": 1e9},
                {"top_p": 0.0}, {"top_p": 2.0}, {"seed": 2**40}, {"seed": "x"}):
        status, data = _post(gw.port, {"prompt": [1, 2, 3], "max_new_tokens": 2,
                                       "stream": False, **bad})
        assert status == 400, f"{bad} -> {status}"
        payload = json.loads(data)
        assert payload["error"] in ("invalid_sampling", "invalid_request"), payload
    # in-range params are admitted and produce tokens
    status, data = _post(gw.port, {"prompt": [1, 2, 3], "max_new_tokens": 3,
                                   "stream": False, "temperature": 0.8, "top_p": 0.9,
                                   "seed": 7})
    assert status == 200
    assert len(json.loads(data)["tokens"]) == 3


def test_sampled_request_seeded_determinism(gw):
    """Two requests with the SAME (prompt, seed, temperature) must stream
    identical tokens — draws are keyed by (seed, token position), so batch
    composition and replica placement cannot perturb a seeded stream — and
    a different seed diverges."""
    body = {"prompt": [5, 9, 2, 14, 3, 11], "max_new_tokens": 8, "stream": False,
            "temperature": 0.9, "top_p": 0.95, "seed": 1234}
    toks = []
    for seed in (1234, 1234, 99):
        status, data = _post(gw.port, dict(body, seed=seed))
        assert status == 200
        toks.append(json.loads(data)["tokens"])
    assert toks[0] == toks[1], "same seed produced different streams"
    assert toks[0] != toks[2], "different seeds should diverge"


def test_gateway_tree_spec_greedy_parity_with_direct_engine():
    """Greedy parity stays unconditional THROUGH tree verification at the
    gateway: a spec-tree gateway's streams are identical to the direct
    (spec-off) engine's greedy output for the same prompts."""
    from deepspeed_tpu.inference.v2 import SpeculativeConfig
    from tools.serving_load import build_engine as _be

    rng = np.random.default_rng(21)
    motif = rng.integers(0, 128, size=6).tolist()
    prompts = [motif + rng.integers(0, 128, size=3).tolist() + motif + motif
               for _ in range(3)]

    direct = _be()
    want = []
    for i, p in enumerate(prompts):
        got = [int(np.asarray(direct.put([i + 1], [p], sample="greedy")).reshape(-1)[0])]
        while len(got) < 8:
            row = np.asarray(direct.decode([i + 1], [np.asarray([got[-1]], np.int32)], 1))
            got.append(int(row[0, 0]))
        direct.flush(i + 1)
        want.append(got)

    spec = SpeculativeConfig(mode="ngram", k=3, min_match=1, tree_width=3)
    eng = _be(prefix_cache=True, speculative=spec)
    g = ServingGateway([eng], GatewayConfig(enabled=True, port=0)).start()
    try:
        for p, w in zip(prompts, want):
            status, data = _post(g.port, {"prompt": p, "max_new_tokens": 8,
                                          "stream": False})
            assert status == 200
            assert json.loads(data)["tokens"] == w, \
                "tree-spec gateway stream diverged from direct greedy"
        st = g.replicas[0].state()
        assert st.get("speculative", {}).get("drafted", 0) > 0, \
            "the tree drafter never fired — parity was not exercised"
    finally:
        g.stop()
