"""Request-scoped tracing (``serving/reqtrace.py`` + the threaded request
plane): what these pin, layer by layer —

  * request-id hygiene: client ``X-Request-Id`` / W3C ``traceparent``
    sanitized and propagated, ``X-Request-Id`` attached on EVERY gateway
    response path (200 stream + blocking, 400, 404, 429, 503, bad JSON);
  * end-to-end propagation: one client id surfaces in the SSE meta frame,
    the response header, the JSONL summary record, and every span the
    request emitted on the trace bus;
  * the stage breakdown (ingress + queue + prefill + decode) reconstructs
    each completed request's end-to-end latency within 10% under the
    closed-loop HTTP workload (the ISSUE acceptance bar), and every
    completed/shed request yields a summary record;
  * tail-aware sampling: at ``sample_rate=0`` ALL SLO-miss / shed /
    rejected records are retained while healthy ones are dropped;
  * zero overhead with the block absent: no tracing plane, no contexts,
    no scheduler observer, no threads, nothing on the flight ring (the
    ``tests/test_health.py`` before/after pattern);
  * the bounded JSONL log rotates atomically and stays bounded;
  * admission queue depth / shed rate are scrapeable at ``/metrics`` and
    forensic dumps name the in-flight requests on a wedged replica;
  * the ``tools/check_request_tracing.py`` AST gate (tier-1): one
    id-attaching respond helper, every serving span carries request_id.
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
from deepspeed_tpu.monitor.metrics import get_metrics
from deepspeed_tpu.monitor.trace import get_tracer
from deepspeed_tpu.serving import (GatewayConfig, RequestLog, RequestTraceConfig,
                                   ServingGateway, SLOClassConfig,
                                   extract_request_id, parse_sse,
                                   parse_traceparent, sanitize_request_id)
from tools.serving_load import (attribution_table, build_engine, build_gateway,
                                make_workload, read_request_log, run_http_load)


@pytest.fixture(autouse=True)
def _reset_trace_bus():
    """Tracer/flight are process singletons: leave them disarmed and empty
    so this module's enables never leak into other test files (the
    test_monitor_trace/test_health contract)."""
    yield
    get_tracer().reset()
    get_flight_recorder().configure(enabled=False)
    get_flight_recorder().clear()


@pytest.fixture(scope="module")
def traced_gw(tmp_path_factory):
    """Two prefix-cache replicas under one started gateway with request
    tracing ON (sample_rate=1: every terminal logged)."""
    log = str(tmp_path_factory.mktemp("reqlog") / "requests.jsonl")
    g = build_gateway(n_replicas=2, prefix_cache=True,
                      tracing=RequestTraceConfig(enabled=True, log_path=log))
    yield g, log
    g.stop()


def _post(port, body, headers=None, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/generate", json.dumps(body),
                 {"Content-Type": "application/json", **(headers or {})})
    resp = conn.getresponse()
    data = resp.read()
    rid = resp.getheader("X-Request-Id")
    conn.close()
    return resp.status, data, rid


# ---------------------------------------------------------------------------
# id hygiene (pure helpers)
# ---------------------------------------------------------------------------
def test_request_id_sanitize_and_traceparent():
    assert sanitize_request_id("abc-DEF_1.2") == "abc-DEF_1.2"
    assert sanitize_request_id('ev il"id\n{}') == "evilid"
    assert sanitize_request_id("x" * 200) == "x" * 64  # bounded
    assert sanitize_request_id("   ") is None
    assert sanitize_request_id(None) is None
    tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    assert parse_traceparent(tp) == "ab" * 16
    assert parse_traceparent("garbage") is None
    assert parse_traceparent("00-" + "0" * 32 + "-" + "cd" * 8 + "-01") is None
    # precedence: X-Request-Id > traceparent trace-id > generated
    rid, got_tp = extract_request_id({"X-Request-Id": "client-1", "traceparent": tp})
    assert rid == "client-1" and got_tp == "ab" * 16
    rid2, _ = extract_request_id({"traceparent": tp})
    assert rid2 == "ab" * 16
    rid3, tp3 = extract_request_id({})
    assert len(rid3) == 16 and tp3 is None


def test_request_log_rotation_bounded(tmp_path):
    path = str(tmp_path / "req.jsonl")
    log = RequestLog(path, max_bytes=500, max_files=3)
    for i in range(100):
        log.write({"request_id": f"r{i}", "pad": "x" * 40})
    log.close()
    # max_files bounds TOTAL retained files (live + rotations): .3 never
    # appears no matter how many rotations happened
    files = sorted(p for p in os.listdir(tmp_path) if p.startswith("req.jsonl"))
    assert files == ["req.jsonl", "req.jsonl.1", "req.jsonl.2"]
    assert log.rotations > 0 and log.written == 100
    for p in files:  # bounded AND every retained line parses
        full = os.path.join(tmp_path, p)
        assert os.path.getsize(full) <= 500 + 80  # one record of slack
        for line in open(full):
            json.loads(line)
    # the newest record survived in the live file
    assert any(json.loads(l)["request_id"] == "r99" for l in open(path))


# ---------------------------------------------------------------------------
# X-Request-Id on EVERY response path (satellite 1)
# ---------------------------------------------------------------------------
def test_x_request_id_on_every_response_path():
    cfg = GatewayConfig(
        enabled=True,
        slo_classes={"interactive": SLOClassConfig(max_queue_depth=2)})
    g = ServingGateway([build_engine()], cfg).start()
    try:
        port = g.port
        # 200 blocking: client id echoed
        st, body, rid = _post(port, {"prompt": [1, 2, 3], "max_new_tokens": 3,
                                     "stream": False},
                              headers={"X-Request-Id": "my-req-1"})
        assert st == 200 and rid == "my-req-1"
        assert json.loads(body)["request_id"] == "my-req-1"
        # 200 stream: header + meta frame + final frame
        st, body, rid = _post(port, {"prompt": [2, 3, 4], "max_new_tokens": 3},
                              headers={"X-Request-Id": "my-req-2"})
        events = parse_sse(body)
        assert rid == "my-req-2" and events[0]["request_id"] == "my-req-2"
        assert events[-1]["request_id"] == "my-req-2"
        # hostile id sanitized before echo (length + charset)
        st, _, rid = _post(port, {"prompt": [1, 2], "max_new_tokens": 2,
                                  "stream": False},
                           headers={"X-Request-Id": 'e vil"\u00e9{}id' + "y" * 100})
        assert st == 200 and rid == "evilid" + "y" * 58  # 64-char bound
        # 400 invalid request / bad json / unknown class
        for hdr, bad in (({"X-Request-Id": "bad-1"}, {"prompt": []}),
                         ({"X-Request-Id": "bad-2"}, {"prompt": [1], "slo_class": "nope"})):
            st, body, rid = _post(port, bad, headers=hdr)
            assert st == 400 and rid == hdr["X-Request-Id"]
            assert json.loads(body)["request_id"] == rid
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("POST", "/v1/generate", "{not json",
                     {"X-Request-Id": "bad-json-7"})
        resp = conn.getresponse()
        assert resp.status == 400
        assert resp.getheader("X-Request-Id") == "bad-json-7"
        conn.close()
        # 404 + GET endpoints
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("POST", "/nope", "{}", {"X-Request-Id": "nf-1"})
        assert conn.getresponse().getheader("X-Request-Id") == "nf-1"
        conn.close()
        req = urllib.request.Request(f"http://127.0.0.1:{port}/healthz",
                                     headers={"X-Request-Id": "hz-1"})
        assert urllib.request.urlopen(req, timeout=10).headers["X-Request-Id"] == "hz-1"
        # 429 shed at depth, id still attached
        g.replicas[0].pause()
        for i in range(2):
            st, req_obj = g.submit([1, 2, 3 + i], max_new_tokens=2)
            assert st == 200
        st, body, rid = _post(port, {"prompt": [9, 9], "max_new_tokens": 2},
                              headers={"X-Request-Id": "shed-me"})
        assert st == 429 and rid == "shed-me"
        assert json.loads(body)["request_id"] == "shed-me"
        # 503 draining
        g.drain()
        st, body, rid = _post(port, {"prompt": [1, 2], "max_new_tokens": 2},
                              headers={"X-Request-Id": "drained"})
        assert st == 503 and rid == "drained"
        g.drain(False)
        # absent id: one is GENERATED (never a missing header)
        g.replicas[0].resume()
        # the resumed replica pulls the two queued requests on its own thread:
        # posting before it has would be shed again at depth 2 (seen under load)
        deadline = time.monotonic() + 30
        while g.admission.depth() >= 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        st, _, rid = _post(port, {"prompt": [5, 6, 7], "max_new_tokens": 2,
                                  "stream": False})
        assert st == 200 and rid and len(rid) == 16
    finally:
        g.stop()


# ---------------------------------------------------------------------------
# traceparent/e2e propagation: meta frame + log record + spans (satellite 4)
# ---------------------------------------------------------------------------
def test_traceparent_propagation_e2e(traced_gw):
    gw, log = traced_gw
    get_tracer().configure(enabled=True)
    tp = "00-" + "42" * 16 + "-" + "cd" * 8 + "-01"
    st, body, rid = _post(gw.port, {"prompt": list(range(3, 15)),
                                    "max_new_tokens": 4},
                          headers={"X-Request-Id": "e2e-trace-1",
                                   "traceparent": tp})
    assert st == 200 and rid == "e2e-trace-1"
    events = parse_sse(body)
    assert events[0]["meta"] and events[0]["request_id"] == "e2e-trace-1"
    # the summary record carries the id, the traceparent trace-id, and the
    # full stage breakdown the ISSUE names
    recs = [r for r in read_request_log(log) if r["request_id"] == "e2e-trace-1"]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["traceparent"] == "42" * 16
    for key in ("queue_ms", "route_choice", "prefix_hit_tokens", "prefill_ms",
                "ttft_ms", "tpot_ms", "finish_reason", "slo_verdict"):
        assert key in rec, key
    assert rec["finish_reason"] == "length" and rec["route_choice"] in ("0", "1")
    # every span the request emitted carries the id; the canonical stages
    # are all present on the bus
    spans = [e for e in get_tracer().drain()
             if e.get("args", {}).get("request_id") == "e2e-trace-1"]
    names = {e["name"] for e in spans}
    assert {"serving/route", "serving/queue_wait", "serving/prefill_chunk",
            "serving/first_token", "serving/request_done"} <= names, names
    route = next(e for e in spans if e["name"] == "serving/route")
    assert set(route["args"]["scores"]) == {"0", "1"}  # candidate scores
    assert "overlap_blocks" in route["args"]


# ---------------------------------------------------------------------------
# stage breakdown sums to e2e under closed-loop HTTP load (acceptance)
# ---------------------------------------------------------------------------
def test_stage_breakdown_sums_and_every_request_logged(traced_gw):
    gw, log = traced_gw
    wl = make_workload(10, prompt_lo=6, prompt_hi=20, new_lo=3, new_hi=6,
                       rate_rps=None, seed=11, uid_base=700_000)
    agg, recs = run_http_load(gw.config.host, gw.port, wl)
    assert agg["completed"] == 10
    by_rid = {r["request_id"]: r for r in read_request_log(log)}
    checked = 0
    for r in recs:
        rec = by_rid.get(f"load-{r['uid']}")
        assert rec is not None, f"no summary record for uid {r['uid']}"
        if rec["finish_reason"] not in ("length", "eos"):
            continue
        parts = [rec[k] for k in ("ingress_ms", "queue_ms", "prefill_ms",
                                  "decode_ms")]
        assert all(p is not None for p in parts), rec
        total = sum(parts)
        # the acceptance bar: stage breakdown within 10% of measured e2e
        # (2ms absolute floor for CPU-smoke clock granularity)
        assert abs(total - rec["e2e_ms"]) <= max(0.1 * rec["e2e_ms"], 2.0), rec
        # server-side e2e is bounded by the client-observed latency
        assert rec["e2e_ms"] <= r["latency_ms"] + 2.0
        checked += 1
    assert checked == 10
    table = attribution_table([by_rid[f"load-{r['uid']}"] for r in recs])
    assert table["n_completed"] == 10 and table["breakdown_ok_frac"] == 1.0
    assert table["p99_request"]["request_id"].startswith("load-7000")
    assert set(table["stages_p99_ms"]) == {"ingress_ms", "queue_ms",
                                           "prefill_ms", "decode_ms"}


# ---------------------------------------------------------------------------
# tail-aware sampling: misses/shed/rejected retained at sample_rate=0
# ---------------------------------------------------------------------------
def test_tail_sampling_retains_all_misses_at_rate_zero(tmp_path):
    log = str(tmp_path / "tail.jsonl")
    cfg = GatewayConfig(
        enabled=True,
        default_slo_class="tight",
        slo_classes={"tight": SLOClassConfig(ttft_target_ms=0.001,
                                             max_queue_depth=2),
                     "loose": SLOClassConfig(ttft_target_ms=1e9)},
        tracing=RequestTraceConfig(enabled=True, log_path=log, sample_rate=0.0))
    g = ServingGateway([build_engine()], cfg).start()
    try:
        # SLO miss (any real TTFT > 0.001ms): retained despite rate 0
        st, _, _ = _post(g.port, {"prompt": [1, 2, 3, 4], "max_new_tokens": 3,
                                  "stream": False},
                         headers={"X-Request-Id": "miss-1"})
        assert st == 200
        # healthy (loose target met): head-sampled OUT at rate 0
        st, _, _ = _post(g.port, {"prompt": [2, 3, 4, 5], "max_new_tokens": 3,
                                  "slo_class": "loose", "stream": False},
                         headers={"X-Request-Id": "healthy-1"})
        assert st == 200
        # rejected (400) and shed (429): always retained
        st, _, _ = _post(g.port, {"prompt": []}, headers={"X-Request-Id": "rej-1"})
        assert st == 400
        g.replicas[0].pause()
        for i in range(2):
            assert g.submit([1, 2, 3 + i], max_new_tokens=2)[0] == 200
        st, _, _ = _post(g.port, {"prompt": [7, 7], "max_new_tokens": 2},
                         headers={"X-Request-Id": "shed-1"})
        assert st == 429
        g.replicas[0].resume()
        time.sleep(0.1)
    finally:
        g.stop()
    recs = {r["request_id"]: r for r in read_request_log(log)}
    assert recs["miss-1"]["slo_verdict"] == "ttft_miss"
    assert recs["rej-1"]["finish_reason"] == "rejected"
    assert recs["shed-1"]["finish_reason"] == "shed"
    assert "healthy-1" not in recs  # healthy + rate 0 -> dropped
    # the in-memory terminal ring still saw it (dump forensics)


# ---------------------------------------------------------------------------
# zero overhead with the block absent (the PR 1/5 bar)
# ---------------------------------------------------------------------------
def test_zero_overhead_when_tracing_absent():
    fr = get_flight_recorder()
    tr = get_tracer()
    ring_before = fr.total_recorded
    g = ServingGateway([build_engine()], GatewayConfig(enabled=True))
    assert g.reqtrace is None  # no plane object at all
    threads_before = {t.name for t in threading.enumerate()}
    g.start()
    try:
        st, req = g.submit([1, 2, 3, 4, 5], max_new_tokens=3)
        assert st == 200
        assert req.ctx is None            # no per-request context allocation
        assert req.rid and len(req.rid) == 16  # the id contract still holds
        assert g.replicas[0]._scheduler.step_observer is None  # untraced loop
        assert req.stream.wait_done(timeout=60)
        # threads: only what the un-traced gateway already runs (no log
        # writer thread exists in ANY mode — writes are synchronous)
        new = {t.name for t in threading.enumerate()} - threads_before
        assert not any("req" in n.lower() or "trace" in n.lower() for n in new), new
        assert fr.total_recorded == ring_before  # nothing on the flight ring
        assert tr.drain() == []                  # nothing on the trace bus
        assert "tracing" not in g.state()
    finally:
        g.stop()


# ---------------------------------------------------------------------------
# admission gauges on /metrics (satellite 2)
# ---------------------------------------------------------------------------
def test_admission_gauges_scrapeable_on_metrics(traced_gw):
    gw, _ = traced_gw
    h = get_health()
    h.configure(enabled=True, export_port=0)
    try:
        # re-register (a previous test's shutdown() may have cleared it)
        h.set_gauge_provider("gateway", gw.admission.gauge_rows)
        gw.replicas[0].pause()
        gw.replicas[1].pause()
        submitted = []
        for i in range(3):
            st, req = gw.submit([4, 5, 6 + i], max_new_tokens=2)
            assert st == 200
            submitted.append(req)
        text = urllib.request.urlopen(h.server.url + "/metrics",
                                      timeout=10).read().decode()
        depth_lines = [ln for ln in text.splitlines()
                       if ln.startswith("dstpu_gateway_queue_depth{")]
        assert depth_lines, text[:2000]
        assert any('slo_class="interactive"' in ln and 'replica="' in ln
                   and not ln.endswith(" 0") for ln in depth_lines), depth_lines
        assert "dstpu_gateway_shed_rate{" in text
        assert "dstpu_gateway_queued_uncached_tokens{" in text
    finally:
        gw.replicas[0].resume()
        gw.replicas[1].resume()
        for req in submitted:
            assert req.stream.wait_done(timeout=60)
        h.shutdown()


# ---------------------------------------------------------------------------
# forensic dumps name the in-flight requests (satellite 3)
# ---------------------------------------------------------------------------
def test_dump_names_inflight_requests_on_wedged_replica(traced_gw, tmp_path):
    gw, _ = traced_gw
    h = get_health()
    h.configure(enabled=True, dump_dir=str(tmp_path))
    gate = threading.Event()
    originals = [(r, r._scheduler.step) for r in gw.replicas]
    for r, orig in originals:  # wedge whichever replica the router picks
        r._scheduler.step = (lambda o: lambda: (gate.wait(timeout=30) and False) or o())(orig)
    try:
        h.set_dump_provider("inflight_requests", gw.inflight_request_summaries)
        st, req = gw.submit(list(range(9)), max_new_tokens=3,
                            rid="wedged-req-1")
        assert st == 200
        deadline = time.time() + 20  # wait for the driver to PULL it
        while time.time() < deadline:
            if any(r.inflight_summaries() for r in gw.replicas):
                break
            time.sleep(0.01)
        path = h.dump("test_wedge")
        kinds = {}
        for line in open(path):
            e = json.loads(line)
            kinds.setdefault(e.get("kind"), []).append(e)
        assert "inflight_requests" in kinds
        roster = kinds["inflight_requests"][0]["inflight"]
        mine = [row for row in roster if row["request_id"] == "wedged-req-1"]
        assert mine, roster  # the bundle NAMES the wedged request
        assert mine[0]["replica"] == req.replica_name
        assert mine[0]["slo_class"] == "interactive"
    finally:
        gate.set()
        for r, orig in originals:
            r._scheduler.step = orig
        assert req.stream.wait_done(timeout=60)
        h.shutdown()


# ---------------------------------------------------------------------------
# the check_request_tracing AST gate (tier-1, satellite 6)
# ---------------------------------------------------------------------------
def test_check_request_tracing_gate():
    from tools.check_request_tracing import check
    assert check() == []


def test_check_request_tracing_catches_violations(tmp_path):
    from tools.check_request_tracing import check
    # a gateway.py that writes a raw response outside the helper
    bad_gw = tmp_path / "gateway.py"
    bad_gw.write_text(
        "class H:\n"
        "    def _respond(self, code):\n"
        "        self.send_response(code)\n"        # fine: inside the helper
        "    def do_GET(self):\n"
        "        self.send_response(200)\n"         # violation: raw write
        "        self.end_headers()\n")             # violation: raw write
    violations = check(str(tmp_path))
    assert len(violations) == 2
    assert all("outside the _respond helper" in v[3] for v in violations)
    bad_gw.unlink()
    # a serving module emitting spans without request ids
    bad_spans = tmp_path / "emit.py"
    bad_spans.write_text(
        "def f(tr, t0, rid):\n"
        "    tr.instant('serving/x', tid='serving')\n"                  # no rid
        "    tr.instant('serving/y', tid='serving', request_id=rid)\n"  # fine
        "    tr.complete('serving/z', t0, 0.1, args={'n': 1})\n"        # no rid
        "    tr.complete('serving/w', t0, 0.1, args={'request_id': rid})\n")
    violations = check(str(tmp_path))
    assert len(violations) == 2
    whys = sorted(v[3] for v in violations)
    assert "request_id= keyword" in whys[1]
    assert "args={'request_id': ...}" in whys[0]
