"""Causal timeline plane (deepspeed_tpu/monitor/timeline.py +
deepspeed_tpu/serving/timeline.py): cross-replica trace assembly,
per-request critical-path attribution, and differential regression explain.

What these pin, layer by layer: the pure segment model (stamps tile
[t_recv, t_done] so the segments-sum acceptance checks the STAMPS, with
out-of-order stamps clamped, never negative); the overlay re-attributions
(stall gaps and recompile events move milliseconds to their causal owner
WITHOUT creating or destroying any; an applied actuation naming the
request flips a queue verdict to actuation-induced); the differential
explain (dominant stage follows the delta's own direction, in both
directions); the presence-enabled config block (absent = zero objects,
zero chaos observers, zero threads, ``/v1/timeline`` 404s; present
requires the tracing block); the always-retained p99 exemplars outliving
the ring; a REAL migrated request through the disagg broker assembling one
cross-replica timeline whose segments sum to client e2e within tolerance
(ISSUE 20's acceptance) with the broker sub-stages on its critical path
and the satellite handoff fields on its summary record and final SSE
frame; a closed-loop HTTP run with disagg AND control armed where every
terminal request — completed and shed alike — has an addressable
timeline; and the ``tools/check_timeline_joins.py`` AST gate (clean on the live
tree AND catching a violation planted in a temp file).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.monitor.timeline import (CAUSES, HANDOFF_SEGMENTS,
                                            assemble_timeline, build_segments,
                                            coverage_ok, explain_delta,
                                            stage_totals)
from deepspeed_tpu.runtime.resilience import chaos
from deepspeed_tpu.serving import (DisaggConfig, GatewayConfig,
                                   RequestTraceConfig, ServingGateway,
                                   SLOClassConfig, TimelineConfig, parse_sse)
from deepspeed_tpu.serving.timeline import TimelineCollector
from tools.serving_load import build_engine, build_gateway, make_workload, \
    run_http_load

T0 = 1000.0  # synthetic perf_counter origin for the pure-model tests


def _stamps(**offsets_ms):
    """Stamps at T0 + offset milliseconds (``None`` offsets stay absent)."""
    return {k: (T0 + v / 1e3 if v is not None else None)
            for k, v in offsets_ms.items()}


_MIGRATED = dict(t_recv=0.0, t_admitted=10.0, t_dequeued=50.0,
                 t_first_token=200.0, t_handoff_start=250.0,
                 t_handoff_export=300.0, t_handoff_verify=420.0,
                 t_resume_enqueued=430.0, t_resume_submitted=500.0,
                 t_last_token=800.0, t_done=810.0)


# ---------------------------------------------------------------------------
# pure model: segments tile, clamp, and sum to e2e by construction
# ---------------------------------------------------------------------------
def test_segments_tile_and_sum_to_e2e():
    tl = assemble_timeline(_stamps(**_MIGRATED),
                           record={"request_id": "r1",
                                   "handoff_state": "migrated"})
    assert [s["name"] for s in tl["segments"]] == [
        "ingress", "queue", "prefill", "decode", "handoff_export",
        "broker_verify", "handoff_install", "resume_wait", "decode_resumed",
        "close"]
    assert tl["e2e_ms"] == pytest.approx(810.0, abs=1e-6)
    assert tl["sum_ms"] == pytest.approx(tl["e2e_ms"], abs=1e-3)
    assert tl["coverage_ok"] and tl["migrated"]
    # the handoff gap is the broker sub-stages, no more, no less
    assert tl["handoff_gap_ms"] == pytest.approx(
        (300 - 250) + (420 - 300) + (430 - 420) + (500 - 430), abs=1e-3)
    assert tl["dominant_segment"] == "decode_resumed"  # 300 ms
    assert len(tl["critical_path"]) == 5
    assert tl["critical_path"][0]["name"] == "decode_resumed"
    # causes partition the wall: every cause is in the closed taxonomy
    assert set(tl["causes_ms"]) <= set(CAUSES)
    assert sum(tl["causes_ms"].values()) == pytest.approx(tl["sum_ms"], abs=0.01)


def test_shed_stub_is_one_ingress_segment():
    tl = assemble_timeline(_stamps(t_recv=0.0, t_done=1.5),
                           record={"request_id": "shed-1", "status": 429})
    assert [s["name"] for s in tl["segments"]] == ["ingress"]
    assert tl["coverage_ok"]  # 2 ms absolute floor covers sub-ms stubs
    assert not tl["migrated"] and "handoff_gap_ms" not in tl


def test_out_of_order_stamps_clamp_never_negative():
    # a racing t_dequeued BEFORE t_admitted (never the design, always a
    # possibility) must clamp to a zero-duration segment, and the tiling
    # must still sum to e2e exactly
    tl = assemble_timeline(_stamps(t_recv=0.0, t_admitted=40.0,
                                   t_dequeued=20.0, t_first_token=60.0,
                                   t_last_token=90.0, t_done=100.0))
    assert all(s["ms"] >= 0.0 for s in tl["segments"])
    assert tl["sum_ms"] == pytest.approx(tl["e2e_ms"], abs=1e-3)


def test_missing_bounds_produce_no_segments():
    assert build_segments({"t_recv": None, "t_done": T0}) == []
    assert build_segments({"t_recv": T0, "t_done": T0 - 1.0}) == []


def test_coverage_budget_and_floor():
    assert coverage_ok(100.0, 105.0)            # within 10%
    assert not coverage_ok(100.0, 150.0)        # way off
    assert coverage_ok(1.0, 2.9)                # 2 ms absolute floor
    assert not coverage_ok(None, 100.0)
    assert coverage_ok(80.0, 100.0, tolerance=0.25)


# ---------------------------------------------------------------------------
# overlays: re-attribution conserves milliseconds
# ---------------------------------------------------------------------------
def test_stall_overlay_moves_overlap_to_stall_cause():
    stamps = _stamps(t_recv=0.0, t_admitted=5.0, t_dequeued=10.0,
                     t_first_token=110.0, t_last_token=300.0, t_done=310.0)
    # an 80 ms measured driver gap entirely inside the decode segment
    tl = assemble_timeline(stamps, stalls=[(T0 + 0.150, T0 + 0.230)])
    assert tl["stalls"] == 1
    assert tl["causes_ms"]["stall"] == pytest.approx(80.0, abs=1e-3)
    decode_seg = next(s for s in tl["segments"] if s["name"] == "decode")
    assert decode_seg["stall_ms"] == pytest.approx(80.0, abs=1e-3)
    # conservation: the move neither created nor destroyed milliseconds
    assert sum(tl["causes_ms"].values()) == pytest.approx(tl["sum_ms"], abs=0.01)
    assert tl["causes_ms"]["decode"] == pytest.approx(
        (300 - 110) + (310 - 300) - 80.0, abs=1e-3)


def test_stall_overlay_caps_at_segment_duration():
    # a gap LONGER than the segment it overlaps moves at most the segment
    stamps = _stamps(t_recv=0.0, t_admitted=5.0, t_dequeued=10.0,
                     t_first_token=40.0, t_last_token=60.0, t_done=61.0)
    tl = assemble_timeline(stamps, stalls=[(T0 - 1.0, T0 + 1.0)])
    assert sum(tl["causes_ms"].values()) == pytest.approx(tl["sum_ms"], abs=0.01)
    assert tl["causes_ms"]["stall"] == pytest.approx(tl["sum_ms"], abs=0.01)


def test_recompile_overlay_owns_segment_remainder():
    stamps = _stamps(t_recv=0.0, t_admitted=5.0, t_dequeued=10.0,
                     t_first_token=210.0, t_last_token=250.0, t_done=260.0)
    ev = {"bucket": "tokens=64", "t": T0 + 0.100}  # inside prefill
    tl = assemble_timeline(stamps, recompiles=[ev])
    assert tl["recompiles"] == 1
    assert tl["causes_ms"]["recompile"] == pytest.approx(200.0, abs=1e-3)
    assert "prefill" not in tl["causes_ms"]  # fully re-attributed
    assert tl["dominant_cause"] == "recompile"
    assert sum(tl["causes_ms"].values()) == pytest.approx(tl["sum_ms"], abs=0.01)


def test_actuation_flips_queue_verdict():
    stamps = _stamps(t_recv=0.0, t_admitted=2.0, t_dequeued=400.0,
                     t_first_token=430.0, t_last_token=450.0, t_done=455.0)
    base = assemble_timeline(stamps)
    assert base["dominant_cause"] == "queue"
    hit = {"applied": True, "action": "tighten_depth", "policy": "admission",
           "reason": "miss rate 0.5"}
    tl = assemble_timeline(stamps, actuations=[hit])
    assert tl["dominant_cause"] == "actuation-induced"
    assert tl["actuations"] == [{"policy": "admission",
                                 "action": "tighten_depth",
                                 "reason": "miss rate 0.5"}]
    # an unapplied proposal, or an actuation that can't shrink this
    # request's world (a spec-K retune), never flips the verdict
    miss = assemble_timeline(stamps, actuations=[
        {"applied": False, "action": "tighten_depth"},
        {"applied": True, "action": "set_spec_k"}])
    assert miss["dominant_cause"] == "queue"


# ---------------------------------------------------------------------------
# differential explain: the dominant stage follows the delta's direction
# ---------------------------------------------------------------------------
def _plain_tl(prefill_ms, decode_ms):
    return assemble_timeline(_stamps(
        t_recv=0.0, t_admitted=5.0, t_dequeued=10.0,
        t_first_token=10.0 + prefill_ms,
        t_last_token=10.0 + prefill_ms + decode_ms,
        t_done=12.0 + prefill_ms + decode_ms))


def test_explain_delta_directional():
    base = [_plain_tl(100.0, 50.0) for _ in range(4)]
    slow = [_plain_tl(100.0, 190.0) for _ in range(4)]
    reg = explain_delta(base, slow)
    assert reg["delta_e2e_ms"] == pytest.approx(140.0, abs=1e-3)
    assert reg["dominant_stage"] == "decode"
    assert reg["by_stage"]["decode"]["share"] == pytest.approx(1.0, abs=0.01)
    # a SPEEDUP names the stage that shrank, not the largest absolute row
    imp = explain_delta(slow, base)
    assert imp["delta_e2e_ms"] == pytest.approx(-140.0, abs=1e-3)
    assert imp["dominant_stage"] == "decode"
    # a stage present only in one population contributes zero in the other
    mig = [assemble_timeline(_stamps(**_MIGRATED)) for _ in range(4)]
    rep = explain_delta(base, mig)
    assert rep["by_stage"]["broker_verify"]["base_mean_ms"] == 0.0
    assert rep["by_stage"]["broker_verify"]["delta_ms"] > 0
    assert explain_delta([], base)["dominant_stage"] is None
    assert stage_totals(mig[0])["broker_verify"] == pytest.approx(120.0,
                                                                  abs=1e-3)


# ---------------------------------------------------------------------------
# config: presence-enabled, bounded, requires the tracing block
# ---------------------------------------------------------------------------
def test_timeline_config_validation():
    cfg = GatewayConfig.from_dict({"tracing": {}, "timeline": {}})
    assert cfg.timeline.enabled  # presence-enables
    assert cfg.timeline.last_n == 256 and cfg.timeline.tolerance == 0.10
    with pytest.raises(ValueError, match="unknown keys"):
        GatewayConfig.from_dict({"tracing": {}, "timeline": {"lastn": 8}})
    with pytest.raises(ValueError, match="last_n"):
        GatewayConfig.from_dict({"tracing": {}, "timeline": {"last_n": 0}})
    with pytest.raises(ValueError, match="tolerance"):
        GatewayConfig.from_dict({"tracing": {}, "timeline": {"tolerance": 0.0}})
    with pytest.raises(ValueError, match="requires the tracing"):
        GatewayConfig.from_dict({"timeline": {}})
    assert not GatewayConfig().timeline.enabled  # absent = off


# ---------------------------------------------------------------------------
# zero overhead absent: no objects, no observers, no threads, 404
# ---------------------------------------------------------------------------
def test_timeline_absent_costs_nothing():
    eng = build_engine()
    try:
        threads_before = set(threading.enumerate())
        observers_before = dict(chaos._observers)
        g = ServingGateway([eng], GatewayConfig(enabled=True))
        assert g.timeline is None
        assert all(r._timeline is None for r in g.replicas)
        assert set(threading.enumerate()) == threads_before
        assert chaos._observers == observers_before
        # arming the block WITHOUT tracing is a config error, not a
        # silently-stampless collector
        with pytest.raises(ValueError, match="requires the tracing"):
            ServingGateway([eng], GatewayConfig(
                enabled=True, timeline=TimelineConfig(enabled=True)))
        g.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://{g.config.host}:{g.port}/v1/timeline", timeout=10)
            assert ei.value.code == 404
            assert json.loads(ei.value.read())["error"] == "timeline_disabled"
        finally:
            g.stop()
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# collector retention: the p99 exemplar outlives the ring
# ---------------------------------------------------------------------------
def test_exemplar_retention_outlives_ring():
    col = TimelineCollector(TimelineConfig(enabled=True, last_n=2,
                                           exemplar_slots=2))
    for rid, ttft in (("a", 10.0), ("b", 500.0), ("c", 20.0), ("d", 30.0)):
        tl = assemble_timeline(_stamps(t_recv=0.0, t_admitted=1.0,
                                       t_dequeued=2.0, t_first_token=ttft,
                                       t_last_token=ttft + 5.0,
                                       t_done=ttft + 6.0),
                               record={"request_id": rid, "ttft_ms": ttft,
                                       "tpot_ms": ttft / 10.0})
        col._store(tl, tl["record"])
    assert [t["request_id"] for t in col.recent()] == ["c", "d"]  # ring
    # "b" (the p99 outlier) fell off the ring but stays addressable
    assert col.get("b") is not None and col.get("b")["request_id"] == "b"
    assert col.get("a") is None  # neither recent nor an exemplar
    ex = col.exemplars()["ttft"]
    assert [e["request_id"] for e in ex] == ["b", "d"]  # worst-first
    assert col.state()["assembled"] == 4
    names = [name for name, _labels, _v in col.gauge_rows()]
    assert names == ["timeline/assembled_total",
                     "timeline/coverage_failures_total",
                     "timeline/errors_total", "timeline/ring_size"]


# ---------------------------------------------------------------------------
# live: a migrated request assembles ONE cross-replica timeline
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def disagg_tl_gateway():
    gw = build_gateway(
        n_replicas=2, prefix_cache=True, host_blocks=160,
        disagg=DisaggConfig(enabled=True, roles=("prefill", "decode")),
        tracing=RequestTraceConfig(enabled=True),
        timeline=TimelineConfig(enabled=True, last_n=64))
    yield gw
    gw.stop()


def _wait_timeline(gw, rid, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        tl = gw.timeline.get(rid)
        if tl is not None:
            return tl
        time.sleep(0.02)
    raise AssertionError(f"timeline for {rid} never assembled")


def test_migrated_request_timeline_end_to_end(disagg_tl_gateway):
    """ISSUE 20 acceptance: a migrated request's stamps from BOTH replicas
    assemble into one timeline on one clock — segments sum to client e2e
    within tolerance, the broker sub-stages are on the critical path, and
    the satellite handoff fields ride the summary record."""
    gw = disagg_tl_gateway
    rng = np.random.default_rng(23)
    prompt = rng.integers(1, 120, size=12).astype(np.int32)
    status, req = gw.submit(prompt, max_new_tokens=8)
    assert status == 200
    assert req.stream.wait_done(timeout=120)
    assert req.handoff_state == "migrated"
    tl = _wait_timeline(gw, req.ctx.rid)
    assert tl["migrated"] and tl["coverage_ok"]
    assert abs(tl["sum_ms"] - tl["e2e_ms"]) <= max(0.10 * tl["e2e_ms"], 2.0)
    names = {s["name"] for s in tl["segments"]}
    assert set(HANDOFF_SEGMENTS) <= names and "decode_resumed" in names
    assert tl["handoff_gap_ms"] > 0.0
    # satellite 1: the summary record carries the migration's cost
    assert tl["record"]["handoff_state"] == "migrated"
    assert tl["record"]["handoff_ms"] > 0.0
    assert tl["record"]["resume_wait_ms"] >= 0.0
    # the endpoint serves the same assembly
    url = f"http://{gw.config.host}:{gw.port}/v1/timeline"
    with urllib.request.urlopen(f"{url}/{req.ctx.rid}", timeout=10) as resp:
        served = json.loads(resp.read())
    assert served["request_id"] == req.ctx.rid and served["migrated"]
    with urllib.request.urlopen(url, timeout=10) as resp:
        state = json.loads(resp.read())
    assert state["assembled"] >= 1 and "exemplars" in state
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"{url}/nope-0", timeout=10)
    assert ei.value.code == 404
    assert json.loads(ei.value.read())["error"] == "unknown_request_id"


def test_final_sse_frame_carries_handoff_fields(disagg_tl_gateway):
    """Satellite 1: the client sees what the migration cost — the final
    SSE frame of a migrated request carries handoff_state / handoff_ms /
    resume_wait_ms next to the latency fields it already had."""
    gw = disagg_tl_gateway
    rng = np.random.default_rng(29)
    body = json.dumps({"prompt": rng.integers(1, 120, size=10).tolist(),
                       "max_new_tokens": 4, "stream": True}).encode()
    req = urllib.request.Request(
        f"http://{gw.config.host}:{gw.port}/v1/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        events = parse_sse(resp.read())
    final = events[-1]
    assert final.get("done")
    assert final["handoff_state"] == "migrated"
    assert final["handoff_ms"] > 0.0
    assert final["resume_wait_ms"] is not None


def test_attribution_table_handoff_block():
    """Satellite 1 (request-log side): records carrying handoff_state grow
    a migration-cost block in the attribution table."""
    from tools.serving_load import attribution_table

    recs = [{"finish_reason": "length", "ttft_ms": 10.0,
             "handoff_state": "migrated", "handoff_ms": 12.0,
             "resume_wait_ms": 3.0},
            {"finish_reason": "length", "ttft_ms": 11.0,
             "handoff_state": "fallback", "handoff_ms": 7.0,
             "resume_wait_ms": None}]
    out = attribution_table(recs)
    assert out["handoff"]["migrated"] == 1 and out["handoff"]["fallbacks"] == 1
    assert out["handoff"]["handoff_ms_p50"] is not None
    assert out["handoff"]["resume_wait_ms_p50"] == pytest.approx(3.0)
    assert "handoff" not in attribution_table(
        [{"finish_reason": "length", "ttft_ms": 5.0}])


# ---------------------------------------------------------------------------
# closed loop with disagg + control armed: every terminal request has one
# ---------------------------------------------------------------------------
def test_every_terminal_request_has_a_timeline():
    from deepspeed_tpu.serving import ControlConfig

    gw = build_gateway(
        n_replicas=2, prefix_cache=True, host_blocks=160,
        disagg=DisaggConfig(enabled=True, roles=("prefill", "decode")),
        tracing=RequestTraceConfig(enabled=True),
        timeline=TimelineConfig(enabled=True, last_n=256),
        slo_classes={"interactive": SLOClassConfig(priority=0,
                                                   max_queue_depth=1,
                                                   ttft_target_ms=25.0),
                     "batch": SLOClassConfig(priority=1, max_queue_depth=2)},
        control=ControlConfig(enabled=True, interval_s=0.05, window_s=1.0,
                              policies=("admission",), sustain_ticks=2,
                              cooldown_s=0.1, max_actuations_per_window=8,
                              slo_miss_tighten=0.3, slo_miss_relax=0.05,
                              min_queue_depth=1, min_window_completions=2))
    try:
        assert gw.timeline.state()["chaos_observer_armed"]
        wl = make_workload(10, prompt_lo=8, prompt_hi=16, new_lo=3, new_hi=6,
                           rate_rps=None, seed=31, uid_base=0)
        for r in wl:
            r["slo_class"] = "interactive"
        _agg, recs = run_http_load(gw.config.host, gw.port, wl,
                                   concurrency=6, stream=False)
        statuses = {r["status"] for r in recs}
        assert 200 in statuses, recs
        assert 429 in statuses, "depth-1 queue under 6-way load must shed"
        # EVERY terminal request — completed and shed alike — is addressable
        for r in recs:
            tl = _wait_timeline(gw, f"load-{r['uid']}")
            if r["status"] == 200:
                assert tl["coverage_ok"], tl
            else:
                assert tl["segments"][0]["name"] == "ingress"
        for tl in gw.timeline.recent():
            if tl["migrated"]:
                assert tl["coverage_ok"], tl
        # the p99 exemplar is complete and addressable
        ex = gw.timeline.exemplars()["ttft"]
        assert ex and all(e["timeline"]["coverage_ok"] for e in ex)
        assert gw.timeline.get(ex[0]["request_id"]) is not None
        assert gw.timeline.state()["errors"] == 0
    finally:
        gw.stop()
    assert not gw.timeline.state()["chaos_observer_armed"]  # disarmed clean


# ---------------------------------------------------------------------------
# the join gate: clean on the live tree, catches planted drift
# ---------------------------------------------------------------------------
def test_timeline_joins_gate_clean_on_live_tree():
    from tools.check_timeline_joins import check

    assert check() == []


def test_timeline_joins_gate_catches_drift(tmp_path):
    from tools.check_timeline_joins import check, main

    control = tmp_path / "control"
    control.mkdir()
    (tmp_path / "disagg.py").write_text(
        "def broker(trace, t0):\n"
        "    trace.instant('serving/handoff_export', args={'blocks': 3})\n"
        "    trace.complete('serving/broker_verify', t0, args={'n': 1})\n"
        "    observe_latency(t0, 'serving/handoff', span_args={'blocks': 3})\n")
    # the documented fleet-scoped exemption still passes
    (control / "decisions.py").write_text(
        "def emit(trace):\n"
        "    trace.instant('control/decision', args={'action': 'drain'})\n")
    # a NEW unjoinable control emission is caught (not grandfathered)
    (control / "controller.py").write_text(
        "def tick(trace):\n"
        "    trace.span('control/actuate')\n")
    bad = check(str(tmp_path))
    assert [(f, why.split("'")[1]) for f, _ln, _sn, why in bad] == [
        ("disagg.py", "instant"), ("disagg.py", "complete"),
        ("disagg.py", "observe_latency"), ("controller.py", "span")]
    assert main([str(tmp_path)]) == 1
    assert main([]) == 0  # the live tree, via the CLI entry


# ---------------------------------------------------------------------------
# namespace discipline for the new plane
# ---------------------------------------------------------------------------
def test_timeline_metrics_neutral_and_namespaced():
    from tools.check_metric_names import APPROVED_PREFIXES

    assert "timeline" in APPROVED_PREFIXES
