"""Live-health plane tests (``monitor/health.py`` / ``monitor/flight.py`` /
``monitor/export.py``): the flight recorder ring, the stall watchdog (trips
on a deliberately-stalled fake collective, stays silent on a healthy loop),
straggler detection, the Prometheus/JSON telemetry exporter, the bounded
saver join + tracer atexit satellites, and the ``tools/check_heartbeats.py``
AST gate (tier-1, the ``check_timed_ops.py`` pattern).
"""

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import comm as dist
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.monitor.export import (escape_label_value, heartbeat_gauge_rows,
                                          render_prometheus, sanitize_metric_name)
from deepspeed_tpu.monitor.flight import FlightRecorder, get_flight_recorder
from deepspeed_tpu.monitor.health import HealthPlane, get_health
from deepspeed_tpu.monitor.metrics import Histogram, MetricsRegistry, get_metrics
from deepspeed_tpu.monitor.trace import get_tracer
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.runtime.resilience import fault_injection

from conftest import tiny_batch

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

REPO = os.path.join(os.path.dirname(__file__), os.pardir)


@pytest.fixture(autouse=True)
def _reset_health_plane():
    """The plane, recorder, registry, and tracer mirror are process-global:
    always leave them fully disarmed so engines built by OTHER test files
    never pay the observing path (same contract as test_monitor_trace) — and
    zero the cumulative trip state both ways, since the plane is a process
    singleton and stall counts would otherwise bleed between tests."""
    h = get_health()
    h.stall_count, h.last_dump_path = 0, None
    yield
    get_health().shutdown()
    h.stall_count, h.last_dump_path = 0, None
    get_tracer().reset()
    get_flight_recorder().configure(enabled=False)
    get_flight_recorder().clear()
    get_metrics().disable()
    get_metrics().reset()
    reg = dist.inflight_collectives
    reg.enabled = False
    reg.on_enter = reg.on_exit = None
    reg._entries.clear()
    fault_injection.clear()


def _wait_for(cond, timeout=10.0, interval=0.01):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_recorder_lossless_up_to_capacity():
    fr = FlightRecorder(capacity=32).configure(enabled=True)
    for i in range(20):
        fr.record("engine", "step", step=i)
    got = fr.dump()
    assert len(got) == 20 and fr.total_recorded == 20
    assert [e["seq"] for e in got] == list(range(20))  # nothing dropped
    assert [e["step"] for e in got] == list(range(20))


def test_flight_recorder_strictly_ordered_overwrite_past_capacity():
    fr = FlightRecorder(capacity=32).configure(enabled=True)
    for i in range(100):
        fr.record("engine", "step", step=i)
    got = fr.dump()
    assert len(got) == 32 and fr.total_recorded == 100
    # exactly the NEWEST window survives, strictly seq-ordered oldest->newest
    assert [e["seq"] for e in got] == list(range(68, 100))
    assert [e["step"] for e in got] == list(range(68, 100))


def test_flight_recorder_disabled_is_noop():
    fr = FlightRecorder(capacity=32)
    fr.record("engine", "step", step=1)
    fr.record_event({"name": "fwd", "ph": "X"})
    assert fr.total_recorded == 0 and fr.dump() == []


def test_tracer_mirror_feeds_ring_with_file_tracing_off(tmp_path):
    """The recorder sees every span/instant the tracer emits even when file
    tracing is disabled — the production default the flight recorder exists
    for — and nothing is buffered for (or written to) a trace file."""
    fr = get_flight_recorder().configure(enabled=True)
    tr = get_tracer()
    assert not tr.enabled
    tr.set_mirror(fr)
    with tr.span("fwd", step=3):
        pass
    tr.instant("marker", tid="comm", note="hello")
    tr.counter("hbm_gb", 3.5)
    names = [e["ev"]["name"] for e in fr.dump() if e["kind"] == "trace"]
    assert {"fwd", "marker", "hbm_gb"} <= set(names)
    assert tr._buf == []  # mirror-only mode: no file-side buffering
    tr.set_mirror(None)
    before = fr.total_recorded
    with tr.span("bwd"):
        pass
    assert fr.total_recorded == before  # unmirrored + disabled -> NULL_SPAN


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------
def test_watchdog_trips_on_stalled_collective_and_dumps_forensics(tmp_path):
    """Acceptance: a deliberately-stalled fake collective trips the watchdog
    within the deadline; the quarantine dump carries all-thread stacks, the
    in-flight collective table, and the flight-recorder ring; the process is
    NOT killed."""
    trips = []
    h = get_health().configure(enabled=True, deadlines={"collective": 0.15},
                               watchdog_poll_s=0.02, dump_dir=str(tmp_path),
                               stall_callback=lambda src, age, path: trips.append((src, age, path)))
    get_flight_recorder().record("engine", "step", step=7)  # ring content to find later
    assert h.watchdog_alive

    token = dist.inflight_collectives.enter("all_reduce", msg_size=4096)
    try:
        # the callback is the LAST act of a trip (counter -> dump -> log ->
        # callback), so waiting on it means the whole bundle is on disk
        assert _wait_for(lambda: trips), "watchdog never tripped"
    finally:
        dist.inflight_collectives.exit(token)

    # the trip is observable three ways: counter, callback, quarantine file
    assert get_metrics().counter("health/stall_total").value >= 1
    assert get_metrics().counter("health/stall_collective_total").value >= 1
    assert trips and trips[0][0] == "collective" and trips[0][1] > 0.15
    dump_path = trips[0][2]
    assert dump_path == h.last_dump_path and os.path.exists(dump_path)

    lines = _read_jsonl(dump_path)
    by_kind = {}
    for entry in lines:
        by_kind.setdefault(entry["kind"], []).append(entry)
    assert by_kind["header"][0]["reason"].startswith("stall_collective")
    # all-thread stacks: at least the main thread and the watchdog itself
    stacks = by_kind["threads"][0]["stacks"]
    assert any("MainThread" in name for name in stacks)
    assert any("dstpu-health-watchdog" in name for name in stacks)
    assert all(isinstance(frames, list) and frames for frames in stacks.values())
    # the in-flight table names the wedged op, its payload, and its age
    inflight = by_kind["inflight_collectives"][0]["entries"]
    assert [(e["op"], e["msg_size"]) for e in inflight] == [("all_reduce", 4096)]
    assert inflight[0]["age_s"] > 0.15
    # heartbeat ages ride along
    assert "collective" in by_kind["heartbeats"][0]["sources"]
    # the flight ring follows the flight_begin marker, in seq order
    ring = [json.loads(ln) for ln in open(dump_path).read().splitlines()]
    begin = next(i for i, e in enumerate(ring) if e["kind"] == "flight_begin")
    tail = ring[begin + 1:]
    assert any(e.get("kind") == "engine" and e.get("step") == 7 for e in tail)
    assert [e["seq"] for e in tail] == sorted(e["seq"] for e in tail)
    # ... and the training process is demonstrably still alive (we are it)


def test_watchdog_silent_on_healthy_heartbeats(tmp_path):
    h = get_health().configure(enabled=True, deadlines={"engine": 0.3},
                               watchdog_poll_s=0.02, dump_dir=str(tmp_path))
    for _ in range(10):  # a healthy loop beating well inside its deadline
        h.beat("engine")
        time.sleep(0.04)
    assert h.stall_count == 0 and h.last_dump_path is None


def test_watchdog_one_trip_per_stall_then_rearms_on_fresh_beat(tmp_path):
    h = get_health().configure(enabled=True, deadlines={"engine": 0.1},
                               watchdog_poll_s=0.02, dump_dir=str(tmp_path))
    h.beat("engine")
    assert _wait_for(lambda: h.stall_count == 1)
    time.sleep(0.3)  # latched: the same stall must not re-fire every poll
    assert h.stall_count == 1
    h.beat("engine")  # recovery re-arms the source
    assert _wait_for(lambda: h.stall_count == 2)


def test_unarmed_sources_are_not_watched(tmp_path):
    h = get_health().configure(enabled=True, deadlines={"saver": 0.05},
                               watchdog_poll_s=0.02, dump_dir=str(tmp_path))
    h.begin("saver")
    h.end("saver")  # op finished: active back to 0, never armed
    time.sleep(0.25)
    assert h.stall_count == 0


def test_sigquit_dump(tmp_path):
    h = get_health().configure(enabled=True, sigquit_dump=True, dump_dir=str(tmp_path))
    os.kill(os.getpid(), signal.SIGQUIT)
    assert _wait_for(lambda: h.last_dump_path is not None)
    assert "sigquit" in os.path.basename(h.last_dump_path)
    assert any(e["kind"] == "threads" for e in _read_jsonl(h.last_dump_path))


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------
def test_straggler_skew_recorded_and_thresholded(tmp_path):
    h = get_health().configure(enabled=True, straggler_threshold_ms=5.0,
                               dump_dir=str(tmp_path))
    reg = get_metrics()
    # rank 0 is 79ms slower than the median host: gauge + counter + breadcrumb
    skew = h.note_straggler([(10, 100.0, 2.0), (10, 20.0, 1.0), (10, 21.0, 1.0)])
    assert skew == pytest.approx(79.0)
    assert reg.gauge("train/straggler_skew_ms").value == pytest.approx(79.0)
    assert reg.counter("health/straggler_total").value == 1
    crumbs = [e for e in get_flight_recorder().dump() if e.get("name") == "straggler"]
    assert crumbs and crumbs[0]["slowest_rank"] == 0
    # balanced hosts: skew below threshold records the gauge but no event
    skew = h.note_straggler([(11, 20.0, 1.0), (11, 21.0, 1.0), (11, 20.5, 1.0)])
    assert skew < 5.0
    assert reg.counter("health/straggler_total").value == 1
    # 2-host pod (even n): true median keeps the straggler visible — the
    # upper median would make its own wall the baseline and report skew 0
    skew = h.note_straggler([(12, 900.0, 1.0), (12, 100.0, 1.0)])
    assert skew == pytest.approx(400.0)
    assert reg.counter("health/straggler_total").value == 2


# ---------------------------------------------------------------------------
# Prometheus text format
# ---------------------------------------------------------------------------
_PROM_SAMPLE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*)\})?'
    r' (?P<value>NaN|[+-]Inf|[+-]?[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?)$')
_PROM_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_prometheus(text):
    """Strict text-format 0.0.4 check: every line is a HELP/TYPE comment or a
    sample matching the exposition grammar, every sample's metric family has
    a preceding TYPE. Returns {name: [(labels_dict, float)]} and the types."""
    samples, types = {}, {}
    assert text.endswith("\n"), "exposition must end with a newline"
    for line in text.splitlines():
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram"), kind
            # one TYPE line per family — real Prometheus scrapers reject
            # the whole exposition on a duplicate
            assert name not in types, f"duplicate TYPE line for {name}"
            types[name] = kind
        else:
            m = _PROM_SAMPLE.match(line)
            assert m, f"unparseable exposition line: {line!r}"
            labels = {k: v.replace('\\"', '"').replace("\\n", "\n").replace("\\\\", "\\")
                      for k, v in _PROM_LABEL.findall(m.group("labels") or "")}
            value = float(m.group("value").replace("Inf", "inf"))
            base = m.group("name")
            for suffix in ("_bucket", "_sum", "_count"):
                if base.endswith(suffix) and base[:-len(suffix)] in types:
                    base = base[:-len(suffix)]
            assert base in types, f"sample {m.group('name')} has no TYPE line"
            samples.setdefault(m.group("name"), []).append((labels, value))
    return samples, types


def test_prometheus_text_round_trips_every_metric_kind():
    reg = MetricsRegistry(enabled=True)
    reg.counter("train/samples").inc(3)
    reg.counter("health/stall_total").inc()  # already _total: no double suffix
    reg.gauge("train/mfu").set(0.415)
    hist = reg.histogram("serving/ttft_ms")
    observations = [0.5, 3.0, 42.0, 900.0, 10_000_000.0]  # incl. +Inf overflow
    for v in observations:
        hist.observe(v)

    samples, types = _parse_prometheus(reg.to_prometheus())

    assert types["dstpu_train_samples_total"] == "counter"
    assert samples["dstpu_train_samples_total"] == [({}, 3.0)]
    assert "dstpu_health_stall_total" in samples  # not ..._total_total
    assert samples["dstpu_train_mfu"] == [({}, pytest.approx(0.415))]

    assert types["dstpu_serving_ttft_ms"] == "histogram"
    buckets = samples["dstpu_serving_ttft_ms_bucket"]
    # cumulative and monotonic, closed by le="+Inf" == count
    counts = [v for _, v in buckets]
    assert counts == sorted(counts)
    assert buckets[-1][0]["le"] == "+Inf" and buckets[-1][1] == len(observations)
    le_2 = next(v for labels, v in buckets if labels["le"] == "2")
    assert le_2 == 1.0  # only the 0.5ms observation
    assert samples["dstpu_serving_ttft_ms_sum"][0][1] == pytest.approx(sum(observations))
    assert samples["dstpu_serving_ttft_ms_count"][0][1] == len(observations)


def test_prometheus_label_escaping_round_trips():
    reg = MetricsRegistry(enabled=True)
    nasty = 'quote:" backslash:\\ newline:\nend'
    text = render_prometheus(reg, extra_gauges=[
        ("health/heartbeat_age_seconds", {"source": nasty}, 1.5)])
    samples, _ = _parse_prometheus(text)
    (labels, value), = samples["dstpu_health_heartbeat_age_seconds"]
    assert labels["source"] == nasty and value == 1.5
    assert escape_label_value(nasty).count("\n") == 0  # escaped flat
    # metric-name sanitizing folds into the legal charset
    assert sanitize_metric_name("serving/ttft p99!") == "dstpu_serving_ttft_p99_"


def test_heartbeat_gauge_rows_render():
    # TWO sources: their age/armed rows interleave, and the renderer must
    # still emit exactly one TYPE header per family
    rows = heartbeat_gauge_rows({"engine": {"age_s": 0.25, "armed": True, "active": 0,
                                            "deadline_s": 60.0, "tripped": False},
                                 "collective": {"age_s": 0.5, "armed": False, "active": 1,
                                                "deadline_s": 0.0, "tripped": False}})
    text = render_prometheus(MetricsRegistry(enabled=True), extra_gauges=rows)
    samples, _ = _parse_prometheus(text)  # parser rejects duplicate TYPE lines
    ages = {labels["source"]: v for labels, v in samples["dstpu_health_heartbeat_age_seconds"]}
    assert ages == {"engine": 0.25, "collective": 0.5}
    armed = {labels["source"]: v for labels, v in samples["dstpu_health_heartbeat_armed"]}
    assert armed == {"engine": 1.0, "collective": 1.0}  # active>0 counts as watched


# ---------------------------------------------------------------------------
# histogram summary (satellite: mean from the locked read)
# ---------------------------------------------------------------------------
def test_histogram_summary_mean_from_locked_read():
    hist = Histogram("x")
    for v in (1.0, 2.0, 3.0):
        hist.observe(v)
    s = hist.summary()
    assert s["count"] == 3 and s["mean"] == pytest.approx(2.0)
    # under a concurrent writer the summary is internally consistent: the
    # mean it reports is exactly total/count of ONE atomic snapshot
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            hist.observe(5.0)

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    try:
        for _ in range(200):
            s = hist.summary()
            assert 1.0 <= s["mean"] <= 5.0
    finally:
        stop.set()
        t.join(timeout=5.0)


# ---------------------------------------------------------------------------
# zero overhead when disabled
# ---------------------------------------------------------------------------
def test_disabled_plane_primitives_allocate_nothing():
    h = HealthPlane()
    h.beat("engine")
    h.touch("engine")
    h.begin("collective")
    h.end("collective")
    h.step_boundary(5)
    assert h._hb == {}  # no entries materialized on the disabled path
    assert h.heartbeats() == {}
    h.disarm("engine")  # safe on an unknown source


def test_all_off_config_adds_no_threads_and_no_health_work(tmp_path, eight_devices):
    """Acceptance: with the `health` block absent, an engine creates no
    watchdog/exporter threads, never materializes a heartbeat entry, and
    records nothing into the flight ring across train_batch steps."""
    fr = get_flight_recorder()
    h = get_health()
    threads_before = set(threading.enumerate())
    ring_before = fr.total_recorded
    engine = _tiny_engine({})
    for i in range(2):
        engine.train_batch(_batch(seed=i))
    assert not h.enabled and not h.watchdog_alive and h.server is None
    assert h._hb == {} and h.stall_count == 0
    assert fr.total_recorded == ring_before  # counter: zero health records
    new = [t for t in set(threading.enumerate()) - threads_before if t.is_alive()]
    assert not [t.name for t in new if t.name.startswith("dstpu-health")]
    assert not dist.inflight_collectives.enabled
    assert len(dist.inflight_collectives) == 0
    engine.destroy()


# ---------------------------------------------------------------------------
# engine end-to-end: config block, /metrics + /healthz, destroy dump
# ---------------------------------------------------------------------------
def _tiny_engine(extra_cfg):
    # deliberately minimal (the test_resilience sizing): these tests exercise
    # the health plane, not the model — engine build + compile dominates
    # their tier-1 cost
    groups.reset()
    model = TransformerLM(TransformerConfig(vocab_size=64, hidden_size=16, num_layers=1,
                                            num_heads=2, max_seq_len=16, intermediate_size=32,
                                            attention_impl="reference", dtype=jnp.float32))
    cfg = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "tpu": {"mesh": {"data": 8}},
        # metrics sampling rides the steps_per_print boundary — the exporter
        # tests need every step recorded
        "steps_per_print": 1,
    }
    cfg.update(extra_cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    return engine


def _batch(seed=0):
    return tiny_batch(batch_size=8, seq=16, vocab=64, seed=seed)


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode()


def test_engine_health_block_serves_metrics_and_healthz(tmp_path, eight_devices):
    """Acceptance: /metrics parses as Prometheus text; /healthz reports
    engine + saver state and per-source heartbeat ages that advance; the
    snapshot file is rewritten atomically each step; destroy() dumps."""
    h = get_health()
    snap = str(tmp_path / "snap.json")
    engine = _tiny_engine({"health": {"export_port": 0, "dump_dir": str(tmp_path),
                                      "deadline_train_step_s": 300,
                                      "snapshot_path": snap, "snapshot_every_steps": 1}})
    assert engine.config.monitor_config.health.enabled
    assert h.enabled and h.server is not None and h.server.port > 0

    engine.train_batch(_batch())
    hz1 = json.loads(_get(h.server.url + "/healthz"))
    assert hz1["engine"]["step"] == 1
    assert hz1["engine"]["last_step_wall_ms"] > 0
    assert hz1["saver"] == {"in_flight": False, "writer_thread": None,
                            "saves_committed": 0, "saves_failed": 0, "last_error": None}
    assert hz1["heartbeats"]["engine"]["armed"]
    age1 = hz1["heartbeats"]["engine"]["age_s"]

    time.sleep(0.15)  # idle: the engine heartbeat age must grow...
    hz_idle = json.loads(_get(h.server.url + "/healthz"))
    assert hz_idle["heartbeats"]["engine"]["age_s"] > age1 + 0.1

    engine.train_batch(_batch(seed=1))
    hz2 = json.loads(_get(h.server.url + "/healthz"))
    assert hz2["engine"]["step"] == 2  # ...and a step resets it + advances state
    assert hz2["heartbeats"]["engine"]["age_s"] < hz_idle["heartbeats"]["engine"]["age_s"]

    samples, types = _parse_prometheus(_get(h.server.url + "/metrics"))
    assert types["dstpu_train_step_time_ms"] == "histogram"
    assert samples["dstpu_train_steps_total"][0][1] == 2.0
    assert samples["dstpu_train_tokens_total"][0][1] == 2 * 8 * 16
    hb_rows = samples["dstpu_health_heartbeat_age_seconds"]
    assert {labels["source"] for labels, _ in hb_rows} >= {"engine"}

    # scrape-less mode: the per-step snapshot is a complete, untorn artifact
    payload = json.load(open(snap))
    assert payload["engine"]["step"] == 2
    assert "heartbeats" in payload and "metrics" in payload
    assert payload["metrics"]["counters"]["train/steps"] == 2
    assert not os.path.exists(snap + ".tmp")  # tmp+rename left nothing behind

    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(h.server.url + "/nope", timeout=10)

    engine.destroy()  # destroy() writes the final forensic dump
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("health_destroy")]
    assert len(dumps) == 1
    kinds = [e["kind"] for e in _read_jsonl(str(tmp_path / dumps[0]))]
    assert {"header", "threads", "heartbeats", "inflight_collectives"} <= set(kinds)


def test_stalled_saver_writer_trips_watchdog_and_join_is_bounded(tmp_path, eight_devices):
    """Acceptance: a stalled saver writer (held on a fault-injection gate)
    trips the `saver` deadline while the run keeps training; satellite: the
    wedged writer cannot hang destroy() forever — the shutdown join times
    out loudly and counts health/saver_join_timeout_total."""
    gate = threading.Event()
    fault_injection.inject("before_manifest", lambda ctx: gate.wait(timeout=60))
    h = get_health()
    engine = _tiny_engine({"health": {"deadline_saver_s": 0.2, "watchdog_poll_s": 0.02,
                                      "dump_on_destroy": False, "dump_dir": str(tmp_path)},
                           "checkpoint": {"async_save": True}})
    engine.train_batch(_batch())
    engine.save_checkpoint(str(tmp_path / "ckpt"), tag="held")
    assert engine._ckpt_saver.in_flight
    assert _wait_for(lambda: h.stall_count >= 1), "stalled writer never tripped"
    assert get_metrics().counter("health/stall_saver_total").value >= 1
    hz = h.healthz_payload()
    assert hz["saver"]["in_flight"] and hz["saver"]["writer_thread"]
    engine.train_batch(_batch(seed=1))  # the step loop is unaffected

    t0 = time.perf_counter()
    assert engine._ckpt_saver.shutdown(timeout=0.3) is False
    assert time.perf_counter() - t0 < 30.0  # bounded, not the unbounded join
    assert get_metrics().counter("health/saver_join_timeout_total").value == 1

    gate.set()  # release: the abandoned writer finishes and the commit lands
    assert engine.flush_checkpoints(raise_on_error=True)
    assert engine._ckpt_saver.shutdown() is True
    engine.destroy()


# ---------------------------------------------------------------------------
# tracer atexit satellite
# ---------------------------------------------------------------------------
def test_tracer_atexit_flushes_tail_on_abrupt_exit(tmp_path):
    """An abrupt sys.exit without drain()/close() must not truncate the tail
    flush_every window of the JSONL artifact."""
    path = str(tmp_path / "trace.jsonl")
    script = (
        "import sys\n"
        "from deepspeed_tpu.monitor.trace import get_tracer\n"
        f"tr = get_tracer().configure(enabled=True, path={path!r}, flush_every=100000)\n"
        "with tr.span('fwd'):\n"
        "    pass\n"
        "tr.instant('tail_marker')\n"
        "sys.exit(0)\n")  # no drain(), no close()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    names = {e["name"] for e in _read_jsonl(path)}
    assert {"fwd", "tail_marker"} <= names, f"tail window lost at exit: {names}"


# ---------------------------------------------------------------------------
# the check_heartbeats AST gate (tier-1)
# ---------------------------------------------------------------------------
def test_check_heartbeats_gate():
    """Every background worker loop in resilience/ + prefetch.py touches a
    heartbeat or a bounded wait (structural, enforced every CI pass)."""
    from tools.check_heartbeats import check
    assert check() == []


def test_check_heartbeats_catches_unwatchable_loop(tmp_path):
    from tools.check_heartbeats import check
    bad = tmp_path / "bad_worker.py"
    bad.write_text(
        "import queue, threading\n"
        "q = queue.Queue()\n"
        "def _worker():\n"
        "    while True:\n"
        "        q.get()\n"  # unbounded wait, no heartbeat
        "def start():\n"
        "    threading.Thread(target=_worker, daemon=True).start()\n")
    violations = check(targets=(str(bad),))
    assert len(violations) == 1 and "_worker" in violations[0]
    good = tmp_path / "good_worker.py"
    good.write_text(
        "import queue, threading\n"
        "q = queue.Queue()\n"
        "def _worker():\n"
        "    while True:\n"
        "        try:\n"
        "            q.get(timeout=0.1)\n"
        "        except queue.Empty:\n"
        "            continue\n"
        "def start():\n"
        "    threading.Thread(target=_worker, daemon=True).start()\n")
    assert check(targets=(str(good),)) == []
    # DEFINING a heartbeat inside the loop is not CALLING one: an uncalled
    # nested def must not satisfy the gate
    sneaky = tmp_path / "sneaky_worker.py"
    sneaky.write_text(
        "import queue, threading\n"
        "q = queue.Queue()\n"
        "def _worker(hb):\n"
        "    while True:\n"
        "        def never_called():\n"
        "            hb.touch('x')\n"
        "        q.get()\n"
        "def start():\n"
        "    threading.Thread(target=_worker, daemon=True).start()\n")
    assert len(check(targets=(str(sneaky),))) == 1
