"""Roofline attribution plane + on-demand profiling (``monitor/roofline.py``).

The PR 16 acceptance bars, test-enforced:

* **zero-overhead-off** — with the ``monitor.roofline`` block absent the
  plane holds no registry, installs no per-compile wrappers (the engine's
  compiled cache holds the raw jitted callables), and starts no threads
  (the PR 5 contract the trace/health/goodput planes carry);
* **cost-join reconciliation** — the registry's measured wall per bucket
  sums to the goodput ledger's serving compute categories within 5% under
  the CPU engine (both instruments watch the same windows, so they can
  never tell different stories about where the time went);
* **verdict math** — with both roofs priced the verdict is
  compute_bound/bandwidth_bound by the binding roof, overhead_bound past
  ``overhead_factor`` x roof, with gap-to-roof disclosed; any missing
  input (CPU peaks, failed cost analysis) yields ``unknown`` + nulls,
  never a guessed utilization;
* **on-demand capture** — ``POST /v1/profile`` on a live gateway produces
  an atomically-renamed XPlane artifact (no ``.tmp-*`` ever visible as a
  result), 409 while a capture is in flight, 404 with the block absent;
* **tooling drift-catch** — ``check_metric_names`` accepts the
  ``profile/`` prefix.
"""

import http.client
import json
import os
import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.monitor.goodput import configure_goodput, get_goodput
from deepspeed_tpu.monitor.health import get_health
from deepspeed_tpu.monitor.metrics import (CHIP_PEAK_FLOPS, CHIP_PEAK_HBM_BW,
                                           compute_mbu, compute_mfu, get_metrics,
                                           peak_flops_per_chip, peak_hbm_bw_per_chip)
from deepspeed_tpu.monitor.roofline import (CaptureBusyError, CaptureManager,
                                            ExecutableCostRegistry, _CapturedExecutable,
                                            configure_roofline, get_capture_manager,
                                            get_roofline)
from deepspeed_tpu.monitor.trace import get_tracer


@pytest.fixture(autouse=True)
def _reset_planes():
    """Process-global planes: leave everything disarmed so engines in other
    test files never pay the observing path."""
    yield
    get_roofline().shutdown()
    get_goodput().shutdown()
    get_metrics().disable()
    get_metrics().reset()
    get_tracer().configure(enabled=False)
    hp = get_health()
    if hp.enabled:
        hp.shutdown()


# ---------------------------------------------------------------------------
# peak tables + MBU companion (satellite: metrics.py)
# ---------------------------------------------------------------------------
def test_peak_tables_keyed_identically_with_v6e():
    assert set(CHIP_PEAK_FLOPS) == set(CHIP_PEAK_HBM_BW)
    assert "v6e" in CHIP_PEAK_FLOPS  # the table used to stop at v5p
    # device_kind alias resolution: the strings real runtimes report
    assert peak_flops_per_chip("TPU v6 lite") == CHIP_PEAK_FLOPS["v6e"]
    assert peak_hbm_bw_per_chip("TPU v6e") == CHIP_PEAK_HBM_BW["v6e"]
    assert peak_flops_per_chip("TPU v5 lite") == CHIP_PEAK_FLOPS["v5e"]
    assert peak_hbm_bw_per_chip("TPU v5p") == CHIP_PEAK_HBM_BW["v5p"]
    # unknown chip -> None, never a guessed roof
    assert peak_flops_per_chip("cpu") is None
    assert peak_hbm_bw_per_chip("cpu") is None


def test_compute_mbu_contract_mirrors_mfu():
    # override path: 1 GB moved in 0.1 s against a 100 GB/s roof = 10%
    assert compute_mbu(1e9, 0.1, peak_bw=100e9) == pytest.approx(0.1)
    # multi-chip denominator scales like compute_mfu's
    assert compute_mbu(1e9, 0.1, n_chips=2, peak_bw=100e9) == pytest.approx(0.05)
    # degenerate inputs -> None, same contract as compute_mfu
    assert compute_mbu(1e9, 0.0, peak_bw=100e9) is None
    assert compute_mbu(1e9, 0.1, peak_bw=None) is None  # CPU: unknown chip
    assert compute_mfu(1e9, 0.1, peak_flops=None) is None


# ---------------------------------------------------------------------------
# config + lifecycle
# ---------------------------------------------------------------------------
def test_roofline_config_presence_enables():
    from deepspeed_tpu.monitor.config import get_monitor_config

    assert not get_monitor_config({}).roofline.enabled
    assert get_monitor_config({"roofline": {}}).roofline.enabled
    cfg = get_monitor_config({"roofline": {"overhead_factor": 3.0}}).roofline
    assert cfg.enabled and cfg.overhead_factor == 3.0
    assert not get_monitor_config(
        {"roofline": {"enabled": False, "overhead_factor": 3.0}}).roofline.enabled


def test_configure_arms_and_shutdown_disarms():
    plane = configure_roofline(enabled=True, peak_flops=1e12, peak_hbm_bw=1e11)
    assert plane.enabled and plane._registry is not None
    assert plane.peaks() == (1e12, 1e11)
    plane.note_wall("b", 0.5)
    assert plane.report()["buckets"]["b"]["wall_s"] == 0.5
    plane.shutdown()
    assert not plane.enabled and plane._registry is None
    # disabled hooks are no-ops, and capture_executable is identity
    plane.note_wall("b", 0.5)
    fn = lambda x: x  # noqa: E731
    assert plane.capture_executable("b", fn) is fn
    assert plane.report()["buckets"] == {}


# ---------------------------------------------------------------------------
# verdict math (peak overrides make the math unit-testable on CPU)
# ---------------------------------------------------------------------------
def test_verdict_math_with_both_roofs_priced():
    plane = configure_roofline(enabled=True, peak_flops=1e12, peak_hbm_bw=1e11,
                               overhead_factor=2.0)
    # compute-bound: t_flops = 1e10/1e12 = 10ms binds over t_bytes = 1ms;
    # measured 12ms is under 2x the 10ms roof
    row = plane.verdict_row({"flops": 1e10, "bytes": 1e8}, wall_s=0.012, calls=1)
    assert row["verdict"] == "compute_bound"
    assert row["roof_s"] == pytest.approx(0.010)
    assert row["gap_to_roof"] == pytest.approx(1.2)
    assert row["mfu"] == pytest.approx(1e10 / 0.012 / 1e12, abs=1e-3)
    # bandwidth-bound: t_bytes = 1e9/1e11 = 10ms binds over t_flops = 1ms
    row = plane.verdict_row({"flops": 1e9, "bytes": 1e9}, wall_s=0.015, calls=1)
    assert row["verdict"] == "bandwidth_bound"
    assert row["mbu"] == pytest.approx(1e9 / 0.015 / 1e11, abs=1e-3)
    # overhead-bound: measured 50ms >> 2 x 10ms roof
    row = plane.verdict_row({"flops": 1e10, "bytes": 1e8}, wall_s=0.050, calls=1)
    assert row["verdict"] == "overhead_bound"
    assert row["gap_to_roof"] == pytest.approx(5.0)


def test_verdict_unknown_when_any_input_missing():
    # no peaks (the CPU default): utilization and verdict stay null even
    # with a priced cost — never a misleading number
    plane = configure_roofline(enabled=True)
    if plane.peaks() != (None, None):  # pragma: no cover - TPU host
        pytest.skip("real chip: peaks are knowable")
    row = plane.verdict_row({"flops": 1e10, "bytes": 1e8}, wall_s=0.01, calls=1)
    assert row["verdict"] == "unknown" and row["mfu"] is None and row["mbu"] is None
    plane.shutdown()
    # one-sided roof must NOT verdict (a missing bandwidth roof could call
    # a bandwidth-bound kernel compute_bound)
    plane = configure_roofline(enabled=True, peak_flops=1e12)
    row = plane.verdict_row({"flops": 1e10, "bytes": 1e8}, wall_s=0.012, calls=1)
    assert row["verdict"] == "unknown" and row["mfu"] is not None
    # no wall samples -> unknown
    plane.configure(peak_hbm_bw=1e11)
    row = plane.verdict_row({"flops": 1e10, "bytes": 1e8}, wall_s=0.0, calls=0)
    assert row["verdict"] == "unknown" and row["mean_wall_s"] is None


def test_cost_fallback_discloses_null_never_crashes():
    plane = configure_roofline(enabled=True, peak_flops=1e12, peak_hbm_bw=1e11)

    class Boom:
        def lower(self, *a):
            raise RuntimeError("no backend")

    plane._registry.register_lazy("bad", Boom(), ())
    plane._registry.note_wall("bad", 0.01)
    row = plane.report()["buckets"]["bad"]  # forcing the thunk must not raise
    assert row["flops"] is None and row["bytes"] is None
    assert row["verdict"] == "unknown"
    assert "RuntimeError" in row["cost_error"]
    # a cost dict with missing keys (some backends price only flops)
    reg = ExecutableCostRegistry()
    reg.register_cost("partial", {"flops": 1e9, "bytes": None})
    reg.note_wall("partial", 0.01)
    row = plane.verdict_row(reg.cost("partial"), 0.01, 1)
    assert row["mfu"] is not None and row["mbu"] is None
    assert row["verdict"] == "unknown"  # both roofs required


# ---------------------------------------------------------------------------
# engine integration: lazy capture + cost-join reconciliation
# ---------------------------------------------------------------------------
def _tiny_serving_run(engine, n_seqs=4, prompt_len=12, horizons=(4, 4, 4)):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, size=prompt_len, dtype=np.int32)
               for _ in range(n_seqs)]
    uids = list(range(n_seqs))
    toks = []
    for u in uids:
        out = engine.put([u], [prompts[u]], sample="greedy")
        toks.append(np.asarray([int(out[0])], np.int32))
    for h in horizons:
        engine.decode(uids, toks, h)
    return uids


def test_zero_overhead_when_block_absent():
    """PR 5 contract: roofline machinery provably absent — no registry, no
    wrappers in the compiled cache, no threads — when never configured."""
    from tools.serving_load import build_engine

    threads_before = set(threading.enumerate())
    plane = get_roofline()
    assert not plane.enabled and plane._registry is None
    engine = build_engine()
    _tiny_serving_run(engine)
    assert plane._registry is None  # traffic armed nothing
    # the compiled cache holds the RAW jitted callables, not wrappers
    for key, fn in engine._compiled.items():
        assert not isinstance(fn, _CapturedExecutable), key
    new = [t for t in set(threading.enumerate()) - threads_before if t.is_alive()]
    assert not [t.name for t in new if "roofline" in t.name.lower() or
                "capture" in t.name.lower()]


def test_cost_join_reconciles_with_goodput_within_5pct():
    """The registry's wall and the goodput ledger's serving compute
    categories watch the same windows: their totals must agree."""
    from tools.serving_load import build_engine

    configure_goodput(enabled=True)
    plane = configure_roofline(enabled=True)
    engine = build_engine()
    engine.goodput_ledger = get_goodput().serving_ledger("rf-test")
    _tiny_serving_run(engine, horizons=(4, 4, 4, 4))
    # every compiled program is wrapped and every bucket has wall samples
    assert all(isinstance(fn, _CapturedExecutable)
               for fn in engine._compiled.values())
    snap = plane._registry.snapshot()
    assert snap, "no buckets registered"
    put_w = sum(w for b, _, w, _ in snap if b.startswith("put/"))
    dec_w = sum(w for b, _, w, _ in snap if b.startswith("decode/"))
    assert put_w > 0 and dec_w > 0
    cats = get_goodput().serving_ledger("rf-test").report()["categories"]
    gp_total = cats.get("prefill_active", 0.0) + cats.get("decode_active", 0.0)
    rf_total = put_w + dec_w
    assert rf_total == pytest.approx(gp_total, rel=0.05), (rf_total, gp_total)
    # the buckets carry the sentinel's label shapes and priced costs (CPU
    # cost_analysis works on this jax; a backend without it would disclose)
    rep = plane.report()
    for bucket, row in rep["buckets"].items():
        assert bucket.startswith(("put/", "decode/")), bucket
        assert row["calls"] > 0
    # verdicts honest on CPU: no peaks -> unknown + null MFU/MBU; with
    # overrides the SAME rows verdict for real
    if rep["peak_flops"] is None:
        assert all(r["verdict"] == "unknown" for r in rep["buckets"].values())
        assert plane.gauge_rows() == []
        plane.configure(peak_flops=1e12, peak_hbm_bw=1e11)
        rep = plane.report()
        priced = [r for r in rep["buckets"].values() if r["flops"] is not None]
        assert priced and all(r["verdict"] != "unknown" for r in priced)
        names = {name for name, _, _ in plane.gauge_rows()}
        assert names <= {"profile/roofline_mfu", "profile/roofline_mbu"}
        assert names


def test_speculative_verify_bucket_joins():
    from tools.serving_load import build_engine

    plane = configure_roofline(enabled=True)
    engine = build_engine()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 128, size=10, dtype=np.int32) for _ in range(2)]
    uids = [0, 1]
    toks = []
    for u in uids:
        out = engine.put([u], [prompts[u]], sample="greedy")
        toks.append(np.asarray([int(out[0])], np.int32))
    drafts = [rng.integers(0, 128, size=3, dtype=np.int32) for _ in uids]
    engine.speculate_decode(uids, toks, drafts, k=3)
    verify = [b for b in plane._registry.buckets() if b.startswith("verify/")]
    assert len(verify) == 1
    _, _, wall, calls = [r for r in plane._registry.snapshot()
                         if r[0] == verify[0]][0]
    assert calls == 1 and wall > 0


# ---------------------------------------------------------------------------
# capture manager + /v1/profile
# ---------------------------------------------------------------------------
def test_capture_manager_modes_and_atomicity(tmp_path):
    cm = CaptureManager()
    root = str(tmp_path / "caps")
    # bounded capture writes a whole artifact, atomically renamed
    final = cm.capture(0.05, root, label="t", max_s=1.0)
    assert os.path.isdir(final) and not os.path.basename(final).startswith(".tmp-")
    assert not [e for e in os.listdir(root) if e.startswith(".tmp-")]
    assert any(files for _, _, files in os.walk(final)), "empty XPlane artifact"
    assert not cm.in_flight
    # manual mode: second start refused while in flight, stop drains
    assert cm.start(str(tmp_path / "manual"))
    assert cm.in_flight
    assert not cm.start(str(tmp_path / "manual2"))
    drained = []
    cm.stop(drain=lambda: drained.append(1))
    assert drained == [1] and not cm.in_flight
    # duration must be positive, and the clamp bounds a typo'd duration
    with pytest.raises(ValueError):
        cm.capture(0.0, root)
    t0 = time.perf_counter()
    cm.capture(500.0, root, label="clamped", max_s=0.05)
    assert time.perf_counter() - t0 < 5.0
    assert get_capture_manager() is get_capture_manager()  # one broker


def test_profile_endpoint_409_busy_and_artifact(tmp_path):
    from deepspeed_tpu.serving.config import ProfilingConfig
    from tools.serving_load import build_gateway

    root = str(tmp_path / "xplane")
    gw = build_gateway(n_replicas=1, prefix_cache=False,
                       profiling=ProfilingConfig(enabled=True, artifact_dir=root,
                                                 default_duration_s=0.1,
                                                 max_duration_s=2.0))

    def post_profile(body, timeout=30):
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=timeout)
        conn.request("POST", "/v1/profile", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = json.loads(resp.read() or b"{}")
        rid = resp.getheader("X-Request-Id")
        conn.close()
        return resp.status, data, rid

    try:
        results = {}

        def long_capture():
            results["bg"] = post_profile({"duration_s": 0.8})

        t = threading.Thread(target=long_capture)
        t.start()
        time.sleep(0.3)  # the background capture is in flight now
        status, body, _ = post_profile({})
        assert status == 409 and body["error"] == "capture_in_flight"
        t.join()
        status, body, rid = results["bg"]
        assert status == 200, body
        assert body["request_id"] == rid  # the id echo rides _respond
        art = body["artifact_dir"]
        assert os.path.isdir(art) and art.startswith(root)
        assert not [e for e in os.listdir(root) if e.startswith(".tmp-")]
        assert any(files for _, _, files in os.walk(art)), "empty XPlane artifact"
        # the broker released: a fresh capture succeeds
        status, body2, _ = post_profile({"duration_s": 0.05})
        assert status == 200 and body2["artifact_dir"] != art
        # bad duration -> 400, never a capture
        status, body3, _ = post_profile({"duration_s": -1})
        assert status == 400 and body3["error"] == "bad_duration"
    finally:
        gw.stop()


def test_profile_endpoint_404_when_block_absent():
    from tools.serving_load import build_gateway

    gw = build_gateway(n_replicas=1, prefix_cache=False)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=10)
        conn.request("POST", "/v1/profile", "{}",
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        conn.close()
        assert resp.status == 404 and body["error"] == "profiling_disabled"
    finally:
        gw.stop()


def test_profiling_config_presence_enables_and_validates():
    from deepspeed_tpu.serving.config import GatewayConfig

    assert not GatewayConfig().profiling.enabled
    cfg = GatewayConfig.from_dict({"profiling": {"artifact_dir": "/tmp/x"}})
    assert cfg.profiling.enabled and cfg.profiling.artifact_dir == "/tmp/x"
    assert not GatewayConfig.from_dict({}).profiling.enabled
    with pytest.raises(ValueError):
        GatewayConfig.from_dict({"profiling": {"max_duration_s": 0}})
    with pytest.raises(ValueError):
        GatewayConfig.from_dict({"profiling": {"bogus_knob": 1}})


# ---------------------------------------------------------------------------
# tooling drift-catch (satellite: check_metric_names)
# ---------------------------------------------------------------------------
def test_check_metric_names_accepts_profile_prefix():
    from tools.check_metric_names import APPROVED_PREFIXES, _FULL_NAME

    assert "profile" in APPROVED_PREFIXES
    assert _FULL_NAME.match("profile/roofline_mfu")
    assert _FULL_NAME.match("profile/captures_total")
    assert not _FULL_NAME.match("rooflines/mfu")
    # every gauge the plane exports passes the gate's full-name rule
    plane = configure_roofline(enabled=True, peak_flops=1e12, peak_hbm_bw=1e11)
    plane._registry.register_cost("b", {"flops": 1e9, "bytes": 1e8})
    plane.note_wall("b", 0.01)
    rows = plane.gauge_rows()
    assert rows
    for name, labels, value in rows:
        assert _FULL_NAME.match(name), name
        assert set(labels) == {"bucket"} and 0 <= value
