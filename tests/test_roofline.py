"""On-demand profiling (``monitor/roofline.py``: the capture manager) and the
per-chip peak tables. The cost-analysis roofline plane whose tests were here
(PR 16) went with PR 53; ``tests/test_scopes.py`` and
``tests/perfbench/test_bench_scopes.py`` hold what replaced it.

* **peak tables** — FLOP/s and HBM bytes/s keyed by the same device kinds,
  and ``compute_mbu`` disclosing null where a peak is unknown;
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

import pytest

from deepspeed_tpu.monitor.goodput import get_goodput
from deepspeed_tpu.monitor.health import get_health
from deepspeed_tpu.monitor.metrics import (CHIP_PEAK_FLOPS, CHIP_PEAK_HBM_BW,
                                           compute_mbu, compute_mfu, get_metrics,
                                           peak_flops_per_chip, peak_hbm_bw_per_chip)
from deepspeed_tpu.monitor.roofline import CaptureBusyError, CaptureManager, get_capture_manager
from deepspeed_tpu.monitor.trace import get_tracer


@pytest.fixture(autouse=True)
def _reset_planes():
    """Process-global planes: leave everything disarmed so engines in other
    test files never pay the observing path."""
    yield
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
    assert _FULL_NAME.match("profile/captures_total")
    assert not _FULL_NAME.match("profiles/captures_total")
