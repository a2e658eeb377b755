"""Self-driving serving (deepspeed_tpu/serving/control/): the feedback
control plane that turns the sensor planes into actuators.

What these pin, layer by layer: the presence-enabled ``control`` config
block (absent = DISARMED: zero threads, zero objects, ``/v1/control``
404s); the four actuator surfaces the controller drives through narrow
public setters (admission depth overrides consulted by ``try_admit``,
replica drain/undrain skipped by the router and the disagg decode picker,
copy-on-write speculative K updates); the decision pass itself, driven
tick-by-tick with synthetic sensor deltas so hysteresis, sustain,
cooldown, and the global flap budget are asserted deterministically; the
bounded JSONL decision log; and the ``tools/check_control_actuators.py`` AST
gate (clean on the live tree AND catches seeded drift).
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler, SpeculativeConfig
from deepspeed_tpu.monitor.metrics import get_metrics
from deepspeed_tpu.serving import (ControlConfig, GatewayConfig, ServingGateway,
                                   SLOClassConfig)
from deepspeed_tpu.serving.admission import AdmissionController
from deepspeed_tpu.serving.control.policies import (AdmissionPolicy,
                                                    ScalingPolicy,
                                                    SpeculationPolicy)
from deepspeed_tpu.serving.disagg import DisaggCoordinator
from tools.serving_load import build_engine, build_gateway


@pytest.fixture(scope="module")
def direct_engine():
    return build_engine()


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# ---------------------------------------------------------------------------
# config: presence-enabled block, validated bands
# ---------------------------------------------------------------------------
def test_control_config_parses_and_validates():
    cfg = GatewayConfig.from_dict({
        "enabled": True,
        "control": {"interval_s": 0.1, "policies": ["admission", "scaling"],
                    "max_actuations_per_window": 2}})
    assert cfg.control.enabled  # presence alone arms it
    assert cfg.control.policies == ("admission", "scaling")
    assert cfg.control.max_actuations_per_window == 2
    # absent block = fully disarmed defaults
    assert not GatewayConfig.from_dict({"enabled": True}).control.enabled

    with pytest.raises(ValueError, match="unknown"):
        GatewayConfig.from_dict({"control": {"no_such_knob": 1}})
    with pytest.raises(ValueError, match="polic"):
        GatewayConfig.from_dict({"control": {"policies": ["admision"]}})
    # hysteresis bands must be ordered or the loop could chase itself
    with pytest.raises(ValueError):
        GatewayConfig.from_dict({"control": {"slo_miss_tighten": 0.1,
                                             "slo_miss_relax": 0.5}})
    with pytest.raises(ValueError):
        GatewayConfig.from_dict({"control": {"spec_k_min": 5, "spec_k_max": 2}})
    # ewma_alpha is a [0, 1] smoothing weight (0 = off)
    assert GatewayConfig.from_dict({"control": {}}).control.ewma_alpha == 0.0
    assert GatewayConfig.from_dict(
        {"control": {"ewma_alpha": 0.2}}).control.ewma_alpha == 0.2
    with pytest.raises(ValueError, match="ewma_alpha"):
        GatewayConfig.from_dict({"control": {"ewma_alpha": 1.5}})


# ---------------------------------------------------------------------------
# controller off: zero threads, zero objects, 404 surface
# ---------------------------------------------------------------------------
def test_controller_off_costs_nothing(direct_engine):
    threads_before = set(threading.enumerate())
    g = ServingGateway([direct_engine], GatewayConfig(enabled=True)).start()
    try:
        assert g.controller is None
        assert not any(t.name == "dstpu-control" for t in threading.enumerate())
        status, body = _get(g.port, "/v1/control")
        assert status == 404 and body["error"] == "control_disabled"
        assert "control" not in g.state()
    finally:
        g.stop()
    leaked = [t for t in set(threading.enumerate()) - threads_before
              if t.is_alive()]
    assert not leaked, [t.name for t in leaked]


def test_controller_on_serves_state_and_stops_clean(direct_engine):
    threads_before = set(threading.enumerate())
    cfg = GatewayConfig(enabled=True,
                        control=ControlConfig(enabled=True, interval_s=0.05,
                                              policies=("admission",)))
    g = ServingGateway([direct_engine], cfg).start()
    try:
        assert g.controller is not None
        assert any(t.name == "dstpu-control" for t in threading.enumerate())
        status, body = _get(g.port, "/v1/control")
        assert status == 200
        assert body["policies"] == ["admission"]
        assert body["errors"] == 0
        assert g.state()["control"]["policies"] == ["admission"]
    finally:
        g.stop()
    leaked = [t for t in set(threading.enumerate()) - threads_before
              if t.is_alive()]
    assert not leaked, [t.name for t in leaked]


# ---------------------------------------------------------------------------
# actuator: admission depth overrides consulted by try_admit
# ---------------------------------------------------------------------------
class _FakeEngine:
    def probe_prefix(self, prompt):
        return 0, 0, 0, None


class _FakeReplica:
    name = "r0"
    engine = _FakeEngine()


class _FakeReq:
    def __init__(self, uid):
        self.uid = uid
        self.slo_class = "interactive"
        self.prompt = np.arange(6, dtype=np.int32)
        self.ctx = None
        self.tenant = None


def test_depth_override_tightens_and_clears():
    adm = AdmissionController(GatewayConfig(
        enabled=True,
        slo_classes={"interactive": SLOClassConfig(max_queue_depth=4)}))
    rep = _FakeReplica()
    for i in range(2):
        ok, why = adm.try_admit(_FakeReq(i), rep)
        assert ok, why
    assert adm.effective_limits("interactive")["max_queue_depth"] == 4

    adm.set_depth_override("interactive", max_queue_depth=2)
    assert adm.effective_limits("interactive")["max_queue_depth"] == 2
    ok, why = adm.try_admit(_FakeReq(2), rep)
    assert not ok and why == "queue_depth"  # override bit, config didn't
    assert adm.state()["depth_overrides"] == {
        "interactive": {"max_queue_depth": 2}}
    rows = {(name, tuple(sorted(labels.items())))
            for name, labels, _v in adm.gauge_rows()}
    assert ("gateway/admitted_rate",
            (("slo_class", "interactive"),)) in rows

    adm.clear_depth_override("interactive")
    assert adm.effective_limits("interactive")["max_queue_depth"] == 4
    ok, why = adm.try_admit(_FakeReq(3), rep)
    assert ok, why
    assert adm.state()["depth_overrides"] == {}


# ---------------------------------------------------------------------------
# actuator: speculative K is copy-on-write (shared config never mutated)
# ---------------------------------------------------------------------------
def test_set_spec_params_copy_on_write(direct_engine):
    spec = SpeculativeConfig(mode="ngram", k=3, min_match=1)
    sched = DynamicSplitFuseScheduler(direct_engine, token_budget=32,
                                      speculative=spec)
    assert sched.spec_params()["k"] == 3
    out = sched.set_spec_params(k=5)
    assert out["k"] == 5 and sched.spec_params()["k"] == 5
    # the injected config object (possibly shared with engine.config /
    # sibling replicas) kept its original K: replace, never setattr
    assert spec.k == 3
    # clamped at the floor; a spec-less scheduler returns None
    assert sched.set_spec_params(k=0)["k"] == 1
    bare = DynamicSplitFuseScheduler(direct_engine, token_budget=32)
    assert bare.spec_params() is None
    assert bare.set_spec_params(k=4) is None


# ---------------------------------------------------------------------------
# actuator: router skips control-drained replicas (with lone-fleet fallback)
# ---------------------------------------------------------------------------
def test_router_skips_draining_replicas(direct_engine):
    class _R:
        def __init__(self, name, draining=False):
            self.name, self.draining = name, draining
            self.role = "mixed"

    r0, r1 = _R("0", draining=True), _R("1")
    from deepspeed_tpu.serving.router import ReplicaRouter

    router = ReplicaRouter.__new__(ReplicaRouter)
    router.stats = {"pool_restricted": 0}
    assert router._placement_pool([r0, r1]) == [r1]
    # every replica draining: degraded placement beats a 503
    r1.draining = True
    assert router._placement_pool([r0, r1]) == [r0, r1]


# ---------------------------------------------------------------------------
# actuator: saturated/drained decode replicas stop receiving handoffs
# ---------------------------------------------------------------------------
def test_disagg_decode_pick_reads_backpressure():
    class _R:
        def __init__(self, name, load, max_inflight=4, draining=False,
                     role="decode", alive=True):
            self.name, self.load, self.max_inflight = name, load, max_inflight
            self.draining, self.role, self.alive = draining, role, alive

    src = _R("p0", 0, role="prefill")
    d_busy, d_free = _R("d0", 4), _R("d1", 1)
    coord = DisaggCoordinator([src, d_busy, d_free], config=None)
    # the saturated decode replica (load == max_inflight) never gets picks
    assert coord.pick_decode_replica(src) is d_free
    d_free.draining = True
    assert coord.pick_decode_replica(src) is None  # fallback-in-place
    d_free.draining = False
    d_busy.load = 0
    assert coord.pick_decode_replica(src) is d_busy  # least-loaded again


# ---------------------------------------------------------------------------
# the decision pass: hysteresis, sustain, cooldown, flap budget — tick-driven
# ---------------------------------------------------------------------------
def _armed_gateway(direct_engine, **ctl):
    base = dict(enabled=True, interval_s=0.05, window_s=1.5,
                policies=("admission",), sustain_ticks=2, cooldown_s=0.0,
                max_actuations_per_window=100, min_window_completions=2,
                slo_miss_tighten=0.5, slo_miss_relax=0.1, min_queue_depth=1)
    base.update(ctl)
    cfg = GatewayConfig(
        enabled=True,
        slo_classes={"interactive": SLOClassConfig(priority=0,
                                                   ttft_target_ms=50.0,
                                                   max_queue_depth=8),
                     "batch": SLOClassConfig(priority=1, max_queue_depth=32)},
        control=ControlConfig(**base))
    # NOT started: the controller exists but its thread doesn't — tests
    # drive tick() with synthetic clocks and synthetic counter deltas
    return ServingGateway([direct_engine], cfg)


def test_tighten_sheds_victim_then_relaxes_and_clears(direct_engine, tmp_path):
    g = _armed_gateway(direct_engine,
                       decision_log_path=str(tmp_path / "decisions.jsonl"))
    ctl = g.controller
    reg = get_metrics()
    done = reg.counter("gateway/completed_interactive_total")
    miss = reg.counter("gateway/slo_ttft_miss_interactive_total")

    ctl.tick(now=0.0)                      # baseline sample
    done.inc(4); miss.inc(4); ctl.tick(now=1.0)   # sustained run 1
    assert ctl.stats["applied"] == 0       # one noisy window never actuates
    done.inc(4); miss.inc(4); ctl.tick(now=2.0)   # sustained run 2: actuate
    assert ctl.stats["applied"] == 1
    # the VICTIM (lower-priority batch) was tightened, not interactive
    assert g.admission.state()["depth_overrides"] == {
        "batch": {"max_queue_depth": 16}}

    # recovery: healthy windows relax the victim back and finally clear
    done.inc(8); ctl.tick(now=3.0)         # miss rate falls mid-band: no-op
    done.inc(8); ctl.tick(now=4.0)         # relax run 1
    done.inc(4); ctl.tick(now=5.0)         # relax run 2: 16*2 >= entry 32
    assert g.admission.state()["depth_overrides"] == {}
    applied = [d for d in ctl.decisions.recent() if d["applied"]]
    assert [d["action"] for d in applied] == ["tighten_depth", "clear_depth"]
    assert all(d["sensors"] for d in applied)

    # the JSONL mirror parses line-for-line with the same records
    ctl.decisions.close()
    lines = [json.loads(ln) for ln
             in (tmp_path / "decisions.jsonl").read_text().splitlines()]
    assert [d["action"] for d in lines if d["applied"]] == \
        ["tighten_depth", "clear_depth"]
    assert all("sensors" in d and "reason" in d for d in lines)


def test_cooldown_blocks_repeat_actuation(direct_engine):
    g = _armed_gateway(direct_engine, cooldown_s=100.0)
    ctl = g.controller
    done = get_metrics().counter("gateway/completed_interactive_total")
    miss = get_metrics().counter("gateway/slo_ttft_miss_interactive_total")
    ctl.tick(now=0.0)
    done.inc(4); miss.inc(4); ctl.tick(now=1.0)
    done.inc(4); miss.inc(4); ctl.tick(now=2.0)
    assert ctl.stats["applied"] == 1
    done.inc(4); miss.inc(4); ctl.tick(now=3.0)   # still missing hard
    assert ctl.stats["applied"] == 1              # cooldown holds the policy


def test_flap_budget_defers_past_max_actuations(direct_engine):
    g = _armed_gateway(direct_engine, max_actuations_per_window=1,
                       window_s=100.0)
    ctl = g.controller
    done = get_metrics().counter("gateway/completed_interactive_total")
    miss = get_metrics().counter("gateway/slo_ttft_miss_interactive_total")
    ctl.tick(now=0.0)
    done.inc(4); miss.inc(4); ctl.tick(now=1.0)
    done.inc(4); miss.inc(4); ctl.tick(now=2.0)   # applied #1 fills the budget
    done.inc(4); miss.inc(4); ctl.tick(now=3.0)   # proposal -> DEFERRED
    assert ctl.stats["applied"] == 1
    assert ctl.stats["deferred"] >= 1
    deferred = [d for d in ctl.decisions.recent() if not d["applied"]]
    assert deferred and "budget" in deferred[-1]["reason"]
    assert deferred[-1]["sensors"]  # a deferred decision still justifies


def test_ewma_smooths_bursty_idle_band_walk(direct_engine):
    """Satellite 2 (ISSUE 20): the idle_frac sensor walks the drain band
    under a BURSTY synthetic-clock trace — three fully-idle ticks, one
    half-busy burst, repeating. Raw sensing (ewma_alpha=0) resets the
    scaling policy's sustain counter at every burst, so a fleet that is
    87.5% idle never drains; ewma_alpha=0.1 smooths the dips inside the
    band and the drain fires. The snapshot keeps BOTH values (idle_frac
    vs idle_frac_raw) so decision records stay auditable, and the applied
    record carries the satellite-3 inflight_rids roster."""
    eng2 = build_engine()
    try:
        applied_by_alpha = {}
        drains = None
        for alpha in (0.0, 0.1):
            cfg = GatewayConfig(
                enabled=True,
                control=ControlConfig(enabled=True, interval_s=0.05,
                                      window_s=1.0, policies=("scaling",),
                                      sustain_ticks=5, cooldown_s=0.0,
                                      max_actuations_per_window=100,
                                      idle_frac_drain=0.85,
                                      queue_depth_undrain=10_000,
                                      min_active_replicas=1,
                                      ewma_alpha=alpha))
            g = ServingGateway([direct_engine, eng2], cfg)  # NOT started
            ctl = g.controller
            acc = {"wall": 0.0, "idle": 0.0}

            def raw(now, _acc=acc):
                return {"t": now, "classes": {}, "spec": {},
                        "goodput": {"idle_s": _acc["idle"],
                                    "wall_s": _acc["wall"]}}

            ctl._raw_sample = raw
            # the gateway is never started (no driver threads to race the
            # synthetic clock), so the liveness the scaling policy needs is
            # part of the synthetic snapshot too
            orig_sense = ctl._sense

            def sense(now, _orig=orig_sense):
                snap = _orig(now)
                for row in snap["replicas"]:
                    row["alive"] = True
                return snap

            ctl._sense = sense
            ctl.tick(now=0.0)  # baseline sample (no delta yet)
            for k, r in enumerate([1.0, 1.0, 1.0, 0.5] * 3):
                acc["wall"] += 1.0
                acc["idle"] += r
                ctl.tick(now=float(k + 1))
            applied_by_alpha[alpha] = ctl.stats["applied"]
            snap = ctl._last_snap
            assert snap["idle_frac_raw"] == pytest.approx(0.5)  # last burst
            if alpha == 0.0:
                assert snap["idle_frac"] == snap["idle_frac_raw"]
            else:
                assert snap["idle_frac"] > 0.85  # smoothed inside the band
                drains = [d for d in ctl.decisions.recent() if d["applied"]]
        # raw: the burst resets sustain every period -> never drains;
        # smoothed: the EWMA never leaves the band -> exactly one drain
        assert applied_by_alpha[0.0] == 0
        assert applied_by_alpha[0.1] == 1
        assert [d["action"] for d in drains] == ["drain_replica"]
        assert "idle_frac" in drains[0]["sensors"]
        # satellite 3: every decision record carries the in-flight roster
        # at actuation time (the timeline plane's clock-free join key)
        assert drains[0]["inflight_rids"] == []
    finally:
        eng2.shutdown()


# ---------------------------------------------------------------------------
# policies in isolation: synthetic snapshots, no gateway at all
# ---------------------------------------------------------------------------
def _ctl_cfg(**kw):
    base = dict(enabled=True, sustain_ticks=1, min_window_completions=2,
                slo_miss_tighten=0.5, slo_miss_relax=0.1, min_queue_depth=1,
                queue_depth_undrain=1, idle_frac_drain=0.9,
                min_active_replicas=1, spec_accept_high=0.8, spec_accept_low=0.4,
                spec_k_min=1, spec_k_max=8, spec_min_window_drafted=8)
    base.update(kw)
    return ControlConfig(**base)


def test_scaling_policy_restart_beats_undrain_and_floors_drain():
    pol = ScalingPolicy(_ctl_cfg())
    reps = [{"name": "0", "alive": False, "paused": False, "draining": False,
             "load": 0, "spec": None},
            {"name": "1", "alive": True, "paused": False, "draining": True,
             "load": 0, "spec": None},
            {"name": "2", "alive": True, "paused": False, "draining": False,
             "load": 2, "spec": None}]
    out = pol.propose({"replicas": reps, "depth_total": 3, "idle_frac": 0.0})
    assert [p["action"] for p in out] == ["restart_replica"]
    assert out[0]["args"] == {"replica": "0", "op": "restart"}
    # no dead replica: pressure un-drains the drained one
    reps[0]["alive"] = True
    out = pol.propose({"replicas": reps, "depth_total": 3, "idle_frac": 0.0})
    assert out[0]["args"] == {"replica": "1", "op": "undrain"}
    # sustained idle drains the least-loaded active — but never under the floor
    reps[1]["draining"] = False
    out = pol.propose({"replicas": reps, "depth_total": 0, "idle_frac": 0.99})
    assert out[0]["args"]["op"] == "drain"
    lone = [{"name": "0", "alive": True, "paused": False, "draining": False,
             "load": 0, "spec": None}]
    assert pol.propose({"replicas": lone, "depth_total": 0,
                        "idle_frac": 0.99}) == []


@pytest.mark.parametrize("body", [{"retune_min_bucket_count": 3}, {"retune_max_sweeps": 2},
                                  {"retune_artifact_dir": "/tmp/x"}, {"policies": ["retune"]}])
def test_retune_fields_are_refused(body):
    """The retune policy and its three fields are gone: a configuration that
    names them fails as any unknown key or policy fails, it is not ignored."""
    from deepspeed_tpu.serving.config import GatewayConfig

    with pytest.raises(ValueError, match="unknown (keys|policies)"):
        GatewayConfig.from_dict({"control": body})


def test_speculation_policy_adapts_k_on_accept_band():
    pol = SpeculationPolicy(_ctl_cfg())
    rep = {"name": "0", "alive": True, "paused": False, "draining": False,
           "load": 0, "spec": {"d_drafted": 20, "d_accepted": 19, "k": 3,
                               "tree_width": 1}}
    out = pol.propose({"replicas": [rep]})
    assert out[0]["action"] == "raise_k" and out[0]["args"]["k"] == 4
    rep["spec"] = {"d_drafted": 20, "d_accepted": 2, "k": 3, "tree_width": 1}
    out = pol.propose({"replicas": [rep]})
    assert out[0]["action"] == "lower_k" and out[0]["args"]["k"] == 2
    rep["spec"] = {"d_drafted": 2, "d_accepted": 2, "k": 3, "tree_width": 1}
    assert pol.propose({"replicas": [rep]}) == []  # window too small to judge


# ---------------------------------------------------------------------------
# the AST gate: clean live tree, and seeded drift is caught
# ---------------------------------------------------------------------------
def test_control_actuator_gate_clean_on_live_tree():
    from tools.check_control_actuators import DEFAULT_PKG_DIR, check

    assert check(DEFAULT_PKG_DIR) == []


def test_control_actuator_gate_catches_drift(tmp_path):
    from tools.check_control_actuators import find_violations

    pkg = tmp_path / "pkg"
    (pkg / "serving" / "control").mkdir(parents=True)
    # rule 1: an actuator call from a request path outside serving/control/
    (pkg / "serving" / "handlers.py").write_text(
        "def route(replica):\n    replica.drain()\n")
    (pkg / "serving" / "control" / "controller.py").write_text(
        # rule 2: an _apply_* helper that actuates without emitting
        "def _apply_scale(prop):\n    prop.rep.restart()\n")
    whys = sorted(why for _rel, _ln, _snip, why in find_violations(str(pkg)))
    assert len(whys) == 2
    assert any("rule 1" in w for w in whys)
    assert any("rule 2" in w for w in whys)


# ---------------------------------------------------------------------------
# the metric namespace admits control/*
# ---------------------------------------------------------------------------
def test_metric_namespace_admits_control_prefix():
    from tools.check_metric_names import APPROVED_PREFIXES, check

    assert "control" in APPROVED_PREFIXES
    assert check() == []
