"""The serving feedback controller: sensors -> policies -> actuators.

One :class:`ServingController` per gateway when ``serving.gateway.control``
is present. A single daemon thread ticks every ``interval_s``: it takes a
raw sensor sample (counters, admission state, replica state, goodput
ledgers — READ-ONLY, through the public surfaces the
earlier PRs built), diffs it against the trailing ``window_s`` of samples,
hands the windowed snapshot to each armed policy, and applies the
proposals through the ``_apply_*`` helpers — the ONLY sanctioned actuator
call sites in the tree (``tools/check_control_actuators.py``).

Flap-proofing is layered so the loop provably cannot oscillate under a
chaos storm:

  * policies act on hysteresis BANDS and require ``sustain_ticks``
    consecutive over-threshold samples (``policies.py``);
  * an applied actuation puts its policy on ``cooldown_s``;
  * a global budget of ``max_actuations_per_window`` applied actuations
    per ``window_s`` caps the whole loop — proposals past it are logged
    as DEFERRED decisions, never applied. The chaos drill's bound is
    exactly this arithmetic: applied <= budget x ceil(elapsed / window).

Every applied AND deferred decision goes through the
:class:`~deepspeed_tpu.serving.control.decisions.DecisionLog` with the
sensor readings that justified it.
"""

import logging
import threading
import time
from collections import deque
from typing import Dict, Optional

from ...monitor.goodput import get_goodput
from ...monitor.health import get_health
from ...monitor.metrics import get_metrics
from .decisions import DecisionLog
from .policies import build_policies

logger = logging.getLogger(__name__)

__all__ = ["ServingController"]


class ServingController:
    """Feedback control loop over one gateway's sensor planes."""

    def __init__(self, gateway, config):
        self.gateway = gateway
        self.config = config
        self.decisions = DecisionLog(config)
        self.policies = build_policies(config)
        self.stats = {"ticks": 0, "applied": 0, "deferred": 0, "errors": 0}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # perf_counter stamps of APPLIED actuations inside the flap window
        self._actuation_t = deque()
        self._cooldown_until: Dict[str, float] = {}
        # trailing raw samples the windowed deltas diff against
        self._samples = deque()
        self._last_snap: dict = {}
        # EWMA state for the idle_frac sensor (ewma_alpha > 0): smooths
        # bursty arrival dips so a momentary busy spike can't reset a
        # drain proposal's sustain counter
        self._idle_ewma: Optional[float] = None
        self._registered_gauges = None
        self._registered_state = None
        self._registered_dump = None

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return self
        health = get_health()
        self._registered_gauges = self.gauge_rows
        self._registered_state = self.state
        self._registered_dump = self.decision_dump
        health.set_gauge_provider("control", self._registered_gauges)
        health.set_state_provider("control", self._registered_state)
        health.set_dump_provider("control_decisions", self._registered_dump)
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, name="dstpu-control",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        health = get_health()
        if self._registered_gauges is not None:
            health.clear_gauge_provider("control", self._registered_gauges)
            health.clear_state_provider("control", self._registered_state)
            health.clear_dump_provider("control_decisions", self._registered_dump)
            self._registered_gauges = None
            self._registered_state = None
            self._registered_dump = None
        self.decisions.close()

    def _run(self):
        while not self._stop.wait(self.config.interval_s):
            try:
                self.tick()
            except Exception as e:  # noqa: BLE001 — the loop must survive
                self.stats["errors"] += 1
                get_metrics().counter("control/errors_total").inc()
                logger.warning(f"control tick failed: {type(e).__name__}: "
                               f"{str(e)[:200]}")

    # -- the decision pass (public so tests drive it deterministically) ------
    def tick(self, now: Optional[float] = None) -> None:
        now = time.perf_counter() if now is None else float(now)
        snap = self._sense(now)
        for pol in self.policies:
            if now < self._cooldown_until.get(pol.name, 0.0):
                continue
            try:
                proposals = pol.propose(snap)
            except Exception as e:  # noqa: BLE001 — one policy never kills a tick
                self.stats["errors"] += 1
                get_metrics().counter("control/errors_total").inc()
                logger.warning(f"control policy {pol.name} failed: "
                               f"{type(e).__name__}: {str(e)[:200]}")
                continue
            for prop in proposals:
                self._actuate(pol, prop, now)
        self.stats["ticks"] += 1

    # -- sensors (read-only, public surfaces only) ---------------------------
    def _raw_sample(self, now: float) -> dict:
        reg = get_metrics()
        classes = {}
        for cls in self.gateway.config.slo_classes:
            classes[cls] = {
                "completed": reg.counter(f"gateway/completed_{cls}_total").value,
                "ttft_miss": reg.counter(f"gateway/slo_ttft_miss_{cls}_total").value,
                "tpot_miss": reg.counter(f"gateway/slo_tpot_miss_{cls}_total").value,
            }
        spec = {}
        for r in self.gateway.replicas:
            st = r.state().get("speculative")
            if st:
                spec[r.name] = {"drafted": st.get("drafted", 0),
                                "accepted": st.get("accepted", 0)}
        sample = {"t": now, "classes": classes, "spec": spec}
        gp = get_goodput()
        if gp.enabled:
            idle = wall = 0.0
            for rep in (gp.report().get("serving") or {}).values():
                idle += rep.get("categories", {}).get("idle", 0.0)
                wall += rep.get("wall_s", 0.0)
            sample["goodput"] = {"idle_s": idle, "wall_s": wall}
        return sample

    def _sense(self, now: float) -> dict:
        cur = self._raw_sample(now)
        horizon = now - self.config.window_s
        while len(self._samples) > 1 and self._samples[1]["t"] <= horizon:
            self._samples.popleft()
        base = self._samples[0] if self._samples else cur
        self._samples.append(cur)
        adm = self.gateway.admission
        classes = {}
        for cls, c in cur["classes"].items():
            b = base["classes"].get(cls, c)
            d_done = c["completed"] - b["completed"]
            d_miss = (c["ttft_miss"] - b["ttft_miss"]) \
                + (c["tpot_miss"] - b["tpot_miss"])
            limits = adm.effective_limits(cls)
            overrides = adm.state().get("depth_overrides", {})
            classes[cls] = {"d_completed": d_done, "d_miss": d_miss,
                            "queue_depth": adm.depth(slo_class=cls),
                            "admitted_rate": adm.admitted_rate(cls),
                            "effective_depth": limits["max_queue_depth"],
                            "override_active": cls in overrides,
                            "priority": int(getattr(
                                self.gateway.config.slo_classes[cls],
                                "priority", 0))}
        replicas = []
        for r in self.gateway.replicas:
            row = {"name": r.name, "alive": r.alive, "paused": r.paused,
                   "draining": r.draining, "load": r.load, "spec": None}
            sp_cur = cur["spec"].get(r.name)
            if sp_cur is not None:
                sp_base = base["spec"].get(r.name, sp_cur)
                params = r.spec_params() or {}
                row["spec"] = {
                    "d_drafted": sp_cur["drafted"] - sp_base["drafted"],
                    "d_accepted": sp_cur["accepted"] - sp_base["accepted"],
                    "k": params.get("k", 0),
                    "tree_width": params.get("tree_width", 1)}
            replicas.append(row)
        idle_frac = None
        idle_frac_raw = None
        if "goodput" in cur and "goodput" in base:
            d_wall = cur["goodput"]["wall_s"] - base["goodput"]["wall_s"]
            if d_wall > 1e-6:
                idle_frac_raw = max(0.0, min(1.0, (cur["goodput"]["idle_s"]
                                                   - base["goodput"]["idle_s"]) / d_wall))
                idle_frac = idle_frac_raw
                alpha = self.config.ewma_alpha
                if alpha > 0.0:
                    # optional EWMA (control.ewma_alpha, default off): one
                    # bursty sub-window dip below the drain band otherwise
                    # resets the policy's sustain counter every burst, so a
                    # genuinely idle fleet never drains
                    self._idle_ewma = (idle_frac_raw if self._idle_ewma is None
                                       else alpha * idle_frac_raw
                                       + (1.0 - alpha) * self._idle_ewma)
                    idle_frac = self._idle_ewma
        snap = {"now": now, "window_s": now - base["t"], "classes": classes,
                "replicas": replicas, "depth_total": adm.depth(),
                "idle_frac": idle_frac, "idle_frac_raw": idle_frac_raw}
        self._last_snap = snap
        return snap

    def _inflight_rids(self, cap: int = 64):
        """Request ids in flight across the fleet AT actuation time — the
        decision record's join key to the timeline plane (decisions stamp
        ``time.time``; requests stamp ``perf_counter``; the roster is the
        one clock-free 'this actuation overlapped that request' join).
        Bounded: a decision record must stay one log line."""
        rids = []
        for r in self.gateway.replicas:
            for row in r.inflight_summaries():
                rid = row.get("request_id")
                if rid:
                    rids.append(rid)
                    if len(rids) >= cap:
                        return rids
        return rids

    # -- actuation (the ONLY sanctioned actuator call sites) -----------------
    def _actuate(self, policy, prop, now: float) -> None:
        horizon = now - self.config.window_s
        while self._actuation_t and self._actuation_t[0] <= horizon:
            self._actuation_t.popleft()
        if len(self._actuation_t) >= self.config.max_actuations_per_window:
            self.decisions.emit(policy=policy.name, action=prop["action"],
                                applied=False,
                                reason="deferred: actuation budget exhausted "
                                       f"({self.config.max_actuations_per_window}"
                                       f"/{self.config.window_s}s)",
                                sensors=prop["sensors"],
                                inflight_rids=self._inflight_rids())
            self.stats["deferred"] += 1
            return
        apply_fn = getattr(self, f"_apply_{prop['kind']}")
        if apply_fn(policy, prop):
            self._actuation_t.append(now)
            self._cooldown_until[policy.name] = now + self.config.cooldown_s
            self.stats["applied"] += 1
        else:
            self.stats["deferred"] += 1

    def _apply_admission(self, policy, prop) -> bool:
        args = prop["args"]
        adm = self.gateway.admission
        if args.get("clear"):
            adm.clear_depth_override(args["slo_class"])
            result = {"cleared": True}
        else:
            result = adm.set_depth_override(
                args["slo_class"],
                max_queue_depth=args.get("max_queue_depth"),
                max_queue_uncached_tokens=args.get("max_queue_uncached_tokens"))
        self.decisions.emit(policy=policy.name, action=prop["action"],
                            applied=True, reason=prop["reason"],
                            sensors=prop["sensors"], result=result,
                            inflight_rids=self._inflight_rids())
        return True

    def _apply_scale(self, policy, prop) -> bool:
        args = prop["args"]
        rep = next((r for r in self.gateway.replicas
                    if r.name == args["replica"]), None)
        if rep is None:
            self.decisions.emit(policy=policy.name, action=prop["action"],
                                applied=False, reason="replica gone",
                                sensors=prop["sensors"],
                                inflight_rids=self._inflight_rids())
            return False
        op = args["op"]
        if op == "drain":
            rep.drain()
        elif op == "undrain":
            rep.undrain()
        else:  # "restart"
            rep.restart()
        self.decisions.emit(policy=policy.name, action=prop["action"],
                            applied=True, reason=prop["reason"],
                            sensors=prop["sensors"],
                            result={"replica": rep.name, "op": op},
                            inflight_rids=self._inflight_rids())
        return True

    def _apply_spec(self, policy, prop) -> bool:
        args = prop["args"]
        rep = next((r for r in self.gateway.replicas
                    if r.name == args["replica"]), None)
        result = None
        if rep is not None:
            result = rep.set_spec_params(k=args.get("k"),
                                         tree_width=args.get("tree_width"))
        applied = result is not None
        self.decisions.emit(policy=policy.name, action=prop["action"],
                            applied=applied,
                            reason=prop["reason"] if applied
                            else "replica gone or not speculating",
                            sensors=prop["sensors"], result=result,
                            inflight_rids=self._inflight_rids())
        return applied

    # -- export surfaces -----------------------------------------------------
    def gauge_rows(self):
        rows = [("control/actuations", {}, float(self.stats["applied"])),
                ("control/deferred", {}, float(self.stats["deferred"]))]
        for cls, w in (self._last_snap.get("classes") or {}).items():
            done = w.get("d_completed", 0)
            if done:
                rows.append(("control/slo_miss_rate", {"slo_class": cls},
                             round(w.get("d_miss", 0) / done, 4)))
        return rows

    def state(self) -> dict:
        return {"policies": [p.name for p in self.policies],
                "interval_s": self.config.interval_s,
                "window_s": self.config.window_s,
                "max_actuations_per_window": self.config.max_actuations_per_window,
                **self.stats,
                "overrides": self.gateway.admission.state().get("depth_overrides", {}),
                "decisions": self.decisions.state(),
                "recent_decisions": self.decisions.recent(10)}

    def decision_dump(self) -> dict:
        """Forensic stall-dump provider: the full in-memory decision ring —
        what the controller did (and declined to do) leading into a wedge."""
        return {"decisions": self.decisions.recent(),
                "snapshot": self._last_snap, **self.stats}
