"""Pluggable control policies: sensors in, proposals out.

Each policy is a small stateful object with one method —
``propose(snap) -> [proposal, ...]`` — where ``snap`` is the controller's
windowed sensor snapshot (see ``ServingController._sense``). A proposal
is a plain dict::

    {"kind": "admission" | "scale" | "spec",
     "action": <short verb string>,
     "reason": <why, one line>,
     "sensors": <the readings that justified it>,
     "args": <kwargs for the controller's _apply_* helper>}

Policies NEVER touch an actuator: the controller's ``_apply_*`` helpers
are the only sanctioned mutation sites (``tools/check_control_actuators.py``
enforces this with an AST gate), and the controller owns the global flap
budget and per-policy cooldowns. A policy's only job is to read the
window and say what it wants.

Hysteresis lives here: every policy acts on a BAND (tighten threshold
strictly above relax threshold) and requires its condition to hold for
``sustain_ticks`` consecutive ticks — one noisy sample never actuates,
and the act/undo thresholds never chase each other.
"""

from typing import Dict, List

__all__ = ["AdmissionPolicy", "ScalingPolicy", "SpeculationPolicy",
           "build_policies"]


class _Sustain:
    """Consecutive-tick counter: ``hit(key, cond)`` returns True only once
    ``cond`` has been True for ``need`` consecutive calls on ``key``."""

    def __init__(self, need: int):
        self.need = max(1, int(need))
        self._runs: Dict[str, int] = {}

    def hit(self, key: str, cond: bool) -> bool:
        run = self._runs.get(key, 0) + 1 if cond else 0
        self._runs[key] = run
        return run >= self.need


class AdmissionPolicy:
    """(a) SLO-aware admission: a class's windowed TTFT/TPOT miss rate
    drives queue-depth overrides on its VICTIMS — the lower-priority
    classes sharing the fleet. Shedding the class that is missing its own
    SLO only thins the traffic the SLO exists to protect (measured: the
    first cut of this policy did exactly that and made the control_ab
    WORSE); shedding the background behind it removes the prefill work the
    misses are queued behind. Past the tighten threshold the
    lowest-priority victim's depth halves (never under ``min_queue_depth``);
    under the relax threshold overrides restore in reverse — doubling back
    toward (and finally clearing to) the configured bound. A class with no
    lower-priority victim left to shed falls back to self-shedding, the
    last resort that at least bounds its own queue."""

    name = "admission"

    def __init__(self, config):
        self.config = config
        self._sustain = _Sustain(config.sustain_ticks)
        # overridden class -> effective depth when first tightened (the
        # relax/clear target)
        self._entry_depth: Dict[str, int] = {}

    def _tighten_for(self, cls, victim, classes, miss_rate, sensors):
        """Halve ``victim``'s depth on behalf of missing class ``cls``;
        None when the victim is already at the floor."""
        cfg = self.config
        vw = classes[victim]
        depth = vw.get("effective_depth", 0)
        base = depth if depth > 0 else max(4 * cfg.min_queue_depth,
                                           2 * vw.get("queue_depth", 0), 8)
        new_depth = max(cfg.min_queue_depth, base // 2)
        if new_depth >= base and vw.get("override_active"):
            return None  # already floored — nothing left to shed here
        if victim not in self._entry_depth:
            self._entry_depth[victim] = base
        verb = "shed" if victim != cls else "self-shed"
        return {"kind": "admission", "action": "tighten_depth",
                "reason": f"{verb} {victim} to protect {cls}: miss_rate "
                          f"{miss_rate:.2f} >= {cfg.slo_miss_tighten}",
                "sensors": {**sensors, "victim": victim,
                            "victim_depth": depth},
                "args": {"slo_class": victim, "max_queue_depth": new_depth}}

    def propose(self, snap) -> List[dict]:
        cfg = self.config
        classes = snap.get("classes", {})
        out = []
        for cls, w in classes.items():
            done = w.get("d_completed", 0)
            if done < cfg.min_window_completions:
                self._sustain.hit(f"tighten/{cls}", False)
                self._sustain.hit(f"relax/{cls}", False)
                continue
            miss_rate = w.get("d_miss", 0) / done
            prio = w.get("priority", 0)
            # victims: strictly lower-priority classes, least important first
            victims = sorted((v for v, vw in classes.items()
                              if vw.get("priority", 0) > prio),
                             key=lambda v: (-classes[v].get("priority", 0), v))
            # restorable: own override first (it sheds protected traffic —
            # most harmful), then victims in reverse shed order
            restorable = ([cls] if w.get("override_active") else []) \
                + [v for v in reversed(victims)
                   if classes[v].get("override_active")]
            sensors = {"slo_class": cls, "miss_rate": round(miss_rate, 4),
                       "window_completions": done,
                       "window_misses": w.get("d_miss", 0),
                       "queue_depth": w.get("queue_depth", 0),
                       "admitted_rate": w.get("admitted_rate", 0.0),
                       "effective_depth": w.get("effective_depth", 0)}
            tighten = self._sustain.hit(f"tighten/{cls}",
                                        miss_rate >= cfg.slo_miss_tighten)
            relax = self._sustain.hit(
                f"relax/{cls}",
                bool(restorable) and miss_rate <= cfg.slo_miss_relax)
            if tighten:
                for victim in victims + [cls]:
                    prop = self._tighten_for(cls, victim, classes, miss_rate,
                                             sensors)
                    if prop is not None:
                        out.append(prop)
                        break
            elif relax:
                victim = restorable[0]
                depth = classes[victim].get("effective_depth", 0)
                entry = self._entry_depth.get(victim, 0)
                new_depth = max(1, depth) * 2
                reason = (f"restore {victim}: {cls} miss_rate "
                          f"{miss_rate:.2f} <= {cfg.slo_miss_relax}")
                if entry and new_depth >= entry:
                    self._entry_depth.pop(victim, None)
                    out.append({"kind": "admission", "action": "clear_depth",
                                "reason": reason,
                                "sensors": {**sensors, "victim": victim},
                                "args": {"slo_class": victim, "clear": True}})
                else:
                    out.append({"kind": "admission", "action": "relax_depth",
                                "reason": reason,
                                "sensors": {**sensors, "victim": victim},
                                "args": {"slo_class": victim,
                                         "max_queue_depth": new_depth}})
        return out


class ScalingPolicy:
    """(b) Replica scaling/draining: sustained fleet idle drains ONE
    un-draining replica (the router steers around it, in-flight work
    finishes); sustained queue pressure un-drains one (or restarts a dead
    one — the stronger form of "bring capacity back"). The hysteresis is
    structural: the drain signal (idle) and the un-drain signal (queued
    work) cannot both hold, and ``min_active_replicas`` floors the fleet."""

    name = "scaling"

    def __init__(self, config):
        self.config = config
        self._sustain = _Sustain(config.sustain_ticks)

    def propose(self, snap) -> List[dict]:
        cfg = self.config
        reps = snap.get("replicas", [])
        depth_total = snap.get("depth_total", 0)
        live = [r for r in reps if r["alive"]]
        active = [r for r in live if not r["draining"]]
        idle_frac = snap.get("idle_frac")
        fleet_idle = (idle_frac >= cfg.idle_frac_drain) if idle_frac is not None \
            else (depth_total == 0 and all(r["load"] == 0 for r in active))
        sensors = {"depth_total": depth_total, "idle_frac": idle_frac,
                   "live": len(live), "active": len(active),
                   "draining": len(live) - len(active),
                   "dead": len(reps) - len(live)}
        out = []
        pressure = self._sustain.hit("undrain",
                                     depth_total >= cfg.queue_depth_undrain)
        idle = self._sustain.hit("drain",
                                 fleet_idle and len(active) > cfg.min_active_replicas)
        if pressure:
            dead = [r for r in reps if not r["alive"]]
            drained = [r for r in live if r["draining"]]
            if dead:
                out.append({"kind": "scale", "action": "restart_replica",
                            "reason": f"queued {depth_total} >= "
                                      f"{cfg.queue_depth_undrain} with a dead replica",
                            "sensors": sensors,
                            "args": {"replica": dead[0]["name"], "op": "restart"}})
            elif drained:
                out.append({"kind": "scale", "action": "undrain_replica",
                            "reason": f"queued {depth_total} >= "
                                      f"{cfg.queue_depth_undrain}",
                            "sensors": sensors,
                            "args": {"replica": drained[0]["name"], "op": "undrain"}})
        elif idle:
            # drain the least-loaded active replica (ties by name for
            # deterministic drills)
            victim = min(active, key=lambda r: (r["load"], r["name"]))
            out.append({"kind": "scale", "action": "drain_replica",
                        "reason": "sustained idle "
                                  + (f"(idle_frac {idle_frac:.2f})"
                                     if idle_frac is not None else "(zero load)"),
                        "sensors": sensors,
                        "args": {"replica": victim["name"], "op": "drain"}})
        return out


class SpeculationPolicy:
    """(c) Per-replica speculative adaptation: the windowed draft accept
    rate tunes K within ``[spec_k_min, spec_k_max]`` (and optionally tree
    width up to ``spec_tree_width_max``). High acceptance = the drafter is
    under-asked, raise K; low acceptance = verify tokens are being burned,
    lower K (the PR 13 per-uid backoff stays as the degenerate in-round
    case)."""

    name = "speculation"

    def __init__(self, config):
        self.config = config
        self._sustain = _Sustain(config.sustain_ticks)

    def propose(self, snap) -> List[dict]:
        cfg = self.config
        out = []
        for r in snap.get("replicas", []):
            sp = r.get("spec")
            if not sp or not r["alive"]:
                continue
            drafted = sp.get("d_drafted", 0)
            if drafted < cfg.spec_min_window_drafted:
                self._sustain.hit(f"up/{r['name']}", False)
                self._sustain.hit(f"down/{r['name']}", False)
                continue
            accept = sp.get("d_accepted", 0) / drafted
            k = sp.get("k", 0)
            sensors = {"replica": r["name"], "accept_rate": round(accept, 4),
                       "window_drafted": drafted,
                       "window_accepted": sp.get("d_accepted", 0), "k": k}
            up = self._sustain.hit(f"up/{r['name']}",
                                   accept >= cfg.spec_accept_high
                                   and k < cfg.spec_k_max)
            down = self._sustain.hit(f"down/{r['name']}",
                                     accept <= cfg.spec_accept_low
                                     and k > cfg.spec_k_min)
            if up:
                args = {"replica": r["name"], "k": min(cfg.spec_k_max, k + 1)}
                if cfg.spec_tree_width_max > 0:
                    args["tree_width"] = min(cfg.spec_tree_width_max,
                                             sp.get("tree_width", 1) + 1)
                out.append({"kind": "spec", "action": "raise_k",
                            "reason": f"accept_rate {accept:.2f} >= "
                                      f"{cfg.spec_accept_high}",
                            "sensors": sensors, "args": args})
            elif down:
                out.append({"kind": "spec", "action": "lower_k",
                            "reason": f"accept_rate {accept:.2f} <= "
                                      f"{cfg.spec_accept_low}",
                            "sensors": sensors,
                            "args": {"replica": r["name"],
                                     "k": max(cfg.spec_k_min, k - 1)}})
        return out


_BUILDERS = {"admission": AdmissionPolicy, "scaling": ScalingPolicy,
             "speculation": SpeculationPolicy}


def build_policies(config) -> List[object]:
    """Instantiate the armed policies in config order (config validation
    already rejected unknown names)."""
    return [_BUILDERS[name](config) for name in config.policies]
