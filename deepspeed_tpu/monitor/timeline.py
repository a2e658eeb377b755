"""Causal request timelines: the pure assembly/attribution model.

The sensor planes each explain one axis of a request's life — reqtrace
stamps its stage boundaries (PR 7), the goodput ledger books its replica's
seconds (PR 14), the handoff ledger brokers its migration (PR 18), the
decision log records the actuations that mutated its environment (PR 19).
None of them joins. This module is the join: given one request's stamps
(all on the ``time.perf_counter`` clock) plus the overlay events the other
planes observed inside its window, it builds a contiguous segment list
that SUMS to client-observed end-to-end latency by construction, then
re-attributes overlapped time to its causal owner and names the dominant
cause.

Pure functions over plain dicts, import-light (no jax, no serving
imports): the serving-side :class:`~deepspeed_tpu.serving.timeline.
TimelineCollector` feeds it live requests; :func:`explain_delta` takes two
captured populations and diffs them. Everything here is unit-testable
without a gateway.

Segment model
-------------

Each stamp opens the segment named for what the request was doing FROM
that instant; the segment closes at the next present stamp (``t_done``
closes the last). Migrated requests interleave both replicas' stamps on
the one shared clock, so the handoff window decomposes into its broker
sub-stages instead of hiding inside decode:

    ingress -> queue -> prefill -> decode -> handoff_export ->
    broker_verify -> handoff_install -> resume_wait -> decode_resumed

Absent stamps simply drop their segment (a shed request is one ``ingress``
segment; a fallback keeps ``decode_fallback`` from the failed broker's
exit). Because segments tile [t_recv, t_done] with no gaps, the
segments-sum acceptance (within ``tolerance`` of e2e, 2 ms floor — PR 7's
budget extended to migrated requests) checks the STAMPS, not the tiling:
a cross-clock or cross-replica skew is exactly what would break it.

Dominant-cause verdict
----------------------

Base attribution maps each segment to one of {queue, prefill, handoff,
decode}; overlays then move overlapped milliseconds to their causal owner:
measured driver stall gaps -> ``stall``, recompile-sentinel events ->
``recompile`` (the containing segment's remainder — a steady-state compile
owns the stage it landed in), and an applied control actuation whose
in-flight roster named this request flips a queue-dominated verdict to
``actuation-induced`` (the controller shrank this request's world; the
queue time is its doing). Attribution is conservative: moves never create
or destroy milliseconds, so the causes always sum to the segments.
"""

from typing import Dict, List, Optional

__all__ = ["CAUSES", "SEGMENT_CAUSE", "STAMP_ORDER", "build_segments",
           "assemble_timeline", "coverage_ok", "stage_totals",
           "explain_delta"]

# the closed verdict taxonomy (ISSUE 20)
CAUSES = ("queue", "prefill", "handoff", "decode", "recompile", "stall",
          "actuation-induced")

# (segment name, stamp that OPENS it), in causal order — the order is the
# tiebreak when two stamps land on the same perf_counter reading
STAMP_ORDER = (
    ("ingress", "t_recv"),                 # parse/validate/route
    ("queue", "t_admitted"),               # class-queue wait
    ("prefill", "t_dequeued"),             # scheduler pickup -> first token
    ("decode", "t_first_token"),           # decode on the source replica
    ("handoff_export", "t_handoff_start"),     # D2H export + manifest
    ("broker_verify", "t_handoff_export"),     # checksum verify window
    ("handoff_install", "t_handoff_verify"),   # dest install + detach
    ("resume_wait", "t_resume_enqueued"),      # dest adoption-queue wait
    ("decode_resumed", "t_resume_submitted"),  # decode on the dest replica
    ("decode_fallback", "t_handoff_done"),     # failed broker -> in place
    ("close", "t_last_token"),             # last token -> terminal
)

SEGMENT_CAUSE = {
    "ingress": "queue", "queue": "queue",
    "prefill": "prefill",
    "handoff_export": "handoff", "broker_verify": "handoff",
    "handoff_install": "handoff", "resume_wait": "handoff",
    "decode": "decode", "decode_resumed": "decode",
    "decode_fallback": "decode", "close": "decode",
}

HANDOFF_SEGMENTS = ("handoff_export", "broker_verify", "handoff_install",
                    "resume_wait")

# actuations that shrink a request's world mid-flight (tightened class
# depth, a drained/restarted replica) — the ones that can OWN queue time
_ACTUATION_ACTIONS = ("tighten", "drain", "restart", "undrain")


def build_segments(stamps: Dict[str, Optional[float]]) -> List[dict]:
    """Contiguous segments tiling [t_recv, t_done] from one request's
    stamps (absent stamps drop their segment; out-of-order stamps — a
    race, never the design — clamp to zero-duration rather than going
    negative). Each segment: ``{"name", "cause", "start_ms", "ms"}`` with
    ``start_ms`` relative to ``t_recv``."""
    t_recv = stamps.get("t_recv")
    t_done = stamps.get("t_done")
    if t_recv is None or t_done is None or t_done < t_recv:
        return []
    bounds = [(float(stamps[key]), i, name)
              for i, (name, key) in enumerate(STAMP_ORDER)
              if stamps.get(key) is not None]
    bounds.sort()  # by time, causal index as tiebreak
    segments = []
    prev_t = t_recv
    for j, (t, _i, name) in enumerate(bounds):
        t = min(max(t, prev_t), t_done)  # clamp monotonic, inside the window
        end = (min(max(bounds[j + 1][0], t), t_done)
               if j + 1 < len(bounds) else t_done)
        segments.append({"name": name,
                         "cause": SEGMENT_CAUSE.get(name, "decode"),
                         "start_ms": round((t - t_recv) * 1e3, 3),
                         "ms": round((end - t) * 1e3, 3)})
        prev_t = t
    return segments


def coverage_ok(sum_ms, e2e_ms, tolerance=0.10) -> bool:
    """The segments-sum acceptance: within ``tolerance`` of client e2e,
    with a 2 ms absolute floor (sub-ms smoke requests must not fail on
    scheduler jitter) — PR 7's budget, extended to migrated requests."""
    if sum_ms is None or e2e_ms is None:
        return False
    return abs(sum_ms - e2e_ms) <= max(tolerance * e2e_ms, 2.0)


def _overlap_ms(seg, t0_ms, t1_ms) -> float:
    a = max(seg["start_ms"], t0_ms)
    b = min(seg["start_ms"] + seg["ms"], t1_ms)
    return max(0.0, b - a)


def assemble_timeline(stamps, record=None, stalls=(), recompiles=(),
                      chaos_fires=(), actuations=(), tolerance=0.10) -> dict:
    """One request's assembled :class:`RequestTimeline` (a plain dict —
    JSON-safe end to end, it goes straight out ``GET /v1/timeline/<rid>``).

    ``stamps``     — perf_counter stage boundaries (see ``STAMP_ORDER``).
    ``record``     — the reqtrace terminal summary (joined by reference).
    ``stalls``     — [(t0, t1)] measured driver chaos-fire gaps on this
                     request's replicas (perf_counter, absolute).
    ``recompiles`` — sentinel events joined to this request
                     (``{"bucket", "t", ...}``, perf_counter ``t``).
    ``chaos_fires``— chaos events joined to this request (annotation only:
                     a stall fire's cost already arrives via ``stalls``).
    ``actuations`` — applied control decisions whose in-flight roster
                     named this request.
    """
    record = record or {}
    segments = build_segments(stamps)
    t_recv = stamps.get("t_recv")
    t_done = stamps.get("t_done")
    e2e_ms = (round((t_done - t_recv) * 1e3, 3)
              if t_recv is not None and t_done is not None else None)
    causes_ms = {}
    for seg in segments:
        causes_ms[seg["cause"]] = causes_ms.get(seg["cause"], 0.0) + seg["ms"]
    # -- overlay 1: measured stall gaps move their overlap to `stall` ------
    n_stalls = 0
    if t_recv is not None:
        for (s0, s1) in stalls:
            t0_ms = (s0 - t_recv) * 1e3
            t1_ms = (s1 - t_recv) * 1e3
            hit = False
            for seg in segments:
                ov = _overlap_ms(seg, t0_ms, t1_ms)
                if ov <= 0.0:
                    continue
                moved = min(ov, seg["ms"] - seg.get("stall_ms", 0.0))
                if moved <= 0.0:
                    continue
                seg["stall_ms"] = round(seg.get("stall_ms", 0.0) + moved, 3)
                causes_ms[seg["cause"]] -= moved
                causes_ms["stall"] = causes_ms.get("stall", 0.0) + moved
                hit = True
            n_stalls += bool(hit)
    # -- overlay 2: a recompile event owns its segment's remainder ---------
    n_recompiles = 0
    if t_recv is not None:
        for ev in recompiles:
            t_ms = (float(ev.get("t", 0.0)) - t_recv) * 1e3
            for seg in segments:
                if seg["start_ms"] <= t_ms <= seg["start_ms"] + seg["ms"] \
                        and not seg.get("recompile"):
                    rem = max(0.0, seg["ms"] - seg.get("stall_ms", 0.0))
                    seg["recompile"] = True
                    causes_ms[seg["cause"]] -= rem
                    causes_ms["recompile"] = causes_ms.get("recompile", 0.0) + rem
                    n_recompiles += 1
                    break
    causes_ms = {k: round(v, 3) for k, v in causes_ms.items() if v > 1e-9}
    sum_ms = round(sum(seg["ms"] for seg in segments), 3) if segments else None
    # -- verdict -----------------------------------------------------------
    dominant_cause = (max(causes_ms, key=causes_ms.get) if causes_ms else None)
    applied = [a for a in actuations
               if a.get("applied") and any(tag in str(a.get("action", ""))
                                           for tag in _ACTUATION_ACTIONS)]
    if dominant_cause == "queue" and applied:
        # the controller shrank this request's world while it waited: the
        # queue time is actuation-induced, not organic back-pressure
        dominant_cause = "actuation-induced"
    by_ms = sorted(segments, key=lambda s: s["ms"], reverse=True)
    handoff_gap_ms = round(sum(s["ms"] for s in segments
                               if s["name"] in HANDOFF_SEGMENTS), 3)
    tl = {
        "request_id": record.get("request_id"),
        "handoff_state": record.get("handoff_state"),
        "migrated": record.get("handoff_state") == "migrated",
        "e2e_ms": e2e_ms,
        "sum_ms": sum_ms,
        "coverage_ok": coverage_ok(sum_ms, e2e_ms, tolerance),
        "segments": segments,
        "causes_ms": causes_ms,
        "critical_path": [{"name": s["name"], "ms": s["ms"]} for s in by_ms[:5]],
        "dominant_segment": by_ms[0]["name"] if by_ms else None,
        "dominant_cause": dominant_cause,
        "stalls": n_stalls,
        "recompiles": n_recompiles,
        "chaos_fires": list(chaos_fires),
        "actuations": [{"policy": a.get("policy"), "action": a.get("action"),
                        "reason": a.get("reason")} for a in applied],
        "record": record,
    }
    if handoff_gap_ms > 0.0 or tl["migrated"]:
        tl["handoff_gap_ms"] = handoff_gap_ms
    return tl


# ---------------------------------------------------------------------------
# population diff: the differential-explain model
# ---------------------------------------------------------------------------
def stage_totals(timeline) -> Dict[str, float]:
    """Per-stage milliseconds of ONE timeline (segments with the same name
    merge — a request can re-enter ``decode`` around a fallback)."""
    out = {}
    for seg in timeline.get("segments", ()):
        out[seg["name"]] = out.get(seg["name"], 0.0) + seg["ms"]
    return out


def _population(timelines):
    stages, causes, e2es = {}, {}, []
    for tl in timelines:
        if tl.get("e2e_ms") is None:
            continue
        e2es.append(tl["e2e_ms"])
        for name, ms in stage_totals(tl).items():
            stages[name] = stages.get(name, 0.0) + ms
        for cause, ms in (tl.get("causes_ms") or {}).items():
            causes[cause] = causes.get(cause, 0.0) + ms
    return len(e2es), sum(e2es), stages, causes


def explain_delta(base_timelines, cur_timelines) -> dict:
    """Diff two timeline populations: the per-stage (and per-cause) delta
    of MEAN contribution per request, and which stage owns the end-to-end
    delta. A stage absent from one population contributes 0 there (a
    migration stage appearing only in the regressed round is itself the
    attribution). ``dominant_stage`` is the largest mover in the delta's
    own direction — a regression names the stage that grew, a speedup the
    stage that shrank."""
    nb, e2e_b, st_b, ca_b = _population(base_timelines)
    nc, e2e_c, st_c, ca_c = _population(cur_timelines)
    out = {"n_base": nb, "n_cur": nc, "delta_e2e_ms": None,
           "by_stage": {}, "by_cause": {}, "dominant_stage": None,
           "dominant_cause": None}
    if nb == 0 or nc == 0:
        return out
    delta_e2e = e2e_c / nc - e2e_b / nb
    out["base_e2e_mean_ms"] = round(e2e_b / nb, 3)
    out["cur_e2e_mean_ms"] = round(e2e_c / nc, 3)
    out["delta_e2e_ms"] = round(delta_e2e, 3)

    def diff(base_map, cur_map):
        rows = {}
        for name in sorted(set(base_map) | set(cur_map)):
            mb = base_map.get(name, 0.0) / nb
            mc = cur_map.get(name, 0.0) / nc
            d = mc - mb
            rows[name] = {"base_mean_ms": round(mb, 3),
                          "cur_mean_ms": round(mc, 3),
                          "delta_ms": round(d, 3),
                          "share": (round(d / delta_e2e, 3)
                                    if abs(delta_e2e) > 1e-9 else None)}
        return rows

    out["by_stage"] = diff(st_b, st_c)
    out["by_cause"] = diff(ca_b, ca_c)
    sign = 1.0 if delta_e2e >= 0 else -1.0
    if out["by_stage"]:
        out["dominant_stage"] = max(
            out["by_stage"], key=lambda n: sign * out["by_stage"][n]["delta_ms"])
    if out["by_cause"]:
        out["dominant_cause"] = max(
            out["by_cause"], key=lambda n: sign * out["by_cause"][n]["delta_ms"])
    return out
