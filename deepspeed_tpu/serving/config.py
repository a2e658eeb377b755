"""``serving.gateway`` configuration block.

Plain dataclasses (the gateway is a standalone serving entry point, not a
training-engine subsystem, so it does not ride the pydantic runtime config):
:meth:`GatewayConfig.from_dict` accepts the ds_config-style nested dict

.. code-block:: python

    {"serving": {"gateway": {
        "enabled": true,
        "port": 8100,
        "router": "prefix",
        "slo_classes": {
            "interactive": {"max_queue_depth": 32, "ttft_target_ms": 250},
            "batch": {"priority": 1, "max_queue_depth": 256},
        },
    }}}

via :meth:`GatewayConfig.from_ds_config`. EVERY knob defaults to off:
``enabled=False``, depth limits 0 (= unbounded, no shedding), SLO targets 0
(= no conformance counters), ``port=0`` (= ephemeral), warmup empty.
"""

from dataclasses import dataclass, field, fields
from typing import Dict, Tuple


@dataclass
class SLOClassConfig:
    """One TTFT/TPOT service class. ``priority`` orders replica pull
    (lower = served first); depth limits of 0 disable shedding for the
    class; targets of 0 disable the SLO-miss conformance counters."""

    priority: int = 0
    # admission sheds (HTTP 429) once this many requests are queued for one
    # replica in this class; 0 = unbounded
    max_queue_depth: int = 0
    # admission sheds once the queued UNCACHED prompt tokens (the real
    # prefill cost after prefix-cache credit) exceed this; 0 = unbounded
    max_queue_uncached_tokens: int = 0
    # advisory SLO targets: a completed request past the target bumps
    # gateway/slo_{ttft,tpot}_miss_<class>_total; 0 = untracked
    ttft_target_ms: float = 0.0
    tpot_target_ms: float = 0.0


def _default_classes() -> Dict[str, SLOClassConfig]:
    # two conventional classes so an empty block is usable out of the box;
    # both unbounded/untracked until the operator sets depths/targets
    return {"interactive": SLOClassConfig(priority=0),
            "batch": SLOClassConfig(priority=1)}


@dataclass
class RequestTraceConfig:
    """``serving.gateway.tracing`` block — request-scoped tracing and the
    per-request summary log (``serving/reqtrace.py``). Presence-enables
    (the ``trace``/``health`` contract): an absent block costs the request
    path zero allocations and zero threads (test-enforced); a present one
    turns on request contexts, request-id-carrying spans on the Tracer/
    FlightRecorder, per-stage Prometheus histograms, and the JSONL summary
    log with tail-aware sampling."""

    enabled: bool = False
    # per-request summary records (JSONL, one line per terminal request);
    # "" = in-memory ring only, no file
    log_path: str = ""
    # atomic rotation: past this size the log rotates to .1/.2/... and the
    # oldest retained file is dropped — the log is bounded, never unbounded
    log_max_bytes: int = 16 << 20
    log_max_files: int = 2
    # head-sampling rate for HEALTHY requests (deterministic on request id).
    # SLO-miss / shed / error / cancelled records are ALWAYS retained
    # regardless — tails are the records the log exists for.
    sample_rate: float = 1.0
    # terminal-summary ring retained in memory (flight-dump forensics +
    # programmatic reads without touching the file)
    last_n: int = 64


@dataclass
class MeteringConfig:
    """``serving.gateway.metering`` block — tenant-scoped resource metering
    & fairness observability (``serving/metering.py``). Presence-enables
    (the ``tracing``/``health`` contract): an absent block costs the
    request path zero allocations and zero threads — no meter object, no
    engine views, no per-block stamp arrays (test-enforced in
    ``tests/test_tenant_metering.py``)."""

    enabled: bool = False
    # tenants exported individually on /metrics and /v1/usage; everything
    # past the cut aggregates into ONE `other` row — the scrape never
    # carries more than top_k + 1 distinct tenant label values
    top_k: int = 8
    # distinct in-memory ledgers; past this bound new tenant ids fold into
    # the `other` ledger (a hostile client inventing ids cannot grow memory)
    max_tracked_tenants: int = 256
    # atomically-rotated usage JSONL (the reqtrace RequestLog pattern):
    # one record per terminal request + periodic full-ledger snapshots;
    # "" = in-memory only, no file
    usage_log_path: str = ""
    usage_log_max_bytes: int = 16 << 20
    usage_log_max_files: int = 2
    # a full per-tenant ledger snapshot line every N terminal requests
    # (0 = per-request records only)
    ledger_snapshot_every: int = 64
    # starvation detector: a tenant's windowed p99 queue wait must exceed
    # BOTH `starvation_factor` x the global p99 AND the absolute floor
    # before the latched starvation instant fires
    starvation_factor: float = 4.0
    starvation_min_wait_s: float = 0.05
    # per-tenant sliding queue-wait window the p99s are computed over
    starvation_window: int = 64


@dataclass
class ProfilingConfig:
    """``serving.gateway.profiling`` block — the on-demand ``POST
    /v1/profile`` XPlane capture endpoint (``monitor/roofline.py``'s
    :class:`CaptureManager` bracketing ``jax.profiler`` around live
    traffic). Presence-enables (the ``tracing``/``metering`` contract): an
    absent block keeps the route returning 404 and allocates nothing."""

    enabled: bool = False
    # artifact root; each capture lands as an atomically-renamed
    # subdirectory (a visible dir is always a whole, loadable artifact)
    artifact_dir: str = "/tmp/dstpu_xplane"
    # capture length when the request body names none
    default_duration_s: float = 2.0
    # hard bound: requested durations clamp here (a typo'd duration must
    # not hold the process-global profiler for an hour)
    max_duration_s: float = 60.0


@dataclass
class DisaggConfig:
    """``serving.gateway.disagg`` block — disaggregated prefill/decode
    serving (``serving/disagg.py`` + ``serving/handoff.py``). Presence-
    enables (the ``tracing``/``metering``/``profiling`` contract): an
    absent block means every replica stays ``mixed``, the router ignores
    roles, and no coordinator/ledger objects exist."""

    enabled: bool = False
    # per-replica role by LIST INDEX ('prefill' | 'decode' | 'mixed');
    # shorter than the replica list pads the tail with 'mixed'. New
    # requests place onto prefill/mixed replicas; completed prefills hand
    # off to decode/mixed replicas through the host tier.
    roles: Tuple = ()
    # generated tokens a prefill replica waits for before handing off —
    # the first token proves prefill really completed (and is the TTFT the
    # client already saw); raising it delays migration
    handoff_after_tokens: int = 1


@dataclass
class TimelineConfig:
    """``serving.gateway.timeline`` block — the causal timeline plane
    (``serving/timeline.py`` + ``monitor/timeline.py``). Presence-enables
    (the ``tracing``/``metering``/``disagg``/``control`` contract): an
    absent block means no collector object, no per-request assembly, no
    chaos observer, no thread (test-enforced). Requires the ``tracing``
    block: the assembler joins the stage stamps request tracing owns."""

    enabled: bool = False
    # assembled timelines retained in the bounded ring (newest win);
    # tail exemplars below survive past ring eviction
    last_n: int = 256
    # always-retained tail exemplars: the top-K requests by TTFT and by
    # TPOT keep their COMPLETE assembled timelines regardless of ring age
    # — the PR 7 tail-retention discipline applied to whole timelines
    exemplar_slots: int = 8
    # segments-sum acceptance tolerance as a fraction of client e2e
    # (2 ms absolute floor) — PR 7's budget extended to migrated requests
    tolerance: float = 0.10


@dataclass
class ControlConfig:
    """``serving.gateway.control`` block — the feedback control plane
    (``serving/control/``). Presence-enables (the ``tracing``/``metering``/
    ``profiling``/``disagg`` contract): an absent block means no controller
    object, no thread, zero overhead on every request path (test-enforced).

    The controller ticks every ``interval_s``, computes windowed sensor
    deltas over the trailing ``window_s``, and lets each armed policy
    propose actuations. Flap-proofing is three-layered: per-policy
    hysteresis bands (the tighten threshold strictly above the relax
    threshold), a per-policy ``cooldown_s`` after any applied actuation,
    and a global ``max_actuations_per_window`` budget — a proposal past
    the budget is logged as a DEFERRED decision, never applied."""

    enabled: bool = False
    # decision-loop tick period
    interval_s: float = 0.25
    # armed policies: 'admission' | 'scaling' | 'speculation'
    policies: Tuple = ("admission", "scaling", "speculation")
    # trailing sensor window the rates/deltas are computed over
    window_s: float = 5.0
    # global actuation budget per window — the provable flap bound
    max_actuations_per_window: int = 4
    # per-policy quiet period after an applied actuation
    cooldown_s: float = 1.0
    # consecutive ticks a condition must hold before a policy may act
    # (one noisy sample never actuates)
    sustain_ticks: int = 2
    # bounded decision JSONL (the reqtrace RequestLog pattern);
    # "" = in-memory ring only, no file
    decision_log_path: str = ""
    decision_log_max_bytes: int = 4 << 20
    decision_log_max_files: int = 2
    # in-memory decision ring (forensic dumps + GET /v1/control)
    last_n: int = 128
    # -- (a) admission policy: windowed SLO-miss-rate hysteresis band ------
    # tighten the class's queue bound when the windowed miss rate crosses
    # the high threshold; relax/clear once it falls under the low one
    slo_miss_tighten: float = 0.5
    slo_miss_relax: float = 0.1
    # tightening halves the effective depth, never below this floor
    min_queue_depth: int = 2
    # windowed completions required before a miss rate is trusted
    min_window_completions: int = 4
    # -- (b) scaling policy: drain on sustained idle, un-drain on queue ----
    # drain one replica when the fleet idles (goodput idle fraction at or
    # past this, or zero load without a ledger) for the sustain window
    idle_frac_drain: float = 0.9
    # optional EWMA smoothing over the windowed idle fraction (0 = off,
    # raw signal). Bursty traffic dips the raw signal below the drain band
    # for single ticks, resetting the sustain counter and under-triggering
    # drains; alpha in (0, 1] blends alpha*raw + (1-alpha)*prev so a brief
    # burst stops masking a genuinely idle fleet (smaller = smoother)
    ewma_alpha: float = 0.0
    # un-drain (or restart a dead replica) when total queued requests
    # reach this for the sustain window
    queue_depth_undrain: int = 1
    # never drain below this many un-draining live replicas
    min_active_replicas: int = 1
    # -- (c) speculation policy: accept-rate band moves K ------------------
    spec_accept_high: float = 0.8
    spec_accept_low: float = 0.4
    spec_k_min: int = 1
    spec_k_max: int = 8
    # 0 = never touch tree_width; otherwise K raises may widen up to this
    spec_tree_width_max: int = 0
    # windowed drafted tokens required before an accept rate is trusted
    spec_min_window_drafted: int = 16


KNOWN_POLICIES = ("admission", "scaling", "speculation")


@dataclass
class GatewayConfig:
    enabled: bool = False
    host: str = "127.0.0.1"
    port: int = 0  # 0 = ephemeral (ServingGateway.port reports the real one)
    # replica placement policy: 'prefix' (radix-overlap oracle, least-loaded
    # fallback) | 'least_loaded' | 'random'
    router: str = "prefix"
    default_slo_class: str = "interactive"
    slo_classes: Dict[str, SLOClassConfig] = field(default_factory=_default_classes)
    # per-forward token budget handed to each replica's SplitFuse scheduler;
    # 0 = the scheduler default (the engine's max_ragged_batch_size)
    token_budget: int = 0
    # requests handed to a replica's scheduler at once (admitted requests
    # beyond this wait in the class queues, preserving SLO priority);
    # 0 = the engine's max_ragged_sequence_count
    max_inflight_per_replica: int = 0
    # hard cap on a request's max_new_tokens; 0 = engine max_context only
    max_new_tokens_cap: int = 0
    # HTTP handler wait bound for one request end-to-end, seconds
    request_timeout_s: float = 120.0
    # Retry-After seconds advertised on every 429/503 (shed, draining, dead
    # replica): the client-visible half of "this failure is retryable here
    # (429) or elsewhere (503)" — load balancers and well-behaved clients
    # key their backoff on it
    retry_after_s: int = 1
    # (seq_bucket, decode_steps) pairs pre-compiled per replica at start()
    # via engine.warmup; empty = no warmup
    warmup: Tuple = ()
    # prefill token buckets ALSO pre-compiled (against the warmup seq
    # buckets) so the recompile sentinel's warmup boundary covers the put
    # path — without these, the first real request per (token, seq) bucket
    # compiles post-boundary and is flagged as a steady-state recompile
    warmup_token_buckets: Tuple = ()
    # request-scoped tracing + per-request summary log; off by default
    tracing: RequestTraceConfig = field(default_factory=RequestTraceConfig)
    # tenant-scoped resource metering + fairness observability; off by
    # default with the same zero-overhead-absent contract
    metering: MeteringConfig = field(default_factory=MeteringConfig)
    # on-demand XPlane capture endpoint (POST /v1/profile); off by default —
    # the route 404s and no capture manager is created
    profiling: ProfilingConfig = field(default_factory=ProfilingConfig)
    # disaggregated prefill/decode replica pools + KV handoff; off by
    # default with the same zero-overhead-absent contract
    disagg: DisaggConfig = field(default_factory=DisaggConfig)
    # feedback control plane (serving/control/); off by default with the
    # same zero-overhead-absent contract
    control: ControlConfig = field(default_factory=ControlConfig)
    # causal timeline plane (serving/timeline.py); off by default with the
    # same zero-overhead-absent contract; requires the tracing block
    timeline: TimelineConfig = field(default_factory=TimelineConfig)

    @classmethod
    def from_dict(cls, d) -> "GatewayConfig":
        d = dict(d or {})
        classes = d.pop("slo_classes", None)
        tracing = d.pop("tracing", None)
        metering = d.pop("metering", None)
        profiling = d.pop("profiling", None)
        disagg = d.pop("disagg", None)
        control = d.pop("control", None)
        timeline = d.pop("timeline", None)
        known = {f.name for f in fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"serving.gateway: unknown keys {sorted(unknown)}")
        cfg = cls(**d)
        if tracing is not None:
            if isinstance(tracing, RequestTraceConfig):
                cfg.tracing = tracing
            else:
                body = dict(tracing)
                tr_known = {f.name for f in fields(RequestTraceConfig)}
                bad = set(body) - tr_known
                if bad:
                    raise ValueError(f"serving.gateway.tracing: unknown keys {sorted(bad)}")
                if "enabled" not in body:  # presence-enables
                    body["enabled"] = True
                cfg.tracing = RequestTraceConfig(**body)
            if not 0.0 <= cfg.tracing.sample_rate <= 1.0:
                raise ValueError("serving.gateway.tracing: sample_rate must be in [0, 1], "
                                 f"got {cfg.tracing.sample_rate}")
        if metering is not None:
            if isinstance(metering, MeteringConfig):
                cfg.metering = metering
            else:
                body = dict(metering)
                mt_known = {f.name for f in fields(MeteringConfig)}
                bad = set(body) - mt_known
                if bad:
                    raise ValueError(f"serving.gateway.metering: unknown keys {sorted(bad)}")
                if "enabled" not in body:  # presence-enables
                    body["enabled"] = True
                cfg.metering = MeteringConfig(**body)
            if cfg.metering.top_k < 1:
                raise ValueError("serving.gateway.metering: top_k must be >= 1, "
                                 f"got {cfg.metering.top_k}")
            if cfg.metering.max_tracked_tenants < cfg.metering.top_k:
                raise ValueError("serving.gateway.metering: max_tracked_tenants "
                                 f"({cfg.metering.max_tracked_tenants}) must cover "
                                 f"top_k ({cfg.metering.top_k})")
        if profiling is not None:
            if isinstance(profiling, ProfilingConfig):
                cfg.profiling = profiling
            else:
                body = dict(profiling)
                pf_known = {f.name for f in fields(ProfilingConfig)}
                bad = set(body) - pf_known
                if bad:
                    raise ValueError(f"serving.gateway.profiling: unknown keys {sorted(bad)}")
                if "enabled" not in body:  # presence-enables
                    body["enabled"] = True
                cfg.profiling = ProfilingConfig(**body)
            if cfg.profiling.max_duration_s <= 0 or cfg.profiling.default_duration_s <= 0:
                raise ValueError("serving.gateway.profiling: durations must be > 0, got "
                                 f"default={cfg.profiling.default_duration_s} "
                                 f"max={cfg.profiling.max_duration_s}")
        if disagg is not None:
            if isinstance(disagg, DisaggConfig):
                cfg.disagg = disagg
            else:
                body = dict(disagg)
                dg_known = {f.name for f in fields(DisaggConfig)}
                bad = set(body) - dg_known
                if bad:
                    raise ValueError(f"serving.gateway.disagg: unknown keys {sorted(bad)}")
                if "enabled" not in body:  # presence-enables
                    body["enabled"] = True
                cfg.disagg = DisaggConfig(**body)
            cfg.disagg.roles = tuple(str(r) for r in cfg.disagg.roles)
            bad_roles = [r for r in cfg.disagg.roles
                         if r not in ("prefill", "decode", "mixed")]
            if bad_roles:
                raise ValueError(f"serving.gateway.disagg: unknown roles {bad_roles}: "
                                 "'prefill' | 'decode' | 'mixed'")
            if cfg.disagg.handoff_after_tokens < 1:
                raise ValueError("serving.gateway.disagg: handoff_after_tokens must "
                                 f"be >= 1, got {cfg.disagg.handoff_after_tokens}")
        if control is not None:
            if isinstance(control, ControlConfig):
                cfg.control = control
            else:
                body = dict(control)
                ct_known = {f.name for f in fields(ControlConfig)}
                bad = set(body) - ct_known
                if bad:
                    raise ValueError(f"serving.gateway.control: unknown keys {sorted(bad)}")
                if "enabled" not in body:  # presence-enables
                    body["enabled"] = True
                cfg.control = ControlConfig(**body)
            ct = cfg.control
            ct.policies = tuple(str(p) for p in ct.policies)
            bad_pols = [p for p in ct.policies if p not in KNOWN_POLICIES]
            if bad_pols:
                raise ValueError(f"serving.gateway.control: unknown policies "
                                 f"{bad_pols}: {' | '.join(KNOWN_POLICIES)}")
            if ct.interval_s <= 0 or ct.window_s <= 0:
                raise ValueError("serving.gateway.control: interval_s and window_s "
                                 f"must be > 0, got interval={ct.interval_s} "
                                 f"window={ct.window_s}")
            if ct.max_actuations_per_window < 1:
                raise ValueError("serving.gateway.control: max_actuations_per_window "
                                 f"must be >= 1, got {ct.max_actuations_per_window}")
            if ct.cooldown_s < 0:
                raise ValueError("serving.gateway.control: cooldown_s must be >= 0, "
                                 f"got {ct.cooldown_s}")
            if ct.sustain_ticks < 1:
                raise ValueError("serving.gateway.control: sustain_ticks must be "
                                 f">= 1, got {ct.sustain_ticks}")
            if not ct.slo_miss_tighten > ct.slo_miss_relax >= 0:
                raise ValueError("serving.gateway.control: the admission hysteresis "
                                 "band needs slo_miss_tighten > slo_miss_relax >= 0, "
                                 f"got tighten={ct.slo_miss_tighten} "
                                 f"relax={ct.slo_miss_relax}")
            if not ct.spec_accept_high > ct.spec_accept_low >= 0:
                raise ValueError("serving.gateway.control: the speculation band "
                                 "needs spec_accept_high > spec_accept_low >= 0, "
                                 f"got high={ct.spec_accept_high} "
                                 f"low={ct.spec_accept_low}")
            if not 1 <= ct.spec_k_min <= ct.spec_k_max:
                raise ValueError("serving.gateway.control: need 1 <= spec_k_min <= "
                                 f"spec_k_max, got min={ct.spec_k_min} "
                                 f"max={ct.spec_k_max}")
            if ct.min_active_replicas < 1:
                raise ValueError("serving.gateway.control: min_active_replicas must "
                                 f"be >= 1, got {ct.min_active_replicas}")
            if not 0.0 <= ct.ewma_alpha <= 1.0:
                raise ValueError("serving.gateway.control: ewma_alpha must be "
                                 f"in [0, 1] (0 = off), got {ct.ewma_alpha}")
        if timeline is not None:
            if isinstance(timeline, TimelineConfig):
                cfg.timeline = timeline
            else:
                body = dict(timeline)
                tl_known = {f.name for f in fields(TimelineConfig)}
                bad = set(body) - tl_known
                if bad:
                    raise ValueError(f"serving.gateway.timeline: unknown keys {sorted(bad)}")
                if "enabled" not in body:  # presence-enables
                    body["enabled"] = True
                cfg.timeline = TimelineConfig(**body)
            tl = cfg.timeline
            if tl.last_n < 1:
                raise ValueError("serving.gateway.timeline: last_n must be >= 1, "
                                 f"got {tl.last_n}")
            if tl.exemplar_slots < 0:
                raise ValueError("serving.gateway.timeline: exemplar_slots must "
                                 f"be >= 0, got {tl.exemplar_slots}")
            if not 0.0 < tl.tolerance <= 1.0:
                raise ValueError("serving.gateway.timeline: tolerance must be in "
                                 f"(0, 1], got {tl.tolerance}")
            if tl.enabled and not cfg.tracing.enabled:
                raise ValueError("serving.gateway.timeline requires the tracing "
                                 "block: the assembler joins the stage stamps "
                                 "request tracing owns")
        if classes is not None:
            slo_known = {f.name for f in fields(SLOClassConfig)}
            parsed = {}
            for name, body in dict(classes).items():
                bad = set(body) - slo_known
                if bad:
                    raise ValueError(f"serving.gateway.slo_classes[{name!r}]: "
                                     f"unknown keys {sorted(bad)}")
                parsed[str(name)] = SLOClassConfig(**body)
            cfg.slo_classes = parsed
        if cfg.default_slo_class not in cfg.slo_classes:
            raise ValueError(f"serving.gateway: default_slo_class "
                             f"{cfg.default_slo_class!r} not in slo_classes "
                             f"{sorted(cfg.slo_classes)}")
        if cfg.router not in ("prefix", "least_loaded", "random"):
            raise ValueError(f"serving.gateway: unknown router {cfg.router!r}: "
                             "'prefix' | 'least_loaded' | 'random'")
        return cfg

    @classmethod
    def from_ds_config(cls, param_dict) -> "GatewayConfig":
        """Parse the ``serving.gateway`` block out of a full ds_config dict.
        An absent block yields the all-off defaults; a present-but-empty
        block enables the gateway with defaults (the presence-enables
        contract of the ``trace``/``health`` blocks)."""
        block = dict((param_dict or {}).get("serving", {}).get("gateway", {}))
        present = "gateway" in (param_dict or {}).get("serving", {})
        if present and "enabled" not in block:
            block["enabled"] = True
        return cls.from_dict(block)

    def class_order(self):
        """Class names in pull order: priority ascending, then name (a
        deterministic tiebreak so replica pull order is reproducible)."""
        return sorted(self.slo_classes, key=lambda n: (self.slo_classes[n].priority, n))
