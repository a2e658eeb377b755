"""Goodput ledger + recompile sentinel: attribute every wall-clock second.

The observability planes so far answer *what happened* (PR 1 spans), *is it
alive* (PR 5 heartbeats/stalls), *where did one request's time go* (PR 7
stage stamps) and *what does memory do* (PR 11). This module answers the
question a production operator actually asks: **of the last hour, how many
seconds were useful compute vs. input wait, exposed comm, checkpoint
blocking, compile, stall, or restart recovery?** — and its serving twin:
prefill vs decode vs verify vs idle vs stalled vs draining vs recovering,
per replica.

Two components:

  * :class:`GoodputLedger` — a wall-clock attribution ledger. One training
    ledger per process (engines attach across restarts, so the ledger spans
    the whole resilient run) and one serving ledger per replica. Categories
    are booked from the EXISTING measurement points (the PR 2 input-wait
    window, the PR 4 ``ckpt_blocked`` observation, the comm host-plane
    bracket, the compile listener, chaos/stall gaps, the resilience
    runner's failure boundary) — and the PR 7 discipline applies globally:
    :meth:`GoodputLedger.report` must sum to measured wall-clock, with any
    unclassified residual disclosed as its own ``unattributed`` bucket
    (and any double-booking disclosed as ``overbooked_s``), never silently
    absorbed.

  * :class:`RecompileSentinel` — after a declared warmup boundary
    (training: the first ``train_warmup_steps`` steps; serving:
    ``InferenceEngineV2.warmup`` completion), every further compile of a
    new (token-bucket, seq-bucket, k, sampling) program is flagged:
    counted per source and shape bucket, joined to the in-flight request
    uids (and request ids when the replica registered a resolver), and
    compile-storm bursts (K unexpected compiles inside a window) raise a
    trace instant + their own counter. The single worst silent perf killer
    in a JAX serving plane — a steady-state recompile when a request lands
    in a never-warmed bucket — becomes a named, attributed event instead
    of an unlabelled blip.

Measurement semantics (stated plainly; the conservation test enforces the
arithmetic, the README documents the physics):

  * ``compute`` (training) is the per-step residual: step wall minus the
    explicitly booked input-wait / compile / ckpt-blocked / comm-exposed /
    stall seconds inside that step window, clamped at zero.
  * ``comm_exposed`` counts BLOCKING host-plane collective time (the
    step-boundary resilience vote, object broadcasts). In-jit collective
    time is invisible to the host and rides ``compute`` — XLA overlaps it.
  * ``stall``/``stalled`` books hook-caused wedges ≥ ``stall_gap_s``,
    measured around the chaos fire points (the step boundary / the driver
    loop top — where the storm drills inject). A wedge INSIDE a forward
    books into the active category that wedged (train ``compute``, serving
    ``prefill_active``/...); the PR 5 watchdog dumps both kinds either way,
    so stall=0 here means "no injected/hook wedge", not "never wedged".
  * serving ``prefill_active``/``decode_active``/``spec_verify`` book the
    engine's own forward walltime; scheduler/gateway python overhead is
    disclosed as ``unattributed``, not laundered into an active bucket.

Everything defaults OFF with the PR 5 zero-overhead contract: no plane
object work, no threads, and one ``is not None`` / ``enabled`` check at
each hook when the ``monitor.goodput`` block is absent.

Import-light by design: stdlib + sibling monitor modules only (comm and
the health plane are reached lazily at configure time).
"""

import threading
import time
from collections import Counter as _Counter
from collections import deque

from .flight import get_flight_recorder
from .metrics import get_metrics
from .trace import get_tracer

TRAIN_CATEGORIES = ("compute", "input_wait", "comm_exposed", "ckpt_blocked",
                    "compile", "stall", "recovery", "idle")
# input_wait on the serving side: admission-path waits a request eats
# before its prefill can start — today the synchronous H2D promotion of a
# demoted prefix chain (the tiered KV cache restoring a host/disk-resident
# hit). Same semantic as the training category: time the accelerator sat
# ready while the input pipeline (here: the memory hierarchy) caught up.
SERVING_CATEGORIES = ("prefill_active", "decode_active", "spec_verify",
                      "handoff", "input_wait", "idle", "stalled", "draining",
                      "recovering")

# training categories booked directly by their sources (compile listener,
# comm hook, ckpt save path, chaos-gap detection) INSIDE a step window; the
# per-step compute residual subtracts their delta so one second is never
# booked twice
_TRAIN_EXPLICIT = ("comm_exposed", "ckpt_blocked", "compile", "stall")

# ---------------------------------------------------------------------------
# span-name -> ledger-category contract (enforced by
# tools/check_goodput_taxonomy.py, tier-1): every DURATION span an
# engine/serving/resilience module emits either maps to exactly ONE ledger
# category here, or sits on the explicit allowlist below with its reason.
# A future PR adding a time-consuming span must classify it — the gate
# fails otherwise.
# ---------------------------------------------------------------------------
SPAN_TO_CATEGORY = {
    "input_wait": "input_wait",
    "train_batch": "compute",
    "checkpoint/save": "ckpt_blocked",
    "jax_compile": "compile",
    "serving/prefill": "prefill_active",
    "serving/decode_step": "decode_active",
    "serving/decode": "decode_active",
    "serving/spec_verify": "spec_verify",
    # tiered KV cache: synchronous promotion wait on the admission path
    "serving/promote_wait": "input_wait",
    # disaggregated serving: the prefill replica's driver exporting +
    # brokering one request's KV to a decode replica — real driver seconds
    # that are neither prefill nor decode compute, so they get their own
    # category instead of contaminating pool purity
    "serving/handoff": "handoff",
}

SPAN_ALLOWLIST = (
    # request-scoped OVERLAYS (serving/reqtrace.py): re-attributions of the
    # same wall time the engine spans above book — booking them too would
    # double-count every request's seconds
    "serving/queue_wait",
    "serving/prefill_chunk",
    "serving/gateway_respond",
    "serving/decode_tail",
    # engine phase OVERLAYS (`_emit_phase`): fwd/bwd/step durations live
    # INSIDE the train_batch window the step residual already books — the
    # ledger booking them too would double-count every training second
    "fwd",
    "bwd",
    "step",
    # restore path: runs before the restarted engine's first step entry,
    # i.e. inside the interval the ledger books as recovery (or startup
    # wall before the first boundary, disclosed as unattributed)
    "checkpoint/load",
    # background writer thread: overlapped with compute by design (the
    # step-loop cost it DOES impose is the ckpt_blocked host snapshot)
    "checkpoint/async_write",
    # legacy v1 one-shot generate path — not wired to a ledger
    "serving/generate",
    # layer-boundary OVERLAYS on the profiler's clock (replica._run,
    # scheduler.step, engine_v2, train_batch): they subdivide or enclose the
    # wall time the engine spans above and the driver's own idle/draining
    # bookings already account for — booking them too would double-count it
    "serving/loop_pull",
    "serving/loop_fanout",
    "serving/loop_idle",
    "serving/sched_step",
    "serving/engine_batch",
    "serving/engine_dispatch",
    "serving/engine_fetch",
    "serving/engine_commit",
    "serving/engine_observe",
    "train/dispatch",
    # zero-duration instants (consume no wall clock)
    "serving/request_rejected",
    "preemption_exit",
    "prefix_hit",
    "cache/evict",
    "serving/admitted",
    "serving/route",
    "serving/first_token",
    "serving/request_done",
    "serving/request_shed",
    "serving/request_failed",
    # tenant metering (serving/metering.py): a starvation detection is a
    # zero-duration instant — it consumes no wall clock
    "serving/tenant_starvation",
    # control plane (serving/control/): a controller decision is a
    # zero-duration instant — it consumes no wall clock
    "control/decision",
    # timeline sub-stage OVERLAYS (serving/disagg.py, serving/reqtrace.py):
    # export -> verify -> resume-adoption decompose the same wall window
    # serving/handoff already books as `handoff` — booking them too would
    # double-count every migrated request's broker seconds
    "serving/handoff_export",
    "serving/broker_verify",
    "serving/resume_wait",
)


class GoodputLedger:
    """Wall-clock attribution for one scope (the training run, or one
    serving replica). ``book`` accumulates seconds into a category;
    :meth:`report` reconciles against measured wall clock."""

    def __init__(self, kind, name):
        assert kind in ("train", "serving")
        self.kind = kind
        self.name = name
        self.categories = TRAIN_CATEGORIES if kind == "train" else SERVING_CATEGORIES
        self._lock = threading.Lock()
        self._books = {c: 0.0 for c in self.categories}
        self._t0 = time.perf_counter()
        self._t_stop = None
        # training step bookkeeping (driven by engine.train_batch)
        self._entry_t = None
        self._last_boundary = None
        self._explicit_mark = 0.0
        self._recovery_begin = None

    # -- core ----------------------------------------------------------
    def book(self, category, seconds):
        """Accumulate ``seconds`` into ``category`` (clamped at 0)."""
        if seconds <= 0.0:
            return
        with self._lock:
            self._books[category] += seconds

    def stop(self):
        """Freeze the wall clock (replica stopped / run over)."""
        if self._t_stop is None:
            self._t_stop = time.perf_counter()
        return self

    def resume(self, category="recovering"):
        """Un-freeze after :meth:`stop`: the frozen interval books into
        ``category`` (a restarted replica was down — that wall clock is
        recovery, not a hole in the ledger)."""
        with self._lock:
            if self._t_stop is not None:
                self._books[category] += max(0.0, time.perf_counter() - self._t_stop)
                self._t_stop = None
        return self

    def wall_s(self):
        return (self._t_stop or time.perf_counter()) - self._t0

    @property
    def stopped_at(self):
        """perf_counter stamp of :meth:`stop`, or None while running."""
        return self._t_stop

    def _explicit_total_locked(self):
        return sum(self._books[c] for c in _TRAIN_EXPLICIT)

    # -- training step hooks (engine.train_batch) ----------------------
    def note_recovery_begin(self, t=None):
        """A training attempt failed (or was preempted): wall clock from
        here to the restarted engine's first step entry is ``recovery``."""
        with self._lock:
            if self._recovery_begin is None:
                self._recovery_begin = t if t is not None else time.perf_counter()

    def step_entry(self):
        """Called at ``train_batch`` entry: books the gap since the last
        step boundary as ``recovery`` (when a restart is in flight) or
        ``idle`` (the caller was doing eval/logging/whatever — from the
        run's perspective, drained time). The explicit sources keep booking
        inside this gap too (a restarted engine re-compiles; a between-steps
        save blocks) — their delta is subtracted, same as the in-step
        compute residual, so one second is never both idle/recovery AND
        compile/ckpt_blocked."""
        now = time.perf_counter()
        with self._lock:
            explicit_now = self._explicit_total_locked()
            delta = max(0.0, explicit_now - self._explicit_mark)
            self._explicit_mark = explicit_now
            rb = self._recovery_begin
            if rb is not None:
                self._books["recovery"] += max(0.0, (now - rb) - delta)
                self._recovery_begin = None
            elif self._last_boundary is not None:
                self._books["idle"] += max(0.0, (now - self._last_boundary) - delta)
            self._entry_t = now

    def step_boundary(self, input_wait_s):
        """Called at the step boundary: books this step's input wait and the
        ``compute`` residual — step wall minus input wait minus whatever
        the explicit sources (compile listener, comm hook, ckpt save,
        stall gaps) booked inside this window."""
        now = time.perf_counter()
        with self._lock:
            entry = self._entry_t if self._entry_t is not None else now
            explicit_now = self._explicit_total_locked()
            delta = max(0.0, explicit_now - self._explicit_mark)
            self._explicit_mark = explicit_now
            iw = max(0.0, float(input_wait_s))
            self._books["input_wait"] += iw
            self._books["compute"] += max(0.0, (now - entry) - iw - delta)
            self._last_boundary = now
            self._entry_t = None

    # -- reconciliation -------------------------------------------------
    def report(self):
        """Categories + the conservation verdict: ``unattributed_s`` is the
        disclosed residual (wall minus booked), ``overbooked_s`` discloses
        any double-booking (both zero-floored — exactly one is nonzero)."""
        with self._lock:
            cats = dict(self._books)
        wall = max(self.wall_s(), 0.0)
        booked = sum(cats.values())
        unattributed = max(0.0, wall - booked)
        out = {
            "kind": self.kind,
            "name": self.name,
            "wall_s": round(wall, 6),
            "categories": {c: round(v, 6) for c, v in cats.items()},
            "unattributed_s": round(unattributed, 6),
            "overbooked_s": round(max(0.0, booked - wall), 6),
        }
        if wall > 0:
            fr = {c: round(v / wall, 6) for c, v in cats.items()}
            fr["unattributed"] = round(unattributed / wall, 6)
            out["fractions"] = fr
        else:
            out["fractions"] = {}
        return out


class RecompileSentinel:
    """Post-warmup compile detector. Engines report every NEW compiled
    program (a compiled-cache miss is exactly the moment XLA compiles) via
    :meth:`note_compile` with their own warmed flag; compiles after the
    warmup boundary are flagged, attributed to their shape bucket and the
    in-flight request uids, and burst-detected into compile storms."""

    def __init__(self, storm_k=5, storm_window_s=10.0):
        self.storm_k = max(2, int(storm_k))
        self.storm_window_s = float(storm_window_s)
        self._lock = threading.Lock()
        self._scopes = {}
        self._uid_resolvers = {}  # replica name -> fn(uid) -> request id|None

    def _scope(self, source):
        sc = self._scopes.get(source)
        if sc is None:
            with self._lock:
                sc = self._scopes.setdefault(source, {
                    "warmed_at": None, "expected": 0, "unexpected": 0,
                    "by_bucket": _Counter(), "events": deque(maxlen=64),
                    "storm_times": deque(), "storms": 0, "storm_latched": False,
                })
        return sc

    def set_uid_resolver(self, name, fn):
        """Replica-registered uid -> request-id join (None removes)."""
        if fn is None:
            self._uid_resolvers.pop(name, None)
        else:
            self._uid_resolvers[name] = fn

    def resolve_rids(self, uids):
        rids = []
        for u in uids or []:
            rid = None
            for fn in list(self._uid_resolvers.values()):
                try:
                    rid = fn(u)
                except Exception:  # noqa: BLE001 — telemetry never raises
                    rid = None
                if rid is not None:
                    break
            rids.append(rid)
        return rids

    def declare_warmed(self, source):
        """Declare the warmup boundary for ``source`` ('train'/'serving'):
        recorded for reporting; the flag engines pass to
        :meth:`note_compile` is what actually arms flagging (each serving
        engine owns its own boundary)."""
        sc = self._scope(source)
        if sc["warmed_at"] is None:
            sc["warmed_at"] = time.perf_counter()

    def note_compile(self, source, bucket, warmed, uids=None, rids=None,
                     seconds=None, step=None):
        """One newly compiled program on ``source`` ('train'/'serving').
        ``warmed`` is the calling engine's own warmup-boundary verdict."""
        sc = self._scope(source)
        with self._lock:
            if not warmed:
                sc["expected"] += 1
                return
            sc["unexpected"] += 1
            sc["by_bucket"][str(bucket)] += 1
            uids = [int(u) for u in (uids or [])][:8]
            if rids is None and uids:
                rids = self.resolve_rids(uids)
            ev = {"bucket": str(bucket), "uids": uids,
                  "rids": [r for r in (rids or []) if r] or None,
                  "step": step, "t": time.perf_counter()}
            sc["events"].append(ev)
            storm = self._note_storm_locked(sc, ev["t"])
        reg = get_metrics()
        if reg.enabled:
            # literal names by branch: the check_metric_names gate reads
            # registration sites statically
            if source == "train":
                reg.counter("train/unexpected_compiles_total").inc()
                if storm:
                    reg.counter("train/compile_storms_total").inc()
            else:
                reg.counter("serving/unexpected_compiles_total").inc()
                if storm:
                    reg.counter("serving/compile_storms_total").inc()
        get_flight_recorder().record("goodput", "unexpected_compile",
                                     source=source, bucket=str(bucket),
                                     uids=uids, rids=ev["rids"])
        tr = get_tracer()
        if tr.enabled:
            tr.instant("unexpected_compile", tid="compile", source=source,
                       bucket=str(bucket), uids=uids, rids=ev["rids"], step=step)
            if storm:
                tr.instant("compile_storm", tid="compile", source=source,
                           k=self.storm_k, window_s=self.storm_window_s)

    def _note_storm_locked(self, sc, now):
        """Burst detection: K unexpected compiles inside the window fires
        ONE storm (latched until the window drains below K)."""
        times = sc["storm_times"]
        times.append(now)
        while times and now - times[0] > self.storm_window_s:
            times.popleft()
        if len(times) >= self.storm_k:
            if not sc["storm_latched"]:
                sc["storm_latched"] = True
                sc["storms"] += 1
                return True
        else:
            sc["storm_latched"] = False
        return False

    def unexpected(self, source):
        sc = self._scopes.get(source)
        return sc["unexpected"] if sc else 0

    def report(self):
        out = {}
        for source, sc in list(self._scopes.items()):
            out[source] = {
                "warmed": sc["warmed_at"] is not None,
                "expected_compiles": sc["expected"],
                "unexpected_compiles": sc["unexpected"],
                "by_bucket": dict(sc["by_bucket"]),
                "storms": sc["storms"],
                "recent": [dict(e, t=round(e["t"], 3)) for e in list(sc["events"])[-8:]],
            }
        return out


class GoodputPlane:
    """Process-global goodput state (see :func:`get_goodput`): the training
    ledger, per-replica serving ledgers, the sentinel, and the export
    wiring (health-plane gauge/state/dump providers, compile listener,
    comm host-plane hook)."""

    def __init__(self):
        self.enabled = False
        self.train_warmup_steps = 2
        self.stall_gap_s = 0.05
        self._lock = threading.Lock()
        self._training = None
        self._serving = {}
        # high-water mark of compile wall already booked: jax emits one
        # duration event PER PHASE (jaxpr trace / lower / backend compile)
        # with nested sub-traces, and threads compile concurrently — summing
        # raw durations overbooks. The ledger books the UNION of compile
        # intervals instead: each event contributes only the part of
        # [now-duration, now] past the mark.
        self._compile_mark = 0.0
        self._gauge_fn = None   # bound-method refs cached at configure time
        self._report_fn = None  # (the health clears are identity-checked)
        self.sentinel = RecompileSentinel()

    # -- configuration --------------------------------------------------
    def configure(self, config=None, **kwargs):
        """Arm the plane. ``config`` is a ``GoodputConfig`` block
        (``monitor_config.goodput``); explicit kwargs win over it."""

        def knob(name, default=None):
            if name in kwargs and kwargs[name] is not None:
                return kwargs[name]
            if config is not None:
                return getattr(config, name, default)
            return default

        enabled = knob("enabled")
        if enabled is not None and not enabled:
            self.shutdown()
            return self
        if not enabled and not self.enabled:
            return self
        self.train_warmup_steps = int(knob("train_warmup_steps",
                                           self.train_warmup_steps))
        self.stall_gap_s = float(knob("stall_gap_s", self.stall_gap_s))
        self.sentinel.storm_k = max(2, int(knob("storm_k", self.sentinel.storm_k)))
        self.sentinel.storm_window_s = float(knob("storm_window_s",
                                                  self.sentinel.storm_window_s))
        if not self.enabled:
            # the ledger's counters/fractions are served through the metrics
            # registry + health providers — the goodput block implies
            # metrics, like `trace` and `health` do
            get_metrics().enable()
            from .trace import add_compile_listener

            add_compile_listener(self._on_compile_event)
            self._set_comm_hook(self._on_host_collective)
        # health providers are (re-)registered on EVERY arm, not just the
        # first: HealthPlane.shutdown() clears all providers, so a later
        # health re-arm (drills do this) would otherwise serve /healthz and
        # forensic dumps with no goodput section while this plane reports
        # enabled (the memory plane re-registers the same way)
        from .health import get_health

        hp = get_health()
        if self._gauge_fn is None:
            # bound-method references are cached ONCE: the health clears
            # are identity-checked (rollover contract), and
            # `self.gauge_rows` makes a fresh object per attribute access
            self._gauge_fn = self.gauge_rows
            self._report_fn = self.report
        hp.set_gauge_provider("goodput", self._gauge_fn)
        hp.set_state_provider("goodput", self._report_fn)
        hp.set_dump_provider("goodput", self._report_fn)
        self.enabled = True
        return self

    def shutdown(self):
        """Disarm + drop every ledger. Idempotent."""
        if self.enabled:
            from .trace import remove_compile_listener

            remove_compile_listener(self._on_compile_event)
            self._set_comm_hook(None)
            from .health import get_health

            hp = get_health()
            hp.clear_gauge_provider("goodput", self._gauge_fn)
            hp.clear_state_provider("goodput", self._report_fn)
            hp.clear_dump_provider("goodput", self._report_fn)
        self.enabled = False
        with self._lock:
            self._training = None
            self._serving.clear()
            self._compile_mark = 0.0
        self.sentinel = RecompileSentinel(self.sentinel.storm_k,
                                          self.sentinel.storm_window_s)
        return self

    def _set_comm_hook(self, fn):
        try:
            from ..comm import comm as _comm  # lazy: comm imports monitor.trace

            _comm.goodput_comm_hook = fn
        except Exception as e:  # noqa: BLE001 — telemetry never kills runs
            self._log().warning(f"goodput: comm hook not armed: {e!r}")

    # -- ledgers ---------------------------------------------------------
    @property
    def training(self):
        """The process training ledger (created on first access while the
        plane is armed — it spans engine restarts)."""
        if not self.enabled:
            return None
        with self._lock:
            if self._training is None:
                self._training = GoodputLedger("train", "train")
            return self._training

    def serving_ledger(self, name):
        """The serving ledger for replica/engine ``name`` (created on first
        access; wall-clock origin = that first access)."""
        if not self.enabled:
            return None
        with self._lock:
            led = self._serving.get(name)
            if led is None or led._t_stop is not None:
                # a STOPPED ledger under this name belongs to a previous
                # replica generation (gateways reuse replica names "0"/"1"):
                # a new instance gets a fresh wall-clock origin — booking
                # into a frozen clock would overdraw it. A replica that
                # merely restarts keeps its own ledger reference and
                # resume()s it instead (it never re-fetches here).
                led = self._serving[name] = GoodputLedger("serving", str(name))
            return led

    def note_training_failure(self):
        """A training attempt just failed/preempted (called by the
        resilience runner): start the recovery clock."""
        with self._lock:
            led = self._training
        if led is not None:
            led.note_recovery_begin()

    # -- event feeds -----------------------------------------------------
    def _on_compile_event(self, source, event, duration):
        """Compile listener subscriber (monitor/trace.py): training-scope
        compile seconds book into the training ledger; serving compiles
        already ride the forward walltime their put/decode booked."""
        if source == "train":
            now = time.perf_counter()
            with self._lock:
                led = self._training
                # interval-union booking (see _compile_mark): nested phase
                # events and concurrent compiling threads must not book the
                # same wall second twice
                start = max(now - duration, self._compile_mark)
                seconds = max(0.0, now - start)
                self._compile_mark = max(self._compile_mark, now)
            if led is not None:
                led.book("compile", seconds)

    def _on_host_collective(self, op, duration):
        """Blocking host-plane collective bracket (comm/comm.py): the
        exposed-comm seconds of the step boundary."""
        with self._lock:
            led = self._training
        if led is not None:
            led.book("comm_exposed", duration)

    # -- export ----------------------------------------------------------
    def report(self):
        with self._lock:
            train = self._training
            serving = dict(self._serving)
        return {
            "train": train.report() if train is not None else None,
            "serving": {name: led.report() for name, led in serving.items()},
            "sentinel": self.sentinel.report(),
        }

    def gauge_rows(self):
        """Labelled Prometheus rows for the health exporter:
        ``goodput/seconds_total{scope=...,category=...}`` + fraction gauges
        + the sentinel's per-bucket unexpected-compile counts."""
        rows = []
        with self._lock:
            ledgers = ([] if self._training is None else [self._training]) \
                + list(self._serving.values())
        for led in ledgers:
            rep = led.report()
            scope = f"{led.kind}:{led.name}" if led.kind == "serving" else "train"
            cats = dict(rep["categories"])
            cats["unattributed"] = rep["unattributed_s"]
            for cat, secs in cats.items():
                rows.append(("goodput/seconds_total",
                             {"scope": scope, "category": cat}, secs))
            for cat, frac in rep.get("fractions", {}).items():
                rows.append(("goodput/fraction",
                             {"scope": scope, "category": cat}, frac))
        for source, sc in self.sentinel.report().items():
            for bucket, n in sc["by_bucket"].items():
                rows.append((f"{source}/unexpected_compiles_total",
                             {"bucket": bucket}, n))
        return rows

    @staticmethod
    def _log():
        from ..utils.logging import logger  # lazy: keep module import-light

        return logger


_plane = GoodputPlane()


def get_goodput() -> GoodputPlane:
    return _plane


def configure_goodput(config=None, **kwargs) -> GoodputPlane:
    return _plane.configure(config=config, **kwargs)


def conservation_ok(report, tolerance=0.05, max_unattributed_frac=None):
    """The PR 7 acceptance arithmetic for one ledger report: booked
    categories + disclosed unattributed must equal measured wall clock
    within ``tolerance`` (double-booking shows up as overbooked_s > the
    tolerance band and fails). By construction ``unattributed_s`` absorbs
    any under-attribution, so callers whose scope SHOULD be mostly booked
    (a step loop under load, a drill) pass ``max_unattributed_frac`` to
    make silent hook-loss a failure too — scopes with legitimate
    un-booked orchestration time (the bench engine between phases) leave
    it None and read the disclosed fraction instead."""
    wall = report["wall_s"]
    if wall <= 0:
        return False
    if max_unattributed_frac is not None and \
            report["unattributed_s"] > max_unattributed_frac * wall:
        return False
    total = sum(report["categories"].values()) + report["unattributed_s"]
    return abs(total - wall) <= tolerance * wall and \
        report["overbooked_s"] <= tolerance * wall
