"""Replica driver: one background thread per engine running the SplitFuse
put/decode loop and fanning generated tokens out to per-request streams.

The driver owns the ONLY thread that touches its engine (JAX dispatch,
scheduler state): the HTTP handlers and the admission path never call into
the engine's forward — they enqueue work and read from
:class:`TokenStream`s. A slow (or absent) stream consumer therefore cannot
stall the decode loop: ``TokenStream.push`` never blocks, and the stream's
buffer is bounded by the request's own ``max_new_tokens`` (which admission
capped), so a stalled client costs one bounded buffer, not batch progress.

Liveness rides the PR 5 health plane: while a replica has work its driver
beats the instance-qualified ``serving:<name>`` source every loop (the
family deadline ``monitor.health.deadline_serving_s`` applies via the
prefix fallback), and the engine's own ``put``/``decode`` begin/end the
``serving`` source around each forward — a wedged device call or a wedged
driver both trip the stall watchdog with a full forensic dump.
"""

import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..monitor.flight import get_flight_recorder
from ..monitor.goodput import get_goodput
from ..monitor.health import get_health
from ..monitor.metrics import get_metrics
from ..monitor.trace import NULL_SPAN, get_tracer
from ..inference.v2 import DynamicSplitFuseScheduler
from ..runtime.resilience import chaos


class TokenStream:
    """Bounded single-producer / single-consumer token queue for ONE request.

    The replica driver pushes token batches (never blocking — overflow past
    ``capacity`` is counted and dropped, though with ``capacity ==
    max_new_tokens`` it is unreachable); the HTTP handler drains at the
    client's pace. ``finish`` latches the terminal state exactly once.
    """

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._tokens: List[int] = []   # produced tokens, in order
        self._cursor = 0               # consumer read position
        self._cond = threading.Condition()
        self.done = False
        self.finish_reason: Optional[str] = None
        self.error: Optional[str] = None
        self.dropped = 0
        self.first_token_t: Optional[float] = None
        self.last_token_t: Optional[float] = None

    @property
    def produced(self) -> int:
        return len(self._tokens)

    def push(self, tokens) -> int:
        """Append ``tokens`` (non-blocking). Returns how many were kept.
        A finished stream drops everything — ``finish`` latches the
        terminal state, so a late producer cannot make the final frame's
        ``n_tokens`` disagree with the token list a reader collects."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            return 0
        now = time.perf_counter()
        with self._cond:
            if self.done:
                self.dropped += len(tokens)
                return 0
            space = self.capacity - len(self._tokens)
            kept = tokens[:max(0, space)]
            self.dropped += len(tokens) - len(kept)
            if kept:
                if self.first_token_t is None:
                    self.first_token_t = now
                self.last_token_t = now
                self._tokens.extend(kept)
                self._cond.notify_all()
        return len(kept)

    def finish(self, reason: str = "length", error: Optional[str] = None):
        with self._cond:
            if self.done:
                return
            self.done = True
            self.finish_reason = reason
            self.error = error
            self._cond.notify_all()

    def get(self, timeout: Optional[float] = None):
        """Drain everything available (blocking up to ``timeout`` for the
        first new token). Returns ``(tokens, done)`` — ``([], done)`` on
        timeout, so the caller can distinguish 'no progress' from 'over'."""
        with self._cond:
            if self._cursor >= len(self._tokens) and not self.done:
                self._cond.wait(timeout)
            out = self._tokens[self._cursor:]
            self._cursor += len(out)
            return out, self.done and self._cursor >= len(self._tokens)

    def wait_done(self, timeout: Optional[float] = None) -> bool:
        deadline = None if timeout is None else time.perf_counter() + timeout
        with self._cond:
            while not self.done:
                rem = None if deadline is None else deadline - time.perf_counter()
                if rem is not None and rem <= 0:
                    return False
                self._cond.wait(rem)
            return True

    def all_tokens(self) -> List[int]:
        with self._cond:
            return list(self._tokens)


class GatewayRequest:
    """One admitted request's lifecycle record (admission -> stream)."""

    __slots__ = ("uid", "prompt", "max_new_tokens", "slo_class", "eos_token_id",
                 "stream", "replica_name", "t_admitted", "cached_tokens",
                 "uncached_tokens", "ttft_ms", "tpot_ms", "rid", "ctx", "sampling",
                 "tenant", "resume_base", "handoff_state",
                 "t_handoff_start", "t_handoff_export", "t_handoff_verify",
                 "t_handoff_done", "t_resume_enqueued", "t_resume_submitted",
                 "handoff_ms", "resume_wait_ms")

    def __init__(self, uid, prompt, max_new_tokens, slo_class, eos_token_id=None,
                 rid=None, ctx=None, sampling=None, tenant=None):
        self.uid = int(uid)
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.slo_class = str(slo_class)
        self.eos_token_id = eos_token_id
        self.sampling = sampling  # SamplingParams | None (= greedy)
        self.stream = TokenStream(capacity=self.max_new_tokens)
        self.replica_name = None
        self.t_admitted = None
        self.cached_tokens = 0    # prefix-cache credit measured at admission
        self.uncached_tokens = 0  # what admission actually charged
        self.ttft_ms = None
        self.tpot_ms = None
        # request id: always present (echoed on the X-Request-Id response
        # header + SSE meta); ctx only when request tracing is configured
        self.rid = rid
        self.ctx = ctx
        # sanitized tenant identity (X-Tenant-Id, DEFAULT_TENANT when
        # absent): always carried so the request log and SSE meta can name
        # the owner; the METER only exists when the config block asks
        self.tenant = tenant
        # disaggregated-serving migration state (serving/disagg.py):
        # resume_base = tokens the stream already held when this request
        # resumed on a decode replica (its scheduler counts from 0 again);
        # handoff_state latches the one migration attempt — None (never
        # tried) | 'migrated' | 'fallback' (failed, decoding in place)
        self.resume_base = 0
        self.handoff_state = None
        # migration stage stamps, all on perf_counter (the one-clock rule
        # the timeline assembler's segments-sum acceptance rests on):
        # broker boundaries stamped by DisaggCoordinator.try_handoff,
        # resume boundaries by the DESTINATION replica. Plain float slots,
        # always stamped when a migration runs — handoff_ms/resume_wait_ms
        # reach the summary record and SSE final frame WITHOUT the timeline
        # plane armed (the PR 18 residual)
        self.t_handoff_start = None
        self.t_handoff_export = None
        self.t_handoff_verify = None
        self.t_handoff_done = None   # failure path only (fallback-in-place)
        self.t_resume_enqueued = None
        self.t_resume_submitted = None
        self.handoff_ms = None
        self.resume_wait_ms = None


class EngineReplica:
    """Driver thread + SplitFuse scheduler over ONE ``InferenceEngineV2``."""

    # bounded idle wait between wake polls: purely a backstop — submit()
    # sets the wake event, so admit latency does not ride this; short
    # enough that pause()/stop() stay responsive, long enough that an idle
    # fleet of replicas is not spinning on the admission lock
    IDLE_WAIT_S = 0.05

    def __init__(self, name, engine, admission, config, reqtrace=None, meter=None,
                 role="mixed"):
        self.name = str(name)
        self.engine = engine
        self.config = config
        # disaggregated pool role (serving/disagg.py): "prefill" replicas
        # push completed prefills to the decode pool through the KV handoff;
        # "mixed" (the default) is the co-located baseline and never migrates
        self.role = str(role)
        self._disagg = None  # DisaggCoordinator, wired by the gateway
        self._timeline = None  # TimelineCollector, wired by the gateway
        self._resume_lock = threading.Lock()
        self._resumes = []  # (req, tokens, remaining) adopted migrations
        self._admission = admission
        self._reqtrace = reqtrace
        # tenant metering plane (serving/metering.py): compute-seconds via
        # the step observer, queue-seconds at dequeue, terminal accounting
        # at close-out. None keeps every site at one attribute check and
        # attaches NOTHING to the engine (the zero-overhead-off contract).
        self._meter = meter
        if meter is not None:
            # per-block owner stamps + prefix-hit attribution ride the
            # engine's own lifecycle hooks — wired through the ONE public
            # entry (the check_gateway_api contract keeps the request
            # plane out of engine internals)
            engine.set_tenant_meter(meter)
        self._scheduler = DynamicSplitFuseScheduler(
            engine, token_budget=config.token_budget or None)
        if reqtrace is not None or meter is not None:
            # per-chunk prefill attribution + per-tenant compute-second
            # apportionment ride the scheduler's step observer (None by
            # default — the un-traced, un-metered path is untouched)
            self._scheduler.step_observer = self._on_sched_step
        self._max_inflight = (config.max_inflight_per_replica
                              or engine.max_concurrent_sequences)
        # total KV blocks a lone request may reserve: measured on the idle
        # engine (free + evictable = the whole usable pool), so validation
        # can refuse requests the scheduler could NEVER admit (they would
        # otherwise sit in the pending queue forever)
        self.pool_blocks = engine.available_blocks
        self._streams: Dict[int, GatewayRequest] = {}
        self._inflight = 0  # requests submitted to the scheduler, not finished
        self._cancel_lock = threading.Lock()
        self._cancelled = []  # uids handed back by timed-out/gone clients
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._wake = threading.Event()
        self.paused = False
        # controller-driven drain: distinct from ``paused`` (tests and the
        # gateway drain pause replicas that must KEEP receiving placements
        # so queues build); the router skips draining replicas whenever an
        # un-draining live alternative exists
        self.draining = False
        self.started = False
        self.warmed = False
        self.steps = 0
        # goodput ledger (attached post-warmup in start(); None = one check
        # per loop iteration, the PR 5 zero-overhead contract)
        self._goodput = None
        self._gp_death_t = None

    # -- public surface the gateway/router/tests drive ---------------------
    @property
    def alive(self) -> bool:
        if not (self.started and self._thread is not None and self._thread.is_alive()):
            return False
        hb = get_health()
        if hb.enabled:
            entry = hb.heartbeats().get(self.heartbeat_source)
            if entry is not None and entry["tripped"]:
                return False
        return True

    @property
    def heartbeat_source(self) -> str:
        return f"serving:{self.name}"

    @property
    def load(self) -> int:
        """Scheduler-inflight + class-queued requests bound for this replica
        (the router's least-loaded signal)."""
        return self._inflight + self._admission.depth(replica=self.name)

    @property
    def max_inflight(self) -> int:
        """Concurrent-request capacity (the saturation denominator the
        disagg coordinator and control plane compare ``load`` against)."""
        return self._max_inflight

    def spec_params(self):
        """Live speculative knobs (``{"k", "tree_width"}``) or None when
        this replica is not speculating — the control plane's read side."""
        return self._scheduler.spec_params()

    def set_spec_params(self, k=None, tree_width=None):
        """Control-plane actuator: retarget speculative K / tree width for
        future draft rounds (scheduler forwarder — the request plane stays
        out of scheduler internals per the check_gateway_api contract).
        Returns the applied params, or None when not speculating."""
        return self._scheduler.set_spec_params(k=k, tree_width=tree_width)

    def prefix_overlap(self, prompt_tokens) -> int:
        """Routing oracle: tokens of ``prompt_tokens`` this replica's radix
        tree could serve, via the PURE read-only ``PrefixKVCache.match`` —
        no references taken, no LRU touch, no stats."""
        pc = self.engine.prefix_cache
        if pc is None:
            return 0
        return int(pc.match(np.asarray(prompt_tokens, np.int32).reshape(-1)).n_cached_tokens)

    def inflight_summaries(self):
        """Last-resort forensics: one summary row per request this replica
        is currently serving (queued-to-scheduler or decoding) — the rows a
        stall dump needs to NAME the requests on a wedged replica."""
        now = time.perf_counter()
        out = []
        for uid, req in list(self._streams.items()):
            row = {"request_id": req.rid, "uid": uid, "replica": self.name,
                   "tenant": req.tenant, "slo_class": req.slo_class,
                   "prompt_tokens": int(req.prompt.size),
                   "max_new_tokens": req.max_new_tokens,
                   "produced": req.stream.produced,
                   "age_ms": (round((now - req.t_admitted) * 1e3, 1)
                              if req.t_admitted else None)}
            if req.ctx is not None:
                row.update({"prefix_hit_tokens": req.ctx.prefix_hit_tokens,
                            "prefill_chunks": req.ctx.prefill_chunks})
            out.append(row)
        return out

    def _on_sched_step(self, uids, chunk_sizes, t0, dur, kind="put"):
        """Scheduler step observer: apportion one engine forward's wall
        time across the requests whose chunks composed it, by token share.
        Two consumers ride the same apportionment:

          * request tracing — per-chunk prefill spans for ``put`` steps
            (a request still pre-first-token is by definition prefilling);
          * tenant metering — compute-seconds charged to each request's
            tenant, bucketed prefill/decode/spec_verify so the per-tenant
            sum reconciles with the goodput ledger's serving active
            categories (the conservation acceptance bar).
        """
        total = sum(chunk_sizes) or 1
        meter = self._meter
        for uid, n in zip(uids, chunk_sizes):
            req = self._streams.get(uid)
            if req is None:
                continue
            share = dur * (n / total)
            if kind == "put" and req.ctx is not None \
                    and req.stream.first_token_t is None:
                self._reqtrace.on_prefill_chunk(req, n, t0, share)
            if meter is not None:
                if kind == "put":
                    bucket = "prefill" if n > 1 else "decode"
                else:
                    bucket = kind  # "decode" | "spec_verify"
                # pool=<role> feeds the per-pool compute split the purity
                # acceptance bar measures (zero decode-seconds on a prefill
                # pool is what proves disaggregation actually disaggregated)
                meter.on_compute(req.tenant, bucket, share, tokens=n,
                                 pool=self.role)

    def set_disagg(self, coordinator):
        """Arm the disaggregation coordinator (gateway wiring, pre-start):
        prefill-role replicas begin offering completed prefills to it."""
        self._disagg = coordinator

    def set_timeline(self, collector):
        """Arm the timeline collector (gateway wiring, pre-start): the
        driver loop starts reporting measured chaos-fire stall gaps to it
        (the assembler's `stall` overlay source). None keeps the loop at
        the same one-check cost as the un-timelined path."""
        self._timeline = collector

    def detach_request(self, uid: int):
        """Surgically remove ``uid`` from this replica WITHOUT terminal
        accounting — the request is migrating, not finishing (the decode
        replica close-out runs exactly once, over the full token count).
        Driver-thread only. The scheduler cancel flushes the engine
        sequence, which publishes its full blocks into this replica's OWN
        radix tree first — the migrated prefix stays locally reusable, so
        prefix sharing flows both directions of the handoff."""
        req = self._streams.pop(int(uid), None)
        if req is None:
            return
        if self._scheduler.cancel(int(uid)):
            self._scheduler.discard_result(int(uid))
        self._inflight -= 1

    def enqueue_resume(self, req, tokens, remaining):
        """Adopt a migrated request (called from the SOURCE replica's driver
        via the coordinator): an infallible list append — the scheduler
        submit happens on THIS replica's own driver at its next loop
        iteration (the single-threaded-scheduler contract). ``tokens`` is
        prompt + everything generated so far; ``remaining`` is the new-token
        budget left."""
        # resume_wait starts HERE (the source driver's enqueue): everything
        # until this replica's driver submits is destination adoption-queue
        # time — the dst half of the handoff gap PR 18 left unattributed
        req.t_resume_enqueued = time.perf_counter()
        with self._resume_lock:
            self._resumes.append((req,
                                  np.asarray(tokens, np.int32).reshape(-1),
                                  max(1, int(remaining))))
        self.wake()

    def book_handoff(self, seconds: float):
        """Goodput booking for handoff broker wall time: driver seconds
        spent migrating (or failing to migrate) a request are neither
        prefill nor decode — they get their own serving category."""
        if self._goodput is not None:
            self._goodput.book("handoff", max(0.0, float(seconds)))

    def cancel(self, uid: int):
        """Request abort of ``uid`` (client timed out / disconnected). The
        actual teardown runs on the DRIVER thread at its next loop — the
        scheduler is single-threaded by contract. An abandoned request
        would otherwise decode to max_new_tokens holding its KV reservation
        and an inflight slot against live traffic."""
        with self._cancel_lock:
            self._cancelled.append(int(uid))
        self.wake()

    def pause(self):
        self.paused = True

    def resume(self):
        self.paused = False
        self.wake()

    def drain(self):
        """Control-plane actuator: stop pulling queued work AND steer the
        router away (new placements go to un-draining replicas while any
        exist). In-flight requests finish; the replica stays alive and
        warmed for an instant undrain."""
        self.draining = True
        self.paused = True

    def undrain(self):
        self.draining = False
        self.paused = False
        self.wake()

    def wake(self):
        self._wake.set()

    def start(self):
        if self.started:
            return self
        seq_warmed = []
        if self.config.warmup:
            for bucket, steps in self.config.warmup:
                # boundary declared once after the WHOLE sequence — a
                # per-call declaration would flag entries 2..N's own
                # warmup compiles as steady-state recompiles
                self.engine.warmup([int(bucket)], int(steps),
                                   declare_warmed=False)
                seq_warmed.append(int(bucket))
        if self.config.warmup_token_buckets:
            # prefill put buckets — also honored WITHOUT decode warmup
            # entries (falls back to the smallest engine seq bucket). The
            # sentinel boundary below makes any bucket missed here a
            # flagged steady-state recompile.
            self.engine.warmup(seq_warmed or [1], [],
                               token_buckets=self.config.warmup_token_buckets,
                               declare_warmed=False)
        if self.config.warmup or self.config.warmup_token_buckets:
            self.engine.declare_gp_warmed()
        self.warmed = True
        gp = get_goodput()
        if gp.enabled and self._goodput is None:
            # ledger wall-clock origin is HERE, after warmup: the serving
            # taxonomy has no compile bucket — warmed-engine serving time is
            # what the ledger attributes (warmup compiles ride the trace bus
            # + sentinel's expected count instead)
            self._goodput = gp.serving_ledger(self.name)
            self.engine.goodput_ledger = self._goodput
        elif self._goodput is not None:
            # stop() -> start() on the same replica: the frozen interval was
            # a deliberate drain, not a failure — book it as draining and
            # un-freeze (no-op if the clock is already running)
            self._goodput.resume("draining")
        if self._goodput is not None:
            # (re-)register the uid -> request-id join; stop() clears it so
            # a dead replica never pins itself on the process-global plane
            self.engine.gp_rid_resolver = self._rid_of
            gp.sentinel.set_uid_resolver(self.name, self._rid_of)
        self._stop.clear()
        self._thread = threading.Thread(target=self._run,
                                        name=f"dstpu-serving-{self.name}", daemon=True)
        self.started = True
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0):
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
        self.started = False
        self._fail_active("replica_stopped")
        if self._goodput is not None:
            self._goodput.stop()  # freeze wall clock: reports stay stable
            # drop the sentinel's strong reference to this replica (the
            # plane is process-global; a stopped replica must be
            # collectable). restart()/start() re-register.
            get_goodput().sentinel.set_uid_resolver(self.name, None)

    def _rid_of(self, uid):
        """uid -> request id for the sentinel's compile-tail attribution
        (None once the request left this replica)."""
        req = self._streams.get(int(uid))
        return req.rid if req is not None else None

    def restart(self):
        """Bring a dead replica back into rotation (chaos drill / operator
        recovery): only valid once the previous driver thread has exited —
        a live driver is left alone. Active state was already failed on the
        way down (crash handler or :meth:`stop`); the engine and scheduler
        are reused, warmup is not repeated, and the first fresh heartbeat
        re-arms liveness for the router."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._fail_active("replica_stopped")  # belt-and-braces: crash paths
        gl = self._goodput
        if gl is not None:
            # down-time books as `recovering`: crash (death stamp) -> now,
            # CLAMPED to any stop() freeze — resume() books the frozen
            # interval itself, so booking past the freeze would double-count
            if self._gp_death_t is not None:
                end = gl.stopped_at if gl.stopped_at is not None \
                    else time.perf_counter()
                gl.book("recovering", end - self._gp_death_t)
                self._gp_death_t = None
            gl.resume("recovering")
            get_goodput().sentinel.set_uid_resolver(self.name, self._rid_of)
        self._stop.clear()
        self._wake.clear()
        self.paused = False
        self.draining = False
        self._thread = threading.Thread(target=self._run,
                                        name=f"dstpu-serving-{self.name}", daemon=True)
        self.started = True
        self._thread.start()
        get_metrics().counter("gateway/replica_restarts_total").inc()
        return self

    # -- driver loop --------------------------------------------------------
    def _run(self):
        hb = get_health()
        src = self.heartbeat_source
        gl = self._goodput
        tl = self._timeline
        tr = get_tracer()
        stall_gap = get_goodput().stall_gap_s
        try:
            while not self._stop.is_set():
                # chaos injection point: a storm's replica kill lands here,
                # between scheduler steps (no-op-when-unhooked fire())
                t_fire = time.perf_counter() if (gl is not None
                                                 or tl is not None) else 0.0
                chaos.fire("serving/driver", {"replica": self.name})
                if gl is not None or tl is not None:
                    gap = time.perf_counter() - t_fire
                    if gap >= stall_gap:
                        # a fire hook wedged the driver — the same gap the
                        # serving watchdog trips on. Booked as `stalled`,
                        # NOT idle: the replica had (or was denied) work.
                        if gl is not None:
                            gl.book("stalled", gap)
                        if tl is not None:
                            # the measured interval, not a flag: the
                            # assembler re-attributes exactly the overlap
                            # with each in-flight request's segments
                            tl.on_stall(self.name, t_fire, gap)
                busy = False
                # the driver's own account of its loop, on the profiler's
                # clock: pulling, stepping (the scheduler's and the engine's
                # spans nest inside), fanning out, waiting for work
                sp = tr.span("serving/loop_pull", tid="serving")
                if sp is not NULL_SPAN and not self._pullable():
                    sp = NULL_SPAN  # nothing to pull or cancel: no span
                with sp:
                    self._process_cancellations()
                    if not self.paused:
                        busy = self._pull_resumes() or busy
                        busy = self._pull_admitted(sp) or busy
                    if sp is not NULL_SPAN:
                        sp.set_args(queue_depth=self._admission.depth(replica=self.name),
                                    inflight=self._inflight)
                if not self.paused and self._scheduler.has_work:
                    if hb.enabled:
                        # armed exactly while work is in flight: a wedged
                        # step (or a dead driver) goes stale and trips the
                        # serving-family deadline
                        hb.beat(src)
                    busy = self._step() or busy
                if not busy:
                    if hb.enabled:
                        hb.disarm(src)
                    t_wait = time.perf_counter() if gl is not None else 0.0
                    with tr.span("serving/loop_idle", tid="serving", paused=self.paused):
                        self._wake.wait(self.IDLE_WAIT_S)
                    self._wake.clear()
                    if gl is not None:
                        gl.book("draining" if self.paused else "idle",
                                time.perf_counter() - t_wait)
        except BaseException:  # noqa: BLE001 — driver death is a replica
            # failure, distinct from shed in the metrics: the counter is what
            # lets an operator tell "queue full" from "replica died" on a
            # dashboard. Every request this driver was actively serving is
            # failed HERE (the loop-level crash window the _step handler
            # cannot see), so no admitted request goes unreported.
            get_metrics().counter("gateway/replica_failures_total").inc()
            get_flight_recorder().record("serving", "replica_driver_death",
                                         replica=self.name)
            if gl is not None:
                # recovery clock starts at the death site; restart() books it
                self._gp_death_t = time.perf_counter()
            self._fail_active("replica_stopped")
            raise
        finally:
            # the driver is the ONLY consumer of this replica's admission
            # queues: on the way out (clean stop or crash) fail whatever is
            # still queued, so waiting clients get an immediate error instead
            # of the full request timeout, and a stranded full queue cannot
            # pin gateway readiness to False
            self._admission.fail_for(self.name, "replica_stopped")
            if hb.enabled:
                hb.release(src)

    def _fail_active(self, error):
        """Fail every request currently on the scheduler (driver death /
        stop): cancel its engine sequence so the KV reservation frees,
        finish its stream so the waiting client gets an immediate terminal
        frame, and finalize its trace record."""
        for uid, req in list(self._streams.items()):
            try:
                if self._scheduler.cancel(uid):
                    self._scheduler.discard_result(uid)
            except Exception as e:  # noqa: BLE001 — a poisoned engine must
                # not keep the remaining streams from being failed/reported
                get_flight_recorder().record("serving", "cancel_error",
                                             replica=self.name, uid=uid,
                                             error=repr(e))
            req.stream.finish(reason="error", error=error)
            if self._reqtrace is not None:
                self._reqtrace.finalize(req)
        self._streams.clear()
        self._inflight = 0
        # adopted migrations still queued for submit die with the driver
        # too — the never-lose-a-request contract covers the resume queue
        with self._resume_lock:
            resumes, self._resumes = self._resumes, []
        for req, _tokens, _remaining in resumes:
            req.stream.finish(reason="error", error=error)
            if self._reqtrace is not None:
                self._reqtrace.finalize(req)

    def _process_cancellations(self):
        with self._cancel_lock:
            uids, self._cancelled = self._cancelled, []
        for uid in uids:
            req = self._streams.pop(uid, None)
            if req is None:
                continue  # already finished (or never reached this replica)
            spec = self._scheduler.spec_summary(uid)  # read before discard drops it
            if self._scheduler.cancel(uid):
                self._scheduler.discard_result(uid)
            self._inflight -= 1
            req.stream.finish(reason="error", error="cancelled")
            get_metrics().counter(f"gateway/cancelled_{req.slo_class}_total").inc()
            if self._meter is not None:
                self._meter.on_terminal(req.tenant, req.rid, req.slo_class,
                                        "cancelled", req.stream.produced,
                                        cancelled=True)
            if self._reqtrace is not None:
                # the stream latched its REAL terminal first (timeout /
                # disconnect / explicit cancel) — finalize reads it
                self._reqtrace.finalize(req, spec=spec)

    def _pullable(self) -> bool:
        """Whether this loop iteration has anything to cancel, adopt or pull
        (asked only while a span sink is live, to leave empty pulls out)."""
        if self._cancelled:
            return True
        return not self.paused and bool(
            self._resumes or (self._inflight < self._max_inflight
                              and self._admission.depth(replica=self.name)))

    def _pull_admitted(self, sp=NULL_SPAN) -> bool:
        pulled = 0
        waits = [] if sp is not NULL_SPAN else None  # admitted -> pulled, for ``loop_pull``
        while self._inflight < self._max_inflight:
            req = self._admission.pop_for(self.name)
            if req is None:
                break
            try:
                self._scheduler.submit(req.uid, req.prompt,
                                       max_new_tokens=req.max_new_tokens,
                                       eos_token_id=req.eos_token_id,
                                       sampling=req.sampling,
                                       tenant=req.tenant)
            except Exception as e:  # validation said yes, scheduler said no
                req.stream.finish(reason="error", error=f"{type(e).__name__}: {e}")
                if self._reqtrace is not None:
                    self._reqtrace.finalize(req)
                continue
            if self._reqtrace is not None and req.ctx is not None:
                self._reqtrace.on_dequeue(req)
            if self._meter is not None and req.t_admitted is not None:
                # queue-seconds per SLO class, stamped at the replica pull
                # (the same admitted->dequeued interval the tracing stage
                # breakdown measures) — also feeds the starvation detector
                self._meter.on_queue_wait(
                    req.tenant, req.slo_class,
                    time.perf_counter() - req.t_admitted, rid=req.rid)
            self._streams[req.uid] = req
            self._inflight += 1
            pulled += 1
            if waits is not None and req.t_admitted is not None and len(waits) < 32:
                waits.append(round((time.perf_counter() - req.t_admitted) * 1e3, 3))
        if waits is not None:
            sp.set_args(pulled=pulled, wait_ms=waits)
        return pulled > 0

    def _pull_resumes(self) -> bool:
        """Driver-side half of a handoff adoption: submit each migrated
        request's full stream (prompt + produced) with its remaining token
        budget. The host chain the handoff installed makes the submit's
        prefix acquisition a hierarchy hit — only the un-exported tail
        re-prefills before decode continues. Bypasses ``_max_inflight``
        (the request already holds a fleet-wide slot, counted on its source
        at admission) and never raises: a failed submit finishes the stream
        with the error, so migrated requests are never silently lost."""
        with self._resume_lock:
            if not self._resumes:
                return False
            items, self._resumes = self._resumes, []
        for req, tokens, remaining in items:
            try:
                self._scheduler.submit(req.uid, tokens,
                                       max_new_tokens=remaining,
                                       eos_token_id=req.eos_token_id,
                                       sampling=req.sampling,
                                       tenant=req.tenant)
            except Exception as e:  # noqa: BLE001 — report, never lose
                req.stream.finish(reason="error",
                                  error=f"{type(e).__name__}: {e}")
                if self._reqtrace is not None:
                    self._reqtrace.finalize(req)
                continue
            req.resume_base = req.stream.produced
            req.replica_name = self.name
            req.t_resume_submitted = time.perf_counter()
            if req.t_resume_enqueued is not None:
                req.resume_wait_ms = (req.t_resume_submitted
                                      - req.t_resume_enqueued) * 1e3
                if self._reqtrace is not None and req.ctx is not None:
                    self._reqtrace.on_resume_wait(req)
            self._streams[req.uid] = req
            self._inflight += 1
            get_metrics().counter("gateway/resumed_requests_total").inc()
        return True

    def _step(self) -> bool:
        try:
            n = self._scheduler.step()
        except Exception as e:  # noqa: BLE001 — one poisoned batch must not
            # silently wedge every queued request: fail the active streams
            # loudly and drop the driver's view of them
            get_flight_recorder().record("serving", "replica_step_error",
                                         replica=self.name, error=repr(e))
            for req in list(self._streams.values()):
                req.stream.finish(reason="error", error=f"{type(e).__name__}: {e}")
                if self._reqtrace is not None:
                    self._reqtrace.finalize(req)
            self._streams.clear()
            self._inflight = 0
            raise
        self.steps += 1
        with get_tracer().span("serving/loop_fanout", tid="serving") as sp:
            pushed, finished = self._fanout()
            if sp is not NULL_SPAN:
                sp.set_args(pushed=pushed, finished=finished)
        return n > 0

    def _fanout(self):
        """Push newly generated tokens to each request's stream; close out
        finished requests with TTFT/TPOT accounting. Reads only each
        stream's TAIL (``new_tokens``) — snapshotting ``results`` here
        would re-copy every active generation whole on every step.
        Returns ``(tokens pushed, requests finished)``."""
        finished = self._scheduler.finished
        reg = get_metrics()
        n_pushed = n_finished = 0
        for uid, req in list(self._streams.items()):
            st = req.stream
            # resume_base: tokens the stream already held when a migrated
            # request resumed HERE — this scheduler's generation restarts at
            # zero, so the stream cursor is offset by what the source made
            new = self._scheduler.new_tokens(uid, st.produced - req.resume_base)
            if new:
                pushed = st.push(new)
                n_pushed += pushed
                if pushed:
                    reg.counter("gateway/tokens_streamed_total").inc(pushed)
                    if req.ttft_ms is None and st.first_token_t is not None:
                        req.ttft_ms = (st.first_token_t - req.t_admitted) * 1e3
                        reg.histogram(f"gateway/ttft_ms_{req.slo_class}").observe(req.ttft_ms)
                        if self._reqtrace is not None and req.ctx is not None:
                            self._reqtrace.on_first_token(req, req.ttft_ms)
            if (self._disagg is not None and uid not in finished
                    and req.handoff_state is None and req.resume_base == 0
                    and req.sampling is None  # greedy-parity contract only
                    and self._disagg.wants_handoff(self)
                    and st.produced >= self._disagg.handoff_after_tokens
                    and st.produced < req.max_new_tokens):
                # prefill is proven done (first tokens exist) and decode
                # remains — migrate to the decode pool. try_handoff runs the
                # whole pipeline on THIS driver thread; True means detach
                # already removed the request from our maps.
                if self._disagg.try_handoff(self, req, st.all_tokens()):
                    req.handoff_state = "migrated"
                    continue
                # terminal fallback: decode in place, never re-attempted
                # (the ledger refused-or-failed entry pins at-most-once)
                req.handoff_state = "fallback"
            if uid in finished:  # once: the stream entry is removed with it
                self._inflight -= 1
                n_finished += 1
                del self._streams[uid]
                self._close_out(req)
                # the stream holds the full generation; dropping the
                # scheduler's copy keeps a long-lived replica's results dict
                # (and each per-step `results` snapshot) from growing with
                # every request ever served
                self._scheduler.discard_result(uid)
        return n_pushed, n_finished

    def _close_out(self, req: GatewayRequest):
        st = req.stream
        n = st.produced
        toks = st.all_tokens()
        reason = ("eos" if (req.eos_token_id is not None and toks
                            and toks[-1] == req.eos_token_id) else "length")
        if (n > 1 and st.first_token_t is not None and st.last_token_t is not None
                and st.last_token_t > st.first_token_t):
            req.tpot_ms = (st.last_token_t - st.first_token_t) / (n - 1) * 1e3
            get_metrics().histogram(f"gateway/tpot_ms_{req.slo_class}").observe(req.tpot_ms)
        cls = self.config.slo_classes.get(req.slo_class)
        if cls is not None:
            if cls.ttft_target_ms > 0 and (req.ttft_ms or 0) > cls.ttft_target_ms:
                get_metrics().counter(f"gateway/slo_ttft_miss_{req.slo_class}_total").inc()
            if cls.tpot_target_ms > 0 and (req.tpot_ms or 0) > cls.tpot_target_ms:
                get_metrics().counter(f"gateway/slo_tpot_miss_{req.slo_class}_total").inc()
        get_metrics().counter(f"gateway/completed_{req.slo_class}_total").inc()
        if self._meter is not None:
            self._meter.on_terminal(req.tenant, req.rid, req.slo_class,
                                    reason, n)
        if self._reqtrace is not None:
            # finalize BEFORE the stream latches done: the HTTP handler
            # wakes on finish and may read the request log immediately —
            # the summary record must already be durable by then.
            # spec_summary is None unless the scheduler actually speculated
            # for this request (ragged.speculative present) — the summary
            # record then carries the per-request acceptance rate
            self._reqtrace.finalize(req, finish_reason=reason, n_tokens=n,
                                    spec=self._scheduler.spec_summary(req.uid))
        st.finish(reason=reason)

    # -- introspection -------------------------------------------------------
    def state(self) -> dict:
        out = {"name": self.name, "alive": self.alive, "paused": self.paused,
               "draining": self.draining,
               "warmed": self.warmed, "role": self.role,
               "inflight": self._inflight,
               "queued": self._admission.depth(replica=self.name),
               "steps": self.steps,
               "available_blocks": self.engine.available_blocks}
        if self._scheduler.speculating:
            sp = self._scheduler.spec_stats
            out["speculative"] = dict(sp, accept_rate=round(
                sp["accepted"] / max(1, sp["drafted"]), 3),
                **(self._scheduler.spec_params() or {}))
        return out
