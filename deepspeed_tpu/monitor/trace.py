"""Process-local span/event bus — Chrome-trace/Perfetto JSONL emission.

The observability spine the reference spreads over ``@timed_op`` wrappers,
the flops profiler and the torch profiler hooks, unified here into one bus:

  * ``get_tracer().span("fwd")`` — THE span primitive, a context manager
    with two sinks. While a profiler session is active
    (``jax.profiler.TraceAnnotation.is_enabled()``: the benchmark's
    ``--trace 1``, the engine's ``_maybe_device_trace``, an operator's
    ``jax.profiler.start_trace``) it is a ``TraceAnnotation`` named
    ``dstpu/<name>`` whose keyword arguments become the event's stats, so
    the program's spans lie in the same ``.xplane.pb`` as the device's
    operations, on the profiler's clock, under the thread that ran them.
    While the bus is enabled it is a Chrome-trace duration event
    (``ph:"X"``) under ``<name>`` with ``pid`` = this host process and
    ``tid`` = a logical stream (engine / comm / compile / checkpoint /
    serving / data). The ``process_name`` metadata event carries
    ``origin_unix_ns`` and ``origin_perf_counter``, so a JSONL file can be
    laid over an xplane file after the fact.
  * ``complete``/``instant``/``counter`` — manual emission for call sites
    that cannot use a ``with`` block (async dispatch, listener callbacks).
  * JAX compile/recompile events are captured through
    ``jax.monitoring.register_event_duration_secs_listener`` and emitted as
    ``jax_compile`` duration events on the ``compile`` stream.

Output is JSONL: one Chrome-trace event object per line, each independently
``json.loads``-able. The
``trace_viewer`` JSON-array form for chrome://tracing or Perfetto is one
``to_chrome_trace`` call away.

Zero overhead when disabled: with the bus off, no mirror and no profiler
session ``span()`` returns a shared no-op singleton (``NULL_SPAN``) after one
attribute check and one ``is_enabled()``; every other emitter early-returns
on one attribute check, and the compile listener is only installed on first
enable. Arguments that cost anything to compute are set with
``if sp is not NULL_SPAN: sp.set_args(...)``, never built before the check.
Retroactive emission (``complete``, ``observe_latency``) cannot reach the
profiler: it stays for what is retroactive by nature (the compile listener,
per-request stages).

Every live span closes with two arguments of its own, in both sinks:
``wall_us``, its time on the host's clock, and ``offcpu_us``, the part of it
in which its thread was not running (wall less ``time.thread_time_ns()``). In
a span that blocks on purpose that is the wait; in one that only computes it
is time the interpreter lock or the machine's scheduler gave to someone else.
Where the machine keeps a thread's clock continuously that is never below 0.
Where the clock ticks (gVisor adds 10 ms to the thread that is running when
its tick comes), a span shorter than a tick reads its wall, or its wall less
a whole tick, and only a sum over many spans says anything: so no span's
reading is cut off at 0, which would take the ticks out of the sum.

This module must stay import-light (no package-internal imports): it is
pulled in by ``comm.comm`` during package bootstrap.
"""

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

# the profiler-side name of every span: the benchmark's readers find the
# program's spans in an xplane file by this prefix
PROFILER_PREFIX = "dstpu/"

# canonical logical streams -> stable Chrome-trace tid numbers
STREAMS = ("engine", "comm", "compile", "checkpoint", "serving", "data")


class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_args(self, **kwargs):
        return self


NULL_SPAN = _NullSpan()


class _Span:
    """One open span: ``_ann`` is its profiler annotation (None without a
    profiler session), ``_bus`` says whether the JSONL bus / mirror gets the
    event when it closes."""

    __slots__ = ("_tracer", "_name", "_tid", "_args", "_t0", "_cpu0", "_ann", "_bus")

    def __init__(self, tracer, name, tid, args, ann, bus):
        self._tracer = tracer
        self._name = name
        self._tid = tid
        self._args = args
        self._ann = ann
        self._bus = bus

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        self._cpu0 = time.thread_time_ns()
        return self

    def set_args(self, **kwargs):
        if self._bus:
            self._args.update(kwargs)
        if self._ann is not None:
            self._ann.set_metadata(**kwargs)
        return self

    def __exit__(self, *exc):
        cpu_us = (time.thread_time_ns() - self._cpu0) * 1e-3  # read inside the wall interval: never more than it
        t1 = time.perf_counter()
        wall_us = (t1 - self._t0) * 1e6
        self.set_args(wall_us=round(wall_us, 3), offcpu_us=round(wall_us - cpu_us, 3))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._bus:
            self._tracer.complete(self._name, self._t0, t1 - self._t0, tid=self._tid, args=self._args)
        return False


class Tracer:
    """Buffered JSONL trace writer. One per process (see ``get_tracer``)."""

    def __init__(self):
        self.enabled = False
        self._path = None
        self._fh = None
        self._buf = []
        self._flush_every = 256
        self._lock = threading.RLock()
        self._origin = time.perf_counter()  # ts epoch: trace times are relative
        self._pid = None
        self._tids = {}
        self._opened_paths = set()  # paths truncated once this process
        # event mirror (the health plane's flight recorder): when set, every
        # emitted event is also handed to mirror.record_event — INCLUDING in
        # "tracing disabled" mode, where the spans exist only for the mirror
        self._mirror = None
        self._atexit_installed = False

    # -- configuration --------------------------------------------------
    def configure(self, enabled=None, path=None, flush_every=None, config=None):
        """Enable/point the tracer. ``config`` may be a ``TraceConfig`` block
        (``monitor_config.trace``); explicit kwargs win over it."""
        if config is not None:
            if enabled is None:
                enabled = getattr(config, "enabled", None)
            if path is None:
                path = getattr(config, "output_path", None) or None
            if flush_every is None:
                flush_every = getattr(config, "flush_every", None)
        with self._lock:
            if path is not None and path != self._path:
                self._close_fh()
                self._path = path
            if flush_every is not None:
                self._flush_every = max(1, int(flush_every))
            if enabled is not None:
                enabled = bool(enabled)
                if enabled and not self.enabled:
                    self._pid = _process_id()
                    _install_compile_listener()
                    self._install_atexit()
                    self.enabled = True
                    # the two origins are one instant on two clocks: ts 0 of
                    # this file on the unix clock an xplane file is stamped in
                    lag = time.perf_counter() - self._origin
                    self._emit({"name": "process_name", "ph": "M", "ts": 0, "pid": self._pid,
                                "tid": 0, "args": {"name": "deepspeed_tpu",
                                                   "origin_unix_ns": time.time_ns() - int(lag * 1e9),
                                                   "origin_perf_counter": self._origin}})
                    # re-announce streams first seen in mirror-only mode:
                    # their thread_name metadata went to the flight ring,
                    # never to the buffer/file — without this, a trace
                    # enabled AFTER the health plane armed the mirror has
                    # tids no viewer can name
                    for stream, tid in sorted(self._tids.items()):
                        self._emit({"name": "thread_name", "ph": "M", "ts": 0,
                                    "pid": self._pid, "tid": tid,
                                    "args": {"name": stream}})
                elif not enabled and self.enabled:
                    self.flush()
                    self.enabled = False
        return self

    def set_mirror(self, mirror):
        """Install/remove the event mirror (``record_event(ev)`` duck type —
        the health plane's flight recorder). With a mirror installed the
        emitters run even while ``enabled`` is False, feeding the mirror
        only: nothing is buffered or written to the trace path."""
        with self._lock:
            if mirror is not None and self._pid is None:
                self._pid = _process_id()
            self._mirror = mirror
        return self

    def _install_atexit(self):
        """Flush/close at interpreter exit: without this, an abrupt
        ``sys.exit`` (preemption runners do exactly that) truncates the tail
        ``flush_every`` window of the JSONL artifact mid-run. Registered
        once per tracer, on first enable; ``close()`` is idempotent so an
        orderly ``drain()``/``close()`` beforehand costs nothing."""
        if self._atexit_installed:
            return
        import atexit

        atexit.register(self.close)
        self._atexit_installed = True

    # -- emission -------------------------------------------------------
    def span(self, name, tid="engine", **args):
        """Context manager for a duration event, to the profiler while a
        session is active and to the bus while it is enabled (or mirrored).
        Allocation-free no-op (the shared ``NULL_SPAN`` object) otherwise."""
        bus = self.enabled or self._mirror is not None
        if TraceAnnotation.is_enabled():
            return _Span(self, name, tid, args, TraceAnnotation(PROFILER_PREFIX + name, **args), bus)
        if not bus:
            return NULL_SPAN
        return _Span(self, name, tid, args, None, True)

    def complete(self, name, t0, duration, tid="engine", args=None):
        """Emit a ``ph:"X"`` duration event. ``t0`` is a ``time.perf_counter``
        reading; ``duration`` is in seconds."""
        if not self.enabled and self._mirror is None:
            return
        ev = {"name": name, "ph": "X", "ts": round((t0 - self._origin) * 1e6, 3),
              "dur": round(duration * 1e6, 3), "pid": self._pid, "tid": self._tid(tid)}
        if args:
            ev["args"] = args
        self._emit(ev)

    def instant(self, name, tid="engine", **args):
        if not self.enabled and self._mirror is None:
            return
        ev = {"name": name, "ph": "i", "s": "t", "ts": self._now_us(), "dur": 0,
              "pid": self._pid, "tid": self._tid(tid)}
        if args:
            ev["args"] = args
        self._emit(ev)

    def counter(self, name, value, tid="engine"):
        if not self.enabled and self._mirror is None:
            return
        self._emit({"name": name, "ph": "C", "ts": self._now_us(), "dur": 0, "pid": self._pid,
                    "tid": self._tid(tid), "args": {"value": float(value)}})

    # -- plumbing -------------------------------------------------------
    def _now_us(self):
        return round((time.perf_counter() - self._origin) * 1e6, 3)

    def _tid(self, stream):
        # under the (reentrant) lock: the jax compile listener can fire from
        # a background thread concurrently with engine-thread spans
        with self._lock:
            tid = self._tids.get(stream)
            if tid is None:
                tid = STREAMS.index(stream) + 1 if stream in STREAMS else len(STREAMS) + 1 + len(self._tids)
                self._tids[stream] = tid
                self._emit({"name": "thread_name", "ph": "M", "ts": 0, "pid": self._pid, "tid": tid,
                            "args": {"name": stream}})
            return tid

    def _emit(self, ev):
        m = self._mirror
        if m is not None:
            m.record_event(ev)
        if not self.enabled:
            return  # mirror-only mode: nothing buffered, nothing written
        with self._lock:
            self._buf.append(ev)
            if self._path is None:
                # buffer-only mode: trim lazily at 2x the cap so the per-event
                # cost stays amortized O(1) instead of an O(cap) slice each time
                if len(self._buf) > 2 * self.MAX_BUFFERED:
                    del self._buf[:len(self._buf) - self.MAX_BUFFERED]
            elif len(self._buf) >= self._flush_every:
                self._flush_locked()

    def flush(self):
        with self._lock:
            self._flush_locked()

    # pathless-tracer memory bound: keep at most this many buffered events
    # (drain()/a later path picks them up; beyond it, oldest are dropped)
    MAX_BUFFERED = 65536

    def _flush_locked(self):
        if not self._buf:
            return
        events, self._buf = self._buf, []
        if self._path is None:
            if len(events) > self.MAX_BUFFERED:
                events = events[len(events) - self.MAX_BUFFERED:]
            self._buf = events  # nowhere to write yet; keep for a later path
            return
        if self._fh is None:
            d = os.path.dirname(os.path.abspath(self._path))
            if d:
                os.makedirs(d, exist_ok=True)
            # truncate on this process's FIRST open of a path: a stale trace
            # from a previous run would interleave near ts=0 (ts is relative
            # to each process's clock origin) and corrupt the artifact;
            # within-process reopen (flush/close cycles) appends
            mode = "a" if self._path in self._opened_paths else "w"
            self._opened_paths.add(self._path)
            self._fh = open(self._path, mode)
        for ev in events:
            self._fh.write(json.dumps(ev) + "\n")
        self._fh.flush()

    def _close_fh(self):
        if self._fh is not None:
            try:
                self._fh.close()
            finally:
                self._fh = None

    def close(self):
        with self._lock:
            self._flush_locked()
            self._close_fh()

    def reset(self):
        """Back to the state of a fresh process: closed, disabled, and no
        path, mirror or buffered event left. The tracer is a process
        singleton, so whatever one user of it (a test, a benchmark run) sets
        outlives that user unless it ends with this."""
        with self._lock:
            self.close()
            self.enabled = False
            self._path = None
            self._mirror = None
            self._buf = []
            self._tids = {}
            self._opened_paths = set()
            self._flush_every = 256
        return self

    def drain(self):
        """Return (and clear) the buffered, not-yet-written events — the
        in-memory read path for tests and programmatic consumers."""
        with self._lock:
            events, self._buf = self._buf, []
        return events


def _process_id():
    """pid for trace events: the jax process index when distributed is up
    (stable across hosts of one job), else the OS pid."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return os.getpid()


# ---------------------------------------------------------------------------
# module singleton + compile-event capture
# ---------------------------------------------------------------------------
_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer


def configure_tracer(config=None, **kwargs) -> Tracer:
    return _tracer.configure(config=config, **kwargs)


_COMPILE_LISTENER = {"installed": False}

# compile-source routing: XLA compiles happen synchronously on the thread
# that triggered them, so a THREAD-LOCAL source label attributes each
# compile event to the engine that compiled — a serving replica thread's
# bucket compile must not count under train/ (the pre-PR-14 drift). The
# default (no scope pushed) stays "train", the historical behavior.
_COMPILE_SOURCES = ("train", "serving")
_compile_tls = threading.local()

# subscribers: fn(source, event_name, duration_s) per compile event — the
# goodput plane books training compile seconds through this. Zero overhead
# while empty (one truthiness check per event).
_compile_subscribers = []


def push_compile_source(source):
    """Set this thread's compile-source label; returns the previous value
    for :func:`pop_compile_source` (nestable)."""
    if source not in _COMPILE_SOURCES:
        source = "train"
    prev = getattr(_compile_tls, "source", None)
    _compile_tls.source = source
    return prev


def pop_compile_source(prev):
    _compile_tls.source = prev


def current_compile_source():
    return getattr(_compile_tls, "source", None) or "train"


def add_compile_listener(fn):
    """Subscribe ``fn(source, event_name, duration_s)`` to compile events."""
    if fn not in _compile_subscribers:
        _compile_subscribers.append(fn)
    _install_compile_listener()


def remove_compile_listener(fn):
    try:
        _compile_subscribers.remove(fn)
    except ValueError:
        pass


def _install_compile_listener():
    """Capture XLA compile/lower durations as ``jax_compile`` trace events and
    ``<source>/compile_*`` metrics. Installed once, fires only while tracing/
    metrics are enabled or a subscriber is registered (one attribute check
    per event otherwise)."""
    if _COMPILE_LISTENER["installed"]:
        return
    try:
        import jax.monitoring as jmon

        def _on_event_duration(event, duration, **kwargs):
            if "compile" not in event and "lower" not in event:
                return
            source = current_compile_source()
            tr = _tracer
            if tr.enabled:
                now = time.perf_counter()
                tr.complete("jax_compile", now - duration, duration, tid="compile",
                            args={"source": event, "engine": source})
            from .metrics import get_metrics

            reg = get_metrics()
            if reg.enabled:
                # <source>/ namespace per tools/check_metric_names.py (the
                # old compile/* names predated the approved prefix set; the
                # old always-train/ attribution predated serving engines
                # compiling from replica threads). Names assembled outside
                # the registration call: this module is gate-allowlisted
                # for dynamic names it validates itself (_COMPILE_SOURCES).
                ev_name = source + "/compile_events"
                sec_name = source + "/compile_seconds"
                reg.counter(ev_name).inc()
                reg.counter(sec_name).inc(duration)
            if _compile_subscribers:
                for fn in list(_compile_subscribers):
                    try:
                        fn(source, event, duration)
                    except Exception:  # noqa: BLE001 — telemetry never raises
                        pass

        jmon.register_event_duration_secs_listener(_on_event_duration)
        _COMPILE_LISTENER["installed"] = True
    except Exception:  # tracing must never break program startup
        pass


def observe_latency(t0, span_name, hist_name=None, tid="serving", span_args=None, gauges=None):
    """Shared tail for instrumented latency call sites: optional histogram
    observation (milliseconds), optional gauge sets, and one trace span.
    ``gauges`` maps name -> value or callable(dt_seconds). Callers guard with
    their own enabled check; returns dt in seconds."""
    dt = time.perf_counter() - t0
    from .metrics import get_metrics

    reg = get_metrics()
    if reg.enabled:
        if hist_name:
            reg.histogram(hist_name).observe(dt * 1e3)
        for gname, gval in (gauges or {}).items():
            reg.gauge(gname).set(gval(dt) if callable(gval) else gval)
    if _tracer.enabled or _tracer._mirror is not None:
        _tracer.complete(span_name, t0, dt, tid=tid, args=span_args or {})
    return dt


def to_chrome_trace(jsonl_path, out_path):
    """Wrap a JSONL trace into the strict ``{"traceEvents": [...]}`` JSON the
    chrome://tracing legacy loader expects (Perfetto loads either form)."""
    events = []
    with open(jsonl_path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return out_path
