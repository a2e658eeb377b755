"""Roofline attribution plane + on-demand XPlane capture manager.

Five sensor planes account for every wall-clock second, HBM byte, and
tenant-owned resource (PRs 1, 5, 11, 14, 15) — none of them can say whether
the chip is running *as fast as the hardware allows*. This module joins what
XLA says a compiled executable must do (``compiled.cost_analysis()`` FLOPs
and bytes accessed — the exact mechanism ``profiling/flops_profiler.py``
uses point-wise) with what we measure it doing (the engine step boundary,
the serving forward wrappers), per shape
bucket — the same bucket labels the PR 14 recompile sentinel tracks — and
renders a per-bucket verdict:

  * ``compute_bound``   — the FLOP roof binds (arithmetic intensity above
    the ridge point) and measured wall is near that roof;
  * ``bandwidth_bound`` — the HBM-bytes roof binds and measured wall is
    near it (a bandwidth-bound decode is what justifies the disaggregated
    fleet, ROADMAP 1);
  * ``overhead_bound``  — measured wall exceeds ``overhead_factor`` x the
    cost-model roof: the executable is near NEITHER roof, the gap is host
    dispatch / launch overhead, and the bucket is a re-tuner nominee
    (ROADMAP 5c);
  * ``unknown``         — cost, wall, or peaks are missing; every missing
    input is disclosed as null, never guessed (the VERDICT r4 trap: a CPU
    fallback must not price itself against a TPU roof).

Cost capture is LAZY: a compile site hands the plane its freshly-jitted
callable via :meth:`RooflinePlane.capture_executable`; the returned wrapper
records the abstract ``ShapeDtypeStruct`` signature of the FIRST real call
and the plane re-lowers (``fn.lower(*abstract).compile().cost_analysis()``)
only at report time — the serving hot path pays one flag check + one
Python-call forward per step while armed, and nothing at all when the
``monitor.roofline`` block is absent (no wrappers are ever installed; the
zero-overhead-absent contract of the trace/health/goodput planes,
test-enforced).

Second half: :class:`CaptureManager` — the shared ``jax.profiler``
start/stop broker both engines and the gateway's ``POST /v1/profile`` ride.
One capture may be in flight per process (``jax.profiler`` is global); a
bounded-duration capture writes into a hidden temp dir and atomically
renames it into place, so a reader never sees a torn artifact and a
concurrent request gets :class:`CaptureBusyError` (HTTP 409 at the
gateway), never a corrupted trace.

Import-light by design: stdlib + sibling monitor modules only; ``jax`` is
imported lazily at capture/lowering time.
"""

import os
import threading
import time

from .metrics import (compute_mbu, compute_mfu, get_metrics,
                      peak_flops_per_chip, peak_hbm_bw_per_chip)

VERDICTS = ("compute_bound", "bandwidth_bound", "overhead_bound", "unknown")


class CaptureBusyError(RuntimeError):
    """A jax.profiler capture is already in flight (one per process)."""


# ---------------------------------------------------------------------------
# on-demand XPlane capture
# ---------------------------------------------------------------------------
class CaptureManager:
    """Process-global ``jax.profiler.start_trace``/``stop_trace`` broker.

    Two modes share one in-flight flag (the profiler is process-global, so
    a training capture and a gateway capture must exclude each other):

      * manual ``start(dir)`` / ``stop()`` — the engine's
        ``tpu.profiler_trace`` step-window capture;
      * bounded :meth:`capture` — start, sleep ``duration_s``, drain, stop,
        then atomically rename the temp dir into the artifact root (the
        ``write_snapshot`` tmp+rename discipline, directory-shaped).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._busy = False
        self._n = 0

    @property
    def in_flight(self):
        return self._busy

    def _acquire(self):
        with self._lock:
            if self._busy:
                return False
            self._busy = True
            return True

    def _release(self):
        with self._lock:
            self._busy = False

    def start(self, trace_dir):
        """Begin a manual capture into ``trace_dir``. Returns False (no
        trace started) when a capture is already in flight."""
        if not self._acquire():
            return False
        try:
            import jax

            jax.profiler.start_trace(trace_dir)
        except Exception:
            self._release()
            raise
        return True

    def stop(self, drain=None):
        """End the manual capture: run ``drain()`` (flush in-flight device
        work so the trace holds whole steps), then ``stop_trace`` — which
        is what writes the artifact. stop_trace always runs, even when the
        drain raises (a partial trace beats a wedged profiler)."""
        if not self._busy:
            return
        import jax

        try:
            if drain is not None:
                drain()
        finally:
            try:
                jax.profiler.stop_trace()
            finally:
                self._release()

    def capture(self, duration_s, out_root, label="capture", max_s=60.0,
                drain=None):
        """One bounded capture: trace live traffic for ``duration_s``
        (clamped to ``max_s``) and return the final artifact directory.
        Raises :class:`CaptureBusyError` when a capture is in flight.

        Atomicity: the profiler writes into ``out_root/.tmp-...``; only a
        COMPLETE capture is renamed to its final name, so any visible
        ``label-*`` directory is a whole, loadable XPlane artifact."""
        duration_s = min(float(duration_s), float(max_s))
        if duration_s <= 0:
            raise ValueError(f"capture duration must be > 0, got {duration_s}")
        if not self._acquire():
            raise CaptureBusyError("a profiler capture is already in flight")
        try:
            import jax

            os.makedirs(out_root, exist_ok=True)
            with self._lock:
                self._n += 1
                n = self._n
            final = os.path.join(out_root, f"{label}-{os.getpid()}-{n:03d}")
            tmp = os.path.join(out_root, f".tmp-{label}-{os.getpid()}-{n:03d}")
            jax.profiler.start_trace(tmp)
            try:
                time.sleep(duration_s)
                if drain is not None:
                    drain()
            finally:
                jax.profiler.stop_trace()
            os.replace(tmp, final)
            get_metrics().counter("profile/captures_total").inc()
            return final
        finally:
            self._release()


_capture = None
_capture_lock = threading.Lock()


def get_capture_manager() -> CaptureManager:
    """The process capture broker (created on first use — a process that
    never profiles never allocates one)."""
    global _capture
    if _capture is None:
        with _capture_lock:
            if _capture is None:
                _capture = CaptureManager()
    return _capture


# ---------------------------------------------------------------------------
# executable-cost registry
# ---------------------------------------------------------------------------
def _abstract_signature(args):
    """Concrete call args -> ShapeDtypeStruct pytree (shardings preserved,
    so a sharded train step re-lowers under the same placement)."""
    import jax

    def one(x):
        if isinstance(x, jax.Array):
            try:
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            except Exception:
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x  # python scalars etc. stay literal

    return jax.tree_util.tree_map(one, args)


def cost_analysis_dict(compiled):
    """``compiled.cost_analysis()`` normalized to ONE flat dict — older jax
    wraps the result in a single-element list. The shared extraction used
    here and by ``profiling/flops_profiler.py``, so every cost consumer in
    the repo reads the same keys."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    return dict(cost or {})


def _cost_of(fn, abstract_args, mesh=None):
    """``fn.lower(*abstract).compile().cost_analysis()`` with every failure
    mode disclosed instead of raised: a backend without cost analysis, a
    lowering that needs a live mesh, a list-wrapped result (older jax) —
    the row reports null flops/bytes plus the error string, never crashes
    (the CPU-fallback contract)."""
    try:
        import contextlib

        cm = mesh if mesh is not None else contextlib.nullcontext()
        with cm:
            compiled = fn.lower(*abstract_args).compile()
        cost = cost_analysis_dict(compiled)
        flops = cost.get("flops")
        bytes_accessed = cost.get("bytes accessed")
        return {"flops": float(flops) if flops is not None else None,
                "bytes": float(bytes_accessed) if bytes_accessed is not None else None}
    except Exception as e:  # noqa: BLE001 — telemetry never kills runs
        return {"flops": None, "bytes": None,
                "error": f"{type(e).__name__}: {str(e)[:160]}"}


class _CapturedExecutable:
    """Transparent wrapper a compile site installs over its jitted callable
    while the plane is armed: the FIRST call snapshots the abstract arg
    signature into the registry; every call forwards. Attribute access
    (``.lower`` for the AOT paths) delegates to the wrapped callable."""

    __slots__ = ("_fn", "_registry", "_bucket", "_mesh", "_seen")

    def __init__(self, fn, registry, bucket, mesh=None):
        self._fn = fn
        self._registry = registry
        self._bucket = bucket
        self._mesh = mesh
        self._seen = False

    def __call__(self, *args):
        if not self._seen:
            self._seen = True
            try:
                self._registry.register_lazy(
                    self._bucket, self._fn, _abstract_signature(args),
                    mesh=self._mesh)
            except Exception:  # noqa: BLE001 — capture must never cost a step
                pass
        return self._fn(*args)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class ExecutableCostRegistry:
    """Per-bucket cost + measured-wall store. Buckets are the recompile
    sentinel's labels (``train_step``, ``put/t{t}/s{s}/...``,
    ``decode/s{s}/n{n}``, ``verify/...``, ``pallas/{kernel}/{bucket}``), so
    the sentinel, the goodput ledger, and the roofline rows all speak the
    same key space."""

    def __init__(self):
        self._lock = threading.Lock()
        # bucket -> {"thunk": callable|None, "cost": dict|None,
        #            "wall_s": float, "calls": int, "last_wall_s": float}
        self._rows = {}

    def _row(self, bucket):
        row = self._rows.get(bucket)
        if row is None:
            row = self._rows[bucket] = {"thunk": None, "cost": None,
                                        "wall_s": 0.0, "calls": 0,
                                        "last_wall_s": 0.0}
        return row

    def register_lazy(self, bucket, fn, abstract_args, mesh=None):
        """Record a cost THUNK for ``bucket``: evaluated once, at report
        time (re-lowering is off the serving hot path by design)."""
        with self._lock:
            row = self._row(bucket)
            if row["thunk"] is None and row["cost"] is None:
                row["thunk"] = lambda: _cost_of(fn, abstract_args, mesh=mesh)

    def register_cost(self, bucket, cost):
        """Record an already-computed cost dict (``{"flops":…, "bytes":…}``)
        for ``bucket`` — the autotuner/tools entry."""
        with self._lock:
            self._row(bucket)["cost"] = dict(cost)

    def note_wall(self, bucket, seconds):
        """One measured wall sample for ``bucket`` (host-observed, through
        the blocking fetch — the same window the goodput ledger books)."""
        with self._lock:
            row = self._row(bucket)
            row["wall_s"] += float(seconds)
            row["calls"] += 1
            row["last_wall_s"] = float(seconds)

    def cost(self, bucket):
        """The (possibly lazily-evaluated) cost dict for ``bucket``, or
        None when the bucket was never registered."""
        with self._lock:
            row = self._rows.get(bucket)
            thunk = row["thunk"] if row is not None else None
        if row is None:
            return None
        if row["cost"] is None and thunk is not None:
            cost = thunk()  # outside the lock: lowering can be slow
            with self._lock:
                if row["cost"] is None:
                    row["cost"] = cost
                    row["thunk"] = None
        return row["cost"]

    def buckets(self):
        with self._lock:
            return sorted(self._rows)

    def snapshot(self):
        """[(bucket, cost_or_None, wall_s, calls)] — costs forced."""
        out = []
        for b in self.buckets():
            cost = self.cost(b)
            with self._lock:
                row = self._rows[b]
                out.append((b, cost, row["wall_s"], row["calls"]))
        return out


# ---------------------------------------------------------------------------
# the plane
# ---------------------------------------------------------------------------
class RooflinePlane:
    """Process-global roofline state (see :func:`get_roofline`): the cost
    registry, the verdict math, and the export wiring (health-plane
    gauge/state/dump providers). Everything defaults OFF with the
    zero-overhead-absent contract: no registry object, no wrappers, no
    threads, one ``enabled`` check per hook."""

    def __init__(self):
        self.enabled = False
        self.overhead_factor = 2.0
        self.peak_flops = None   # None = per-chip table (null on CPU)
        self.peak_hbm_bw = None
        self.capture_dir = "/tmp/dstpu_xplane"
        self.max_capture_s = 60.0
        self._registry = None
        self._gauge_fn = None   # bound-method refs cached at configure time
        self._report_fn = None  # (the health clears are identity-checked)

    # -- configuration --------------------------------------------------
    def configure(self, config=None, **kwargs):
        """Arm the plane. ``config`` is a ``RooflineConfig`` block
        (``monitor_config.roofline``); explicit kwargs win over it."""

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
        self.overhead_factor = float(knob("overhead_factor", self.overhead_factor))
        self.peak_flops = knob("peak_flops", self.peak_flops)
        self.peak_hbm_bw = knob("peak_hbm_bw", self.peak_hbm_bw)
        self.capture_dir = str(knob("capture_dir", self.capture_dir))
        self.max_capture_s = float(knob("max_capture_s", self.max_capture_s))
        if self._registry is None:
            self._registry = ExecutableCostRegistry()
        # the verdict gauges are served through the metrics registry +
        # health providers — the roofline block implies metrics, like
        # `trace`/`health`/`goodput` do
        get_metrics().enable()
        # (re-)registered on EVERY arm: HealthPlane.shutdown() clears all
        # providers (the goodput plane's rollover lesson)
        from .health import get_health

        hp = get_health()
        if self._gauge_fn is None:
            self._gauge_fn = self.gauge_rows
            self._report_fn = self.report
        hp.set_gauge_provider("roofline", self._gauge_fn)
        hp.set_state_provider("roofline", self._report_fn)
        hp.set_dump_provider("roofline", self._report_fn)
        self.enabled = True
        return self

    def shutdown(self):
        """Disarm, drop the registry, and reset every knob to its default
        (a later bare re-arm must not inherit a previous run's peak
        overrides). Idempotent."""
        if self.enabled:
            from .health import get_health

            hp = get_health()
            hp.clear_gauge_provider("roofline", self._gauge_fn)
            hp.clear_state_provider("roofline", self._report_fn)
            hp.clear_dump_provider("roofline", self._report_fn)
        self.enabled = False
        self._registry = None
        self.overhead_factor = 2.0
        self.peak_flops = None
        self.peak_hbm_bw = None
        self.capture_dir = "/tmp/dstpu_xplane"
        self.max_capture_s = 60.0
        return self

    # -- capture hooks (compile sites / measurement points) ---------------
    def capture_executable(self, bucket, fn, mesh=None):
        """Wrap a freshly-jitted callable so its first call registers the
        bucket's cost signature. Called at the compiled-cache-miss sites
        (the same places that feed the recompile sentinel); callers only
        invoke it while ``enabled`` — disabled returns ``fn`` untouched."""
        if not self.enabled or self._registry is None:
            return fn
        return _CapturedExecutable(fn, self._registry, bucket, mesh=mesh)

    def note_wall(self, bucket, seconds):
        if not self.enabled or self._registry is None:
            return
        self._registry.note_wall(bucket, seconds)

    def register_fn(self, bucket, fn, *example_args, mesh=None):
        """Tools entry: register ``bucket``'s cost from a jit-wrapped
        callable + example (or abstract) args."""
        if not self.enabled or self._registry is None:
            return
        self._registry.register_lazy(bucket, fn,
                                     _abstract_signature(tuple(example_args)),
                                     mesh=mesh)

    def register_thunk(self, bucket, thunk):
        """Autotuner entry: register cost from a no-arg measurement thunk
        (closed-over operands become lowering constants — good enough for a
        kernel's flop/byte totals)."""
        if not self.enabled or self._registry is None:
            return
        import jax

        self._registry.register_lazy(bucket, jax.jit(thunk), ())

    # -- verdict math ----------------------------------------------------
    def peaks(self):
        """(peak_flops, peak_hbm_bw) — config overrides first, then the
        per-chip tables; (None, None) on an unknown chip with no override."""
        pf = self.peak_flops if self.peak_flops else peak_flops_per_chip()
        pb = self.peak_hbm_bw if self.peak_hbm_bw else peak_hbm_bw_per_chip()
        return pf, pb

    def verdict_row(self, cost, wall_s, calls):
        """One bucket's joined row: achieved rates, MFU + MBU, the roofline
        verdict, and the gap to the roof — every unknowable field null."""
        pf, pb = self.peaks()
        flops = (cost or {}).get("flops")
        bts = (cost or {}).get("bytes")
        mean = wall_s / calls if calls else None
        row = {"flops": flops, "bytes": bts,
               "wall_s": round(wall_s, 6), "calls": calls,
               "mean_wall_s": round(mean, 6) if mean else None,
               "achieved_flops_per_s": (round(flops / mean, 3)
                                        if flops is not None and mean else None),
               "achieved_hbm_bytes_per_s": (round(bts / mean, 3)
                                            if bts is not None and mean else None),
               "mfu": None, "mbu": None,
               "verdict": "unknown", "roof_s": None, "gap_to_roof": None}
        if (cost or {}).get("error"):
            row["cost_error"] = cost["error"]
        if mean:
            mfu = compute_mfu(flops, mean, peak_flops=pf) if flops is not None else None
            mbu = compute_mbu(bts, mean, peak_bw=pb) if bts is not None else None
            row["mfu"] = round(mfu, 4) if mfu is not None else None
            row["mbu"] = round(mbu, 4) if mbu is not None else None
        # the verdict needs BOTH roofs priced: a one-sided roof could call a
        # bandwidth-bound kernel compute_bound simply because the bandwidth
        # roof was unknowable (disclose, don't guess)
        if (mean and flops is not None and bts is not None
                and pf is not None and pb is not None):
            t_flops = flops / pf
            t_bytes = bts / pb
            roof = max(t_flops, t_bytes)
            row["roof_s"] = round(roof, 9)
            row["gap_to_roof"] = round(mean / roof, 3) if roof > 0 else None
            if roof <= 0:
                pass  # degenerate cost model: stays "unknown"
            elif mean > self.overhead_factor * roof:
                row["verdict"] = "overhead_bound"
            elif t_flops >= t_bytes:
                row["verdict"] = "compute_bound"
            else:
                row["verdict"] = "bandwidth_bound"
        return row

    # -- export ----------------------------------------------------------
    def report(self):
        """The full forensic/healthz section: priced peaks + one joined row
        per bucket (cost thunks forced here, off the hot path)."""
        pf, pb = self.peaks()
        out = {"enabled": self.enabled,
               "peak_flops": pf, "peak_hbm_bw": pb,
               "overhead_factor": self.overhead_factor,
               "buckets": {}}
        if self._registry is None:
            return out
        for bucket, cost, wall_s, calls in self._registry.snapshot():
            out["buckets"][bucket] = self.verdict_row(cost, wall_s, calls)
        return out

    def gauge_rows(self):
        """Labelled rows for /metrics: ``profile/roofline_mfu{bucket=…}`` +
        ``profile/roofline_mbu{bucket=…}`` (only buckets whose utilization
        is knowable — a null never renders as 0.0)."""
        rows = []
        for bucket, row in self.report()["buckets"].items():
            if row["mfu"] is not None:
                rows.append(("profile/roofline_mfu", {"bucket": bucket}, row["mfu"]))
            if row["mbu"] is not None:
                rows.append(("profile/roofline_mbu", {"bucket": bucket}, row["mbu"]))
        return rows


_plane = RooflinePlane()


def get_roofline() -> RooflinePlane:
    return _plane


def configure_roofline(config=None, **kwargs) -> RooflinePlane:
    return _plane.configure(config=config, **kwargs)
