"""On-demand XPlane capture: the ``jax.profiler`` start/stop broker that both
engines and the gateway's ``POST /v1/profile`` ride.

One capture may be in flight per process (``jax.profiler`` is global); a
bounded-duration capture writes into a hidden temp dir and atomically
renames it into place, so a reader never sees a torn artifact and a
concurrent request gets :class:`CaptureBusyError` (HTTP 409 at the
gateway), never a corrupted trace.

What a capture is read by: the artifact's ``*.xplane.pb`` holds the device's
operations and, in its ``/host:metadata`` plane, each program's HLO with the
``jax.named_scope`` part of every instruction (``monitor/scopes.py``);
``python -m benchmark.lib.op_scopes <file.xplane.pb>`` prints the device's
busy time by part of the model. (The cost-analysis roofline plane that lived
here, PR 16, went with PR 53: it divided XLA's ``cost_analysis()`` by a host
wall clock and saw no Pallas kernel, which is most of every served model's
device time.)

Import-light by design: stdlib + sibling monitor modules only; ``jax`` is
imported lazily at capture time.
"""

import os
import threading
import time

from .metrics import get_metrics


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
