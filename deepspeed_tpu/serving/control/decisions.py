"""First-class decision log for the serving control plane.

Every actuation the controller applies — and every proposal it defers
past the flap budget — becomes ONE structured decision record carrying
the sensor readings that justified it. The record fans out to every
forensic surface the repo already has:

  * a bounded, atomically-rotated JSONL file (the reqtrace
    ``RequestLog`` chassis — the log can never grow unbounded);
  * an in-memory ring (``GET /v1/control`` + the health plane's stall
    dump provider read it without touching the file);
  * ``control/*`` Prometheus counters (the controller is audited
    through these);
  * a ``control/decision`` tracer instant + flight-recorder breadcrumb
    (the decision lands in the same timeline as the requests it
    affected).

The emit method is deliberately named ``emit`` (not ``record``/``write``)
so ``tools/check_control_actuators.py`` can gate on the literal call name
without colliding with the registry/flight-recorder verbs.
"""

import threading
import time
from collections import deque
from typing import Optional

from ...monitor.flight import get_flight_recorder
from ...monitor.metrics import get_metrics
from ...monitor.trace import get_tracer
from ..reqtrace import RequestLog

__all__ = ["DecisionLog"]


class DecisionLog:
    """Bounded JSONL + in-memory ring of controller decisions."""

    def __init__(self, config):
        self.config = config
        self._lock = threading.Lock()
        self._ring = deque(maxlen=max(1, int(config.last_n)))
        self._log: Optional[RequestLog] = None
        if config.decision_log_path:
            self._log = RequestLog(config.decision_log_path,
                                   max_bytes=config.decision_log_max_bytes,
                                   max_files=config.decision_log_max_files)
        self.stats = {"applied": 0, "deferred": 0}

    def emit(self, policy: str, action: str, applied: bool, reason: str,
             sensors: dict, **fields) -> dict:
        """Log one decision. ``applied=False`` = the proposal was DEFERRED
        (flap budget / cooldown) — it still gets a full record, because an
        un-applied decision is exactly what a flapping-loop post-mortem
        needs to see. Returns the record."""
        rec = {"t": round(time.time(), 3), "policy": str(policy),
               "action": str(action), "applied": bool(applied),
               "reason": str(reason), "sensors": dict(sensors or {}), **fields}
        reg = get_metrics()
        if applied:
            reg.counter("control/actuations_total").inc()
            reg.counter(f"control/actuations_{policy}_total").inc()
        else:
            reg.counter("control/deferred_total").inc()
        with self._lock:
            self.stats["applied" if applied else "deferred"] += 1
            self._ring.append(rec)
            if self._log is not None:
                self._log.write(rec)
        # request_id=None: a controller decision is fleet-scoped, not
        # request-scoped (the sensors dict names the classes/replicas it
        # read) — the keyword is still required by check_request_tracing
        get_tracer().instant("control/decision", tid="serving",
                             request_id=None,
                             policy=rec["policy"], action=rec["action"],
                             applied=rec["applied"], reason=rec["reason"])
        get_flight_recorder().record("control", rec["action"],
                                     policy=rec["policy"],
                                     applied=rec["applied"],
                                     reason=rec["reason"])
        return rec

    def recent(self, n: Optional[int] = None):
        """Newest-last decision records from the in-memory ring."""
        with self._lock:
            rows = list(self._ring)
        return rows if n is None else rows[-int(n):]

    def state(self) -> dict:
        with self._lock:
            return {"path": self.config.decision_log_path or None,
                    "ring": len(self._ring), **self.stats}

    def close(self) -> None:
        with self._lock:
            if self._log is not None:
                self._log.close()
