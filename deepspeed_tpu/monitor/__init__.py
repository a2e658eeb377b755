"""Observability package: metric sinks (``monitor.py``), the span/event
trace bus (``trace.py``) and the metrics registry (``metrics.py``).

Only the import-light trace/metrics surface is re-exported here:
``monitor.monitor`` imports the comm package (rank gating) and is imported
directly by its consumers (``runtime/engine.py``) to keep package bootstrap
cycle-free.
"""

from .trace import get_tracer, configure_tracer, to_chrome_trace, NULL_SPAN  # noqa: F401
from .metrics import (  # noqa: F401
    get_metrics, configure_metrics, compute_mfu, compute_mbu, peak_flops_per_chip,
    peak_hbm_bw_per_chip, CHIP_PEAK_FLOPS, CHIP_PEAK_HBM_BW,
    DEFAULT_LATENCY_BUCKETS_MS)
from .flight import get_flight_recorder, FlightRecorder  # noqa: F401
from .health import get_health, configure_health, HealthPlane  # noqa: F401
from .memory import get_memory, hbm_report, tree_device_bytes, MemoryAttribution  # noqa: F401
from .goodput import (  # noqa: F401
    get_goodput, configure_goodput, conservation_ok, GoodputLedger, GoodputPlane,
    RecompileSentinel, TRAIN_CATEGORIES, SERVING_CATEGORIES)
from .roofline import (  # noqa: F401
    get_capture_manager, CaptureBusyError, CaptureManager)
