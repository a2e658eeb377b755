"""What every driver needs around a run: the device and its memory, compile
events, the benchmark's own spans, the profiler, the result line."""

import glob
import json
import os
import shutil
import time
from contextlib import contextmanager

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Stamps of every program XLA compiled or read back from the persistent
    cache in this process. One inside the measured window means that a shape
    was not warmed."""

    def __init__(self):
        import jax.monitoring

        self.stamps = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.stamps.append(time.perf_counter())

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.stamps if t0 <= t <= t1)


def require_devices(chips: int, rehearsal: bool):
    """The devices the cell runs on. Off a rehearsal the platform must be a TPU
    with at least ``chips`` chips and published peaks; a rehearsal must be
    on the CPU, so that no rehearsal number can pass for a device number."""
    import jax

    from .peaks import peaks_for

    devices = jax.devices()
    platform = devices[0].platform
    if rehearsal:
        if platform != "cpu":
            raise SystemExit(f"benchmark: --rehearsal runs on the CPU only, found {platform}")
    else:
        if platform != "tpu":
            raise SystemExit(f"benchmark: no TPU: JAX found {len(devices)} x {platform} "
                             f"({devices[0].device_kind}); JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")
        peaks_for(devices[0].device_kind)
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def device_record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


@contextmanager
def span(name: str, **kwargs):
    """One of the benchmark's own spans around a call into a layer of the
    program: a ``TraceAnnotation``, so that it lies on the device trace's
    clock. Costs nothing measurable while no trace is being taken."""
    import jax.profiler

    with jax.profiler.TraceAnnotation("bench/" + name, **kwargs):
        yield


class Tracer:
    """A profiler trace of a short window into a fixed directory under the
    checkout, reduced by ``lib/xplane`` when it stops."""

    def __init__(self, root: str, cell_name: str):
        self.dir = os.path.join(root, ".bench_trace", cell_name)

    def start(self):
        import jax.profiler

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the benchmark's spans are TraceMe events, not Python frames
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop_and_reduce(self) -> dict:
        import jax.profiler

        from . import xplane

        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if len(paths) != 1:
            raise RuntimeError(f"expected one xplane file under {self.dir}, found {paths}")
        return xplane.reduce_trace(xplane.read_trace(paths[0]))


class Phases:
    """Seconds since the process started at which each phase of set-up ended;
    printed beside the result so that a longer set-up says where it grew."""

    def __init__(self, t_process_start: float):
        self.t0, self.marks = t_process_start, {}

    def mark(self, name: str):
        self.marks[name] = round(time.perf_counter() - self.t0, 3)


def begin_run(cell: dict, args, t_process_start: float):
    """What every driver does first: find the cell's devices, start counting
    compiles, and build the system under test with the configuration's
    builder. Returns ``(phases, devices, compiles, system)``."""
    from . import loader

    phases = Phases(t_process_start)
    devices = require_devices(cell["chips"], args.rehearsal)
    phases.mark("devices")
    compiles = CompileLog()
    builder = loader.load_module("builders", cell["config_file"]["builder"], cell["root"])
    system = builder.build(cell, args.seed, devices, args.rehearsal, phases)
    phases.mark("system")
    return phases, devices, compiles, system


def result_line(correct: bool, attempted: int, failed: int, metrics: dict, device: dict, **extra) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device}
    out.update(extra)
    return json.dumps(out)
