"""Wall-clock and throughput timers.

TPU-native analog of the reference ``deepspeed/utils/timer.py``
(``SynchronizedWallClockTimer`` :class via device events, ``ThroughputTimer``,
``NoopTimer``). On TPU there are no CUDA events; ``SynchronizedWallClockTimer``
fences with ``get_accelerator().synchronize()`` as the reference does, which
here waits for a trivial computation enqueued behind everything already
queued on each local device: "device work up to here is done", so a timer
around an un-fetched jitted call covers its run, not its enqueue.
``ThroughputTimer`` sits in ``train_batch``'s hot path and never waits for
the device (a wait there would serialize the asynchronous step pipeline):
it times from dispatch to dispatch, which back-pressure makes the step time
in steady state.
"""

import time

from .logging import log_dist

try:
    import psutil

    PSUTIL_AVAILABLE = True
except ImportError:  # pragma: no cover
    PSUTIL_AVAILABLE = False

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
BACKWARD_INNER_MICRO_TIMER = "bwd_inner_microstep"
BACKWARD_INNER_GLOBAL_TIMER = "bwd_inner"
BACKWARD_REDUCE_MICRO_TIMER = "bwd_allreduce_microstep"
BACKWARD_REDUCE_GLOBAL_TIMER = "bwd_allreduce"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"


def _device_sync():
    from ..accelerator import get_accelerator

    get_accelerator().synchronize()


class CudaEventTimer:  # name kept for API familiarity; this is a host timer pair
    pass


class SynchronizedWallClockTimer:
    """Group of named timers, each synchronizing device work at start/stop."""

    class Timer:

        def __init__(self, name):
            self.name_ = name
            self.started_ = False
            self.start_time = 0.0
            self.total_elapsed_ = 0.0

        def start(self):
            assert not self.started_, f"{self.name_} timer has already been started"
            _device_sync()
            self.start_time = time.time()
            self.started_ = True

        def stop(self, reset=False, record=False):
            assert self.started_, "timer is not started"
            _device_sync()
            elapsed = time.time() - self.start_time
            if reset:
                self.total_elapsed_ = elapsed
            else:
                self.total_elapsed_ += elapsed
            self.started_ = False

        def reset(self):
            self.started_ = False
            self.total_elapsed_ = 0.0

        def elapsed(self, reset=True):
            started = self.started_
            if started:
                self.stop()
            elapsed = self.total_elapsed_
            if reset:
                self.reset()
            if started:
                self.start()
            return elapsed

        def mean(self):
            return self.elapsed(reset=False)

    def __init__(self):
        self.timers = {}

    def get_timers(self):
        return self.timers

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = self.Timer(name)
        return self.timers[name]

    @staticmethod
    def memory_usage():
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
            alloc = stats.get("bytes_in_use", 0) / (1024**3)
            peak = stats.get("peak_bytes_in_use", 0) / (1024**3)
            return f"Mem in use {alloc:.2f} GB | peak {peak:.2f} GB"
        except Exception:
            return "Mem stats unavailable"

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False, ranks=None):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += " | {}: {:.2f}".format(name, elapsed_time)
        log_dist(string, ranks=ranks or [0])


class NoopTimer:

    class Timer:

        def start(self):
            ...

        def reset(self):
            ...

        def stop(self, **kwargs):
            ...

        def elapsed(self, **kwargs):
            return 0

        def mean(self):
            return 0

    def __init__(self):
        self.timer = self.Timer()

    def __call__(self, name):
        return self.timer

    def get_timers(self):
        return {}

    def log(self, names=None, normalizer=1.0, reset=True, memory_breakdown=False, ranks=None):
        ...


class ThroughputTimer:
    """Samples/sec + TFLOPS reporting, mirrors reference ``ThroughputTimer``."""

    def __init__(self, config, batch_size, start_step=2, steps_per_output=None, monitor_memory=False, logging_fn=None):
        self.config = config
        self.start_time = 0
        self.end_time = 0
        self.started = False
        self.batch_size = batch_size or 1
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0
        self.step_elapsed_time = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or log_dist
        self.initialized = False

    @property
    def enabled(self):
        return getattr(self.config, "enabled", True)

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.global_step_count >= self.start_step:
            self.start_time = time.time()

    def stop(self, global_step=False, report_speed=True):
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        if global_step:
            self.global_step_count += 1
        if self.start_time > 0:
            self.end_time = time.time()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            self.step_elapsed_time += duration

            if global_step:
                if report_speed and self.steps_per_output and self.global_step_count % self.steps_per_output == 0:
                    self.logging("epoch={}/micro_step={}/global_step={}, RunningAvgSamplesPerSec={:.2f}, "
                                 "CurrSamplesPerSec={:.2f}".format(self.epoch_count, self.micro_step_count,
                                                                   self.global_step_count, self.avg_samples_per_sec(),
                                                                   self.batch_size / self.step_elapsed_time))
                self.step_elapsed_time = 0

    def avg_samples_per_sec(self):
        if self.global_step_count > self.start_step:
            samples_per_step = self.batch_size
            total_step_offset = self.global_step_count - self.start_step
            avg_time_per_step = self.total_elapsed_time / total_step_offset
            return samples_per_step / avg_time_per_step
        return float("-inf")


def trim_mean(data, trim_percent):
    """Compute the trimmed mean of a list (reference ``utils/timer.py::trim_mean``)."""
    assert 0.0 <= trim_percent <= 1.0
    n = len(data)
    if n == 0:
        return 0
    data.sort()
    k = int(round(n * trim_percent))
    return sum(data[k:n - k]) / max(1, n - 2 * k)
