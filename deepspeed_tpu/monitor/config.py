"""Monitor config (reference ``deepspeed/monitor/config.py``) + the
TPU-native ``trace`` block gating the span/metrics bus (``monitor/trace.py``)."""

from typing import Optional

from pydantic import Field, model_validator

from ..runtime.config_utils import DeepSpeedConfigModel


def get_monitor_config(param_dict):
    monitor_dict = {key: param_dict.get(key, {})
                    for key in ("tensorboard", "wandb", "csv_monitor", "comet", "trace",
                                "health", "goodput")}
    # presence-enables: an EMPTY {"trace": {}} / {"health": {}} block in the
    # config means "on with defaults" (the validator can only see set
    # fields, not presence)
    for key in ("trace", "health", "goodput"):
        if key in param_dict and not monitor_dict[key]:
            monitor_dict[key] = {"enabled": True}
    return DeepSpeedMonitorConfig(**monitor_dict)


class TensorBoardConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class WandbConfig(DeepSpeedConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed"


class CSVConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class CometConfig(DeepSpeedConfigModel):
    enabled: bool = False
    samples_log_interval: int = 100
    project: Optional[str] = None
    workspace: Optional[str] = None
    api_key: Optional[str] = None
    experiment_name: Optional[str] = None
    experiment_key: Optional[str] = None
    online: Optional[bool] = None
    mode: Optional[str] = None


class TraceConfig(DeepSpeedConfigModel):
    """``monitor.trace`` block — the Chrome-trace/Perfetto JSONL span bus and
    metrics registry (``monitor/trace.py`` / ``monitor/metrics.py``). Enabled
    by presence (same contract as ``tpu.profiler_trace``): configuring any
    field turns it on unless ``enabled`` is set explicitly. Off by default —
    the step loop then makes zero trace-related allocations."""
    enabled: bool = False
    output_path: str = "/tmp/dstpu_trace.jsonl"
    flush_every: int = Field(256, ge=1)

    @model_validator(mode="after")
    def enable_when_configured(self):
        if self.model_fields_set and "enabled" not in self.model_fields_set:
            self.enabled = True
        return self


class HealthConfig(DeepSpeedConfigModel):
    """``monitor.health`` block — the live-health plane (``monitor/health.py``
    / ``monitor/flight.py`` / ``monitor/export.py``): flight recorder, stall
    watchdog, straggler detection, and the Prometheus/JSON exporter. Enabled
    by presence (same contract as ``trace``); off by default, and every
    deadline defaults to 0 (= that source unwatched), so enabling the block
    alone arms only the flight recorder + heartbeat bookkeeping — no
    watchdog thread, no server, no behavior change to the step loop beyond
    one boolean check."""
    enabled: bool = False
    # flight recorder ring capacity (events retained for stall/exit dumps)
    flight_capacity: int = Field(4096, ge=16)
    # quarantine directory for watchdog-trip / SIGQUIT / destroy() dumps
    dump_dir: str = "/tmp/dstpu_health"
    dump_on_destroy: bool = True
    # install a SIGQUIT handler that writes a dump (faulthandler-style
    # kill -QUIT forensics); main-thread only
    sigquit_dump: bool = False
    watchdog_poll_s: float = Field(1.0, gt=0)
    # per-source stall deadlines, seconds; 0 = unwatched. The watchdog
    # thread only starts when at least one is > 0.
    deadline_train_step_s: float = Field(0.0, ge=0)
    deadline_collective_s: float = Field(0.0, ge=0)
    deadline_serving_s: float = Field(0.0, ge=0)
    deadline_saver_s: float = Field(0.0, ge=0)
    deadline_prefetch_s: float = Field(0.0, ge=0)
    # straggler trace instants fire past this skew; the skew gauge itself is
    # recorded whenever the engine's resilience vote carries the samples
    straggler_threshold_ms: float = Field(0.0, ge=0)
    # None = no HTTP server; 0 = ephemeral port; N = fixed port
    export_port: Optional[int] = Field(None, ge=0)
    export_host: str = "127.0.0.1"
    # scrape-less mode: atomically rewrite this JSON file every N steps
    snapshot_path: str = ""
    snapshot_every_steps: int = Field(50, ge=1)

    @model_validator(mode="after")
    def enable_when_configured(self):
        if self.model_fields_set and "enabled" not in self.model_fields_set:
            self.enabled = True
        return self


class GoodputConfig(DeepSpeedConfigModel):
    """``monitor.goodput`` block — the wall-clock attribution ledger +
    recompile sentinel (``monitor/goodput.py``). Enabled by presence (same
    contract as ``trace``/``health``); off by default — the step loop and
    the serving driver then pay one ``is not None`` check each, with no
    ledger objects, no threads, no per-step allocations."""
    enabled: bool = False
    # training warmup boundary: jax compiles during the first N steps are
    # expected; every compile after is flagged by the sentinel
    train_warmup_steps: int = Field(2, ge=0)
    # a step/driver-loop gap at least this long books as stall[ed] (the
    # same wedges the PR 5 watchdog dumps; shorter gaps stay in the
    # compute residual / unattributed)
    stall_gap_s: float = Field(0.05, gt=0)
    # compile-storm detection: K unexpected compiles inside the window
    # raise a `compile_storm` trace instant + counter (once per burst)
    storm_k: int = Field(5, ge=2)
    storm_window_s: float = Field(10.0, gt=0)

    @model_validator(mode="after")
    def enable_when_configured(self):
        if self.model_fields_set and "enabled" not in self.model_fields_set:
            self.enabled = True
        return self


class DeepSpeedMonitorConfig(DeepSpeedConfigModel):
    tensorboard: TensorBoardConfig = {}
    wandb: WandbConfig = {}
    csv_monitor: CSVConfig = {}
    comet: CometConfig = {}
    trace: TraceConfig = {}
    health: HealthConfig = {}
    goodput: GoodputConfig = {}

    @property
    def enabled(self):
        """Sink fan-out gate (rank-0 write_events). The trace bus is gated
        separately by ``trace.enabled`` — it has its own writer."""
        return self.tensorboard.enabled or self.wandb.enabled or self.csv_monitor.enabled or self.comet.enabled
