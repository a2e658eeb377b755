"""Unified observability subsystem tests: the Chrome-trace JSONL span bus
(``monitor/trace.py``), the metrics registry + MFU table
(``monitor/metrics.py``), real comms byte/bandwidth accounting through
``@timed_op`` (``comm/comm.py``), the monitor sink fixes, and the
``tools/check_timed_ops.py`` static instrumentation gate."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm import comm as dist
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.monitor.metrics import (Histogram, MetricsRegistry, NULL_METRIC, compute_mfu, get_metrics,
                                           peak_flops_per_chip)
from deepspeed_tpu.monitor.trace import NULL_SPAN, Tracer, get_tracer, to_chrome_trace
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.mesh import MeshConfig

from conftest import tiny_batch

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

CHROME_TRACE_FIELDS = ("name", "ph", "ts", "dur", "pid", "tid")


@pytest.fixture(autouse=True)
def _reset_observability():
    """The tracer/registry are process-global: always leave them disabled so
    engines built by OTHER test files never pay the observing path."""
    get_tracer().reset()  # whatever an earlier file in this process left set
    yield
    get_tracer().reset()
    get_metrics().disable()
    get_metrics().reset()
    dist.comms_logger.enabled = False
    dist.comms_logger.reset()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# trace bus
# ---------------------------------------------------------------------------
def test_trace_jsonl_schema_roundtrip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = get_tracer().configure(enabled=True, path=path, flush_every=1)
    with tr.span("fwd", step=1):
        pass
    with tr.span("bwd", tid="engine"):
        pass
    tr.instant("marker", tid="comm", note="hello")
    tr.counter("hbm_gb", 3.5)
    tr.close()

    events = _read_jsonl(path)  # every line independently json.loads-able
    assert events, "no events written"
    durations = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in durations} >= {"fwd", "bwd"}
    for e in durations:
        for field in CHROME_TRACE_FIELDS:
            assert field in e, f"missing Chrome-trace field {field}: {e}"
        assert e["dur"] >= 0 and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # logical streams announce themselves as thread_name metadata
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)

    # strict chrome://tracing wrapper round-trips
    wrapped = to_chrome_trace(path, str(tmp_path / "trace.json"))
    assert len(json.load(open(wrapped))["traceEvents"]) == len(events)


def test_tracer_disabled_is_allocation_free():
    tr = Tracer()
    assert tr.span("a") is NULL_SPAN and tr.span("b") is NULL_SPAN  # same object
    with tr.span("x", step=3) as sp:
        assert sp is NULL_SPAN
        sp.set_args(ignored=True)
    tr.instant("nope")
    tr.counter("nope", 1)
    assert tr.drain() == []


def test_compile_events_captured(cold_compile):
    # cold_compile: a program the harness's cache hands back gives no compile event (only the seconds it saved)
    tr = get_tracer().configure(enabled=True)  # buffer-only: no path needed
    jax.jit(lambda x: x * 2 + 1)(jnp.ones((3, 5)))  # fresh shape -> real compile
    events = tr.drain()
    compiles = [e for e in events if e["name"] == "jax_compile"]
    assert compiles, f"no jax_compile events among {[e['name'] for e in events]}"
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in compiles)
    assert all("source" in e["args"] for e in compiles)


# ---------------------------------------------------------------------------
# comms accounting: real bytes, real bandwidth, full coverage
# ---------------------------------------------------------------------------
def test_comm_spans_carry_real_bytes_and_finite_bandwidth(eight_devices):
    groups.initialize_mesh(MeshConfig(data=8))
    tr = get_tracer().configure(enabled=True)
    dist.configure(enabled=True, prof_all=True)

    x = np.ones((64, 1024), np.float32)  # 256 KiB
    out = dist.all_reduce(x)  # eager: wrapped in shard_map over the mesh
    assert np.shape(out) == x.shape
    assert float(np.asarray(out)[0, 0]) == 8.0  # replicated operand summed over data=8
    dist.all_reduce(x)  # steady-state sample (first call compiled)
    dist.barrier()

    spans = [e for e in tr.drain() if e["name"] == "comm/all_reduce" and e["ph"] == "X"]
    assert len(spans) == 2, "both all_reduce calls must emit spans"
    assert spans[0]["args"].get("compiled") is True  # compile call disclosed...
    assert "compiled" not in spans[1]["args"]
    for args in (s["args"] for s in spans):
        assert args["msg_size"] == x.nbytes  # the old hardcoded 0 is gone
        assert args["n"] == 8
        assert np.isfinite(args["algbw_gbps"]) and args["algbw_gbps"] > 0
        assert np.isfinite(args["busbw_gbps"]) and args["busbw_gbps"] > 0

    # ...and kept OUT of the bandwidth stats: only the steady sample lands
    summary = dist.comms_logger.summary()
    assert summary["ops"]["all_reduce"]["count"] == 1
    assert summary["ops"]["all_reduce"]["bytes"] == x.nbytes
    assert x.nbytes in dist.comms_logger.comms_dict["all_reduce"]


def test_traced_collectives_record_size_at_trace_time(eight_devices):
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.parallel.mesh import shard_map_compat

    mesh = groups.initialize_mesh(MeshConfig(data=8))
    tr = get_tracer().configure(enabled=True)
    fn = jax.jit(shard_map_compat(lambda t: dist.all_reduce(t), mesh, P(), P()))
    fn(jnp.ones((16, 4), jnp.float32))
    instants = [e for e in tr.drain() if e["name"] == "comm/all_reduce" and e["ph"] == "i"]
    assert instants, "traced collective did not record an instant event"
    assert instants[0]["args"]["msg_size"] == 16 * 4 * 4
    assert instants[0]["args"]["traced"] is True


def test_every_public_collective_is_instrumented():
    from tools.check_timed_ops import PUBLIC_COLLECTIVES, check

    missing = check()
    assert missing == [], f"collectives missing @timed_op: {missing}"
    assert len(PUBLIC_COLLECTIVES) >= 10  # the pre-fix state instrumented exactly 1


def test_calc_bw_log_uses_real_group_degree():
    from deepspeed_tpu.utils.comms_logging import calc_bw_log

    size, dur = 1 << 20, 1e-3
    alg2, bus2, _ = calc_bw_log("all_reduce", size, dur, n=2)
    alg8, bus8, _ = calc_bw_log("all_reduce", size, dur, n=8)
    assert alg2 == alg8  # algbw is size-derived
    assert bus2 < bus8  # busbw scales with 2(n-1)/n
    legacy = calc_bw_log("all_reduce", size, dur)  # no n: legacy placeholder
    assert legacy == calc_bw_log("all_reduce", size, dur, n=8)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_histogram_percentiles_exact_on_known_sequence():
    h = Histogram("lat_ms")
    for v in range(1, 101):  # 1..100
        h.observe(float(v))
    assert h.percentile(50) == 50.0
    assert h.percentile(90) == 90.0
    assert h.percentile(99) == 99.0
    assert h.percentile(100) == 100.0
    assert h.percentile(0) == 1.0
    assert h.count == 100 and sum(h.bucket_counts) == 100
    assert h.mean() == pytest.approx(50.5)
    s = h.summary()
    assert s == {"count": 100, "mean": pytest.approx(50.5), "p50": 50.0, "p90": 90.0, "p99": 99.0}


def test_registry_disabled_is_noop_same_object():
    reg = MetricsRegistry(enabled=False)
    assert reg.counter("a") is reg.counter("b") is NULL_METRIC
    assert reg.gauge("g") is NULL_METRIC and reg.histogram("h") is NULL_METRIC
    NULL_METRIC.inc()
    NULL_METRIC.set(3)
    NULL_METRIC.observe(1.0)
    assert reg.events(step=0) == []
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    reg.enable()
    c = reg.counter("a")
    assert c is not NULL_METRIC
    c.inc(2)
    assert ("a", 2.0, 7) in reg.events(7)


def test_mfu_table_and_math():
    assert peak_flops_per_chip("TPU v4") == 275e12
    assert peak_flops_per_chip("TPU v5 lite") == 197e12
    assert peak_flops_per_chip("TPU v5p") == 459e12
    assert peak_flops_per_chip("cpu") is None
    assert compute_mfu(1e12, 1.0, n_chips=1, peak_flops=2e12) == pytest.approx(0.5)
    assert compute_mfu(1e12, 0.5, n_chips=4, peak_flops=1e12) == pytest.approx(0.5)
    assert compute_mfu(1e12, 1.0, peak_flops=None) is None or isinstance(
        compute_mfu(1e12, 1.0, peak_flops=None), float)  # None off-TPU, float on-TPU


def test_training_flops_per_token():
    from deepspeed_tpu.profiling.flops_profiler import training_flops_per_token

    assert training_flops_per_token(1e9) == 6e9
    with_attn = training_flops_per_token(1e9, num_layers=4, hidden_size=256, seq_len=128)
    assert with_attn == 6e9 + 12 * 4 * 256 * 128


# ---------------------------------------------------------------------------
# monitor sinks
# ---------------------------------------------------------------------------
def _csv_config(tmp_path, enabled=True):
    from deepspeed_tpu.monitor.config import CSVConfig

    return CSVConfig(enabled=enabled, output_path=str(tmp_path), job_name="job")


def test_trace_block_presence_enables():
    from deepspeed_tpu.monitor.config import get_monitor_config

    assert not get_monitor_config({}).trace.enabled  # absent -> off
    assert get_monitor_config({"trace": {}}).trace.enabled  # empty block -> on, defaults
    cfg = get_monitor_config({"trace": {"output_path": "/tmp/x.jsonl"}})
    assert cfg.trace.enabled and cfg.trace.output_path == "/tmp/x.jsonl"
    assert not get_monitor_config({"trace": {"enabled": False,
                                             "output_path": "/tmp/x.jsonl"}}).trace.enabled


def test_timed_op_positional_group_degree(eight_devices):
    from deepspeed_tpu.comm.comm import ReduceOp

    groups.initialize_mesh(MeshConfig(data=4, model=2))
    tr = get_tracer().configure(enabled=True)
    x = np.ones((8, 8), np.float32)
    dist.all_reduce(x, ReduceOp.SUM, "model")  # group passed POSITIONALLY
    spans = [e for e in tr.drain() if e["name"] == "comm/all_reduce" and e["ph"] == "X"]
    assert spans and spans[-1]["args"]["n"] == 2  # model-axis degree, not data's 4


def test_tracer_truncates_stale_artifact(tmp_path):
    path = str(tmp_path / "stale.jsonl")
    with open(path, "w") as f:
        f.write("NOT JSON — stale run leftovers\n")
    tr = Tracer()  # fresh instance: first open of the path in "this process"
    tr.configure(enabled=True, path=path, flush_every=1)
    with tr.span("fresh"):
        pass
    tr.close()
    lines = [l for l in open(path) if l.strip()]
    assert all(json.loads(l) for l in lines)  # stale junk truncated away
    assert any(json.loads(l)["name"] == "fresh" for l in lines)


def test_eager_collective_accepts_keyword_tensor(eight_devices):
    groups.initialize_mesh(MeshConfig(data=8))
    out = dist.all_reduce(tensor=np.ones((4, 4), np.float32))
    assert float(np.asarray(out)[0, 0]) == 8.0


def test_csv_monitor_persistent_handles(tmp_path):
    from deepspeed_tpu.monitor.monitor import csvMonitor

    mon = csvMonitor(_csv_config(tmp_path))
    assert mon.enabled
    mon.write_events([("Train/loss", 1.0, 1), ("Train/lr", 0.1, 1)])
    mon.write_events([("Train/loss", 0.5, 2)])
    mon.flush()
    assert len(mon._files) == 2  # one persistent handle per metric, not per event
    loss_csv = os.path.join(str(tmp_path), "job", "Train_loss.csv")
    lines = open(loss_csv).read().strip().splitlines()
    assert lines[0].startswith("step") and len(lines) == 3
    mon.close()
    assert mon._files == {}


def test_monitor_master_rank_gates_to_zero(tmp_path, monkeypatch):
    import deepspeed_tpu.monitor.monitor as mm
    from deepspeed_tpu.monitor.config import get_monitor_config

    cfg = get_monitor_config({"csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                                              "job_name": "gated"}})
    monkeypatch.setattr(mm, "get_rank", lambda group=None: 1)
    master = mm.MonitorMaster(cfg)
    assert master.csv_monitor is None  # non-zero rank builds no sinks
    master.write_events([("x", 1.0, 0)])  # and writes nothing
    assert not os.path.exists(os.path.join(str(tmp_path), "gated"))

    monkeypatch.setattr(mm, "get_rank", lambda group=None: 0)
    master0 = mm.MonitorMaster(cfg)
    assert master0.csv_monitor is not None and master0.enabled
    master0.write_events([("x", 1.0, 0)])
    master0.flush()
    assert os.path.exists(os.path.join(str(tmp_path), "gated", "x.csv"))


def test_tensorboard_monitor_warns_instead_of_silent_disable(monkeypatch):
    import deepspeed_tpu.monitor.monitor as mm
    from deepspeed_tpu.monitor.config import TensorBoardConfig

    def _boom():
        raise ImportError("neither 'tensorboardX' nor 'torch.utils.tensorboard' is installed")

    warnings = []
    monkeypatch.setattr(mm, "_import_summary_writer", _boom)
    monkeypatch.setattr(mm.logger, "warning", lambda msg, *a, **k: warnings.append(str(msg)))
    mon = mm.TensorBoardMonitor(TensorBoardConfig(enabled=True, output_path="/tmp/tb"))
    assert not mon.enabled
    assert any("tensorboardX" in w for w in warnings), \
        "missing-dependency warning must NAME the missing package"


# ---------------------------------------------------------------------------
# engine end-to-end: config-gated trace + derived throughput/MFU
# ---------------------------------------------------------------------------
def _tiny_engine(extra_cfg):
    model = TransformerLM(TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                                            num_heads=4, max_seq_len=64, intermediate_size=128,
                                            attention_impl="reference", dtype=jnp.float32))
    cfg = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "tpu": {"mesh": {"data": 8}},
        "steps_per_print": 1,
    }
    cfg.update(extra_cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    return engine


def test_engine_trace_block_emits_spans_and_mfu(tmp_path, eight_devices):
    path = str(tmp_path / "engine_trace.jsonl")
    engine = _tiny_engine({"trace": {"output_path": path}})  # presence-enables
    assert engine.config.monitor_config.trace.enabled
    engine.train_batch(tiny_batch(batch_size=16, seq=32))
    # eager 3-call path: fwd/bwd/step phase spans
    loss = engine.forward(tiny_batch(batch_size=16, seq=32))
    engine.backward(loss)
    engine.step()
    get_tracer().close()

    events = _read_jsonl(path)
    names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"train_batch", "fwd", "bwd", "step"} <= names, f"missing spans: {names}"
    tb = next(e for e in events if e["name"] == "train_batch")
    assert tb["args"]["tokens"] == 16 * 32

    reg = get_metrics()
    snap = reg.snapshot()
    assert snap["gauges"]["train/tokens_per_sec"] > 0
    assert snap["counters"]["train/tokens"] == 16 * 32
    assert snap["histograms"]["train/step_time_ms"]["count"] == 1
    # CPU: unknown chip -> no MFU gauge rather than a made-up one
    assert "train/mfu" not in snap["gauges"]
    # registry events drain in MonitorMaster shape
    evs = reg.events(step=1)
    assert all(len(t) == 3 for t in evs) and any(n == "train/tokens_per_sec" for n, _, _ in evs)


def test_engine_without_trace_block_is_zero_overhead(eight_devices):
    engine = _tiny_engine({})
    assert not engine.config.monitor_config.trace.enabled
    assert not get_tracer().enabled and not get_metrics().enabled
    engine.train_batch(tiny_batch(batch_size=16, seq=32))
    assert get_tracer().drain() == []  # nothing buffered, nothing written
    assert get_metrics().snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    assert get_tracer().span("x") is NULL_SPAN  # the step loop's only touch point


# ---------------------------------------------------------------------------
# serving latency histograms (v2 ragged engine)
# ---------------------------------------------------------------------------
def test_serving_ttft_and_decode_histograms(eight_devices):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig

    groups.reset()
    get_tracer().configure(enabled=True)
    get_metrics().enable()

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                            intermediate_size=128, max_seq_len=128, dtype=jnp.float32,
                            attention_impl="reference")
    icfg = RaggedInferenceEngineConfig()
    icfg.kv_block_size = 32
    icfg.num_kv_blocks = 16
    icfg.state_manager.max_tracked_sequences = 2
    icfg.state_manager.max_ragged_sequence_count = 2
    icfg.state_manager.max_ragged_batch_size = 64
    icfg.state_manager.max_context = 96
    eng = InferenceEngineV2(TransformerLM(cfg), icfg)

    prompt = np.arange(16, dtype=np.int32) % cfg.vocab_size
    first = eng.put([0], [prompt], sample="greedy")  # prefill -> one prefill-step sample
    eng.decode([0], [np.asarray([int(first[0])], np.int32)], n_steps=2)

    snap = get_metrics().snapshot()
    assert snap["histograms"]["serving/prefill_step_ms"]["count"] == 1
    assert snap["histograms"]["serving/prefill_step_ms"]["p50"] > 0
    assert "serving/ttft_ms" not in snap["histograms"]  # a step's latency is not a time to first token
    assert snap["histograms"]["serving/decode_ms"]["count"] == 1
    names = {e["name"] for e in get_tracer().drain()}
    assert {"serving/prefill", "serving/decode"} <= names

    # block=False measures dispatch only — span emitted, NO latency sample
    eng.put([0], [np.asarray([1], np.int32)], block=False)
    snap = get_metrics().snapshot()
    assert "serving/decode_step_ms" not in snap["histograms"]
    evs = get_tracer().drain()
    unblocked = [e for e in evs if e["name"] == "serving/decode_step"]
    assert unblocked and unblocked[0]["args"]["blocked"] is False
