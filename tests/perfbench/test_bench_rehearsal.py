"""Each cell's command rehearsed on the CPU at a tiny configuration that is
not in ``workloads`` (four virtual devices for the ZeRO-3 cell): the line must
name the CPU as the device and carry no metric, only counts; and the real path
must exit non-zero, printing no result, when there is no TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import loader

RUN = [sys.executable, os.path.join(loader.ROOT, "benchmark", "run.py")]


def _run(args, devices=1, cache_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("BENCH_RUN", None)
    if cache_dir is not None:  # not the checkout's own cache: another worker's test watches that directory
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return subprocess.run(RUN + args, cwd=loader.ROOT, env=env, capture_output=True, text=True, timeout=900)


def test_rehearsal_cells_are_not_benchmark_cells_and_cover_every_one():
    manifest = loader.load_manifest()
    tiny = loader.rehearsal_cells()
    assert not {w["name"] for w in tiny} & {w["name"] for w in manifest["workloads"]}
    assert not {w["config"] for w in tiny} & {c["name"] for c in manifest["configs"]}
    drivers = lambda cells: sorted((loader._read_json(os.path.join(
        loader.ROOT, "benchmark", "traffic", w["traffic"] + ".json"))["driver"], w["chips"]) for w in cells)
    assert drivers(tiny) == drivers(manifest["workloads"])


@pytest.mark.parametrize("cell", loader.rehearsal_cells(), ids=lambda w: w["name"])
def test_each_cells_command_rehearsed_on_the_cpu(cell, tmp_path):
    p = _run(["--workload", cell["name"], "--seed", str(2**31 + 7), "--seconds", "2", "--trace", "0",
              "--rehearsal"], devices=cell["chips"], cache_dir=tmp_path)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == cell["chips"]
    assert line["metrics"] == {} and line["rehearsal"] is True  # no number under a device metric's name
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["counts"]["compiles_in_window"] == 0 and line["counts"]["steps_in_window"] >= 1


def test_the_real_path_exits_non_zero_without_a_tpu():
    cell = loader.load_manifest()["workloads"][0]["name"]
    p = _run(["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_a_rehearsal_off_the_cpu_environment_is_refused():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    p = subprocess.run(RUN + ["--workload", "tiny-neox.pretrain", "--seed", "1", "--seconds", "1", "--rehearsal"],
                       cwd=loader.ROOT, env=dict(env, JAX_PLATFORMS="tpu"), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 2 and p.stdout.strip() == ""


def test_the_closed_loops_trace_ends_under_load_before_the_drain(monkeypatch):
    """The traced window of a closed loop is ``trace_seconds`` of the full load; the drain, in which
    the rows empty one by one for as long as the longest answer lasts, lies behind it (on the chip a
    6 s trace of 32 clients with answers of up to 1,016 tokens had read 37 s, five sixths of it drain)."""
    import argparse
    import time

    from benchmark.lib import common

    class FakeTracer:
        def __init__(self, root, cell_name):
            pass

        def start(self):
            pass

        def stop_and_reduce(self):
            return {"stopped_at": time.perf_counter()}

    monkeypatch.setattr(common, "Tracer", FakeTracer)
    cell = loader.resolve_cell("tiny-mistral.decode-heavy", rehearsal=True)
    args = argparse.Namespace(seed=5, seconds=1.0, trace=1, rehearsal=True)
    result = loader.load_module("drivers", cell["traffic_file"]["driver"]).run(cell, args, time.perf_counter())
    ctx = result["ctx"]
    t0, t1 = ctx["trace_window"]
    assert result["correct"] and result["failed"] == 0
    assert cell["traffic_file"]["trace_seconds"] <= t1 - t0 < cell["traffic_file"]["trace_seconds"] + 0.5
    assert t1 <= ctx["reduced"]["stopped_at"] < max(step["t1"] for step in ctx["system"].steps)
