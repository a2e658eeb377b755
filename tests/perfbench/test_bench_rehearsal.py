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


def _run(args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("BENCH_RUN", None)
    return subprocess.run(RUN + args, cwd=loader.ROOT, env=env, capture_output=True, text=True, timeout=900)


def _rehearsal_cells():
    return loader._read_json(os.path.join(loader.ROOT, loader.REHEARSALS))["workloads"]


def test_rehearsal_cells_are_not_benchmark_cells_and_cover_every_one():
    manifest = loader.load_manifest()
    tiny = _rehearsal_cells()
    assert not {w["name"] for w in tiny} & {w["name"] for w in manifest["workloads"]}
    assert not {w["config"] for w in tiny} & {c["name"] for c in manifest["configs"]}
    drivers = lambda cells: sorted((loader._read_json(os.path.join(
        loader.ROOT, "benchmark", "traffic", w["traffic"] + ".json"))["driver"], w["chips"]) for w in cells)
    assert drivers(tiny) == drivers(manifest["workloads"])


@pytest.mark.parametrize("cell", _rehearsal_cells(), ids=lambda w: w["name"])
def test_each_cells_command_rehearsed_on_the_cpu(cell):
    p = _run(["--workload", cell["name"], "--seed", str(2**31 + 7), "--seconds", "2", "--trace", "0",
              "--rehearsal"], devices=cell["chips"])
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
