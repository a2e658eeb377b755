"""What PR 35 added to the benchmark: four per-layer metrics of the serving
engine's host side, each a file and a manifest entry on a reader that was
there. Three split the device's idle time inside the engine's call by the
span that held it (``span_gap_share``): the fetch, the launch (batch and
dispatch), and ``serving/engine_observe``, under which the program does what
it does only because its spans are live. The fourth is the share of the
driver thread's host work in which the thread was not running
(``span_arg_ratio`` over the ``offcpu_us`` and ``wall_us`` every live span
closes with). A program from before them reads as nothing, and every step of
a rehearsed twin observes itself under the new span."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import loader, program_spans, xplane, xplane_write

MS = 1_000_000  # ns
NAMES = ["host_gap_fetch_share.tput", "host_gap_launch_share.tput", "host_gap_observe_share.tput",
         "driver_offcpu_share.tput"]
CELLS = ["mistral-7b.decode-heavy", "mellum2-12b-a2.5b.decode-heavy", "trinity-large-preview.decode-heavy-64",
         "sdar-30b-a3b-chat.block-diffusion-64"]
# the four cells whose metric lists tests/perfbench/test_bench_loader.py pins
PINNED = ["pythia-410m.pretrain", "mistral-7b.longprompt", "pythia-1.4b.zero3-x4", "mistral-7b.chat"]
LEAVES = ["serving/engine_batch", "serving/engine_dispatch", "serving/engine_commit", "serving/loop_pull",
          "serving/loop_fanout"]


def _metric(name):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "layer_metrics", name + ".json"))


def _read(ctx, name):
    metric = _metric(name)
    return loader.load_module("readers", metric["reader"]).read({**ctx, "args": metric.get("args", {})})


def _write(tmp_path, driver, cell="cell"):
    """A 100 ms window of one chip, busy but for 10-20, 30-36, 40-43, 50-54,
    60-62 and 70-75 ms, and the driver thread's spans."""
    busy = [(0, 10), (20, 30), (36, 40), (43, 50), (54, 60), (62, 70), (75, 100)]
    planes = {"/device:TPU:0": {"XLA Ops": [(f"%fusion.{i} = f32[] fusion()", a * MS, (b - a) * MS)
                                            for i, (a, b) in enumerate(busy)]},
              "/host:CPU": {"driver": driver, "bench": [("bench/decode", 5 * MS, 60 * MS)]}}
    d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xplane_write.encode_xspace(planes))
    return {"reduced": xplane.reduce_trace(xplane.read_trace(str(d / "host.xplane.pb"))),
            "cell": {"root": str(tmp_path), "name": cell}}


def _driver(observe=True, clocks=True):
    """Two decode calls. The first: the device idles 10-20 ms under the fetch
    (the span runs 8-20), 30-36 under batch (30-33) and dispatch (33-36),
    40-43 under ``engine_observe``, 50-54 under the commit. The second call's
    span covers 60-62 itself, with no child there. 70-75: under no span. With
    ``clocks`` every span closes with its two clock arguments; a parent's
    trace has neither them nor (``observe``) the new span."""
    said = lambda wall, off: f"#wall_us={wall},offcpu_us={off}#" if clocks else ""
    spans = [
        ("dstpu/serving/loop_pull" + said(1000.0, 250.0), 1 * MS, 1 * MS),
        ("dstpu/serving/loop_fanout" + said(1000.0, 0.0), 2 * MS, 1 * MS),
        ("dstpu/serving/decode#rows=3,steps=8#", 3 * MS, 52 * MS),
        ("dstpu/serving/engine_dispatch" + said(3000.0, 150.0), 4 * MS, 3 * MS),
        ("dstpu/serving/engine_fetch" + said(12000.0, 11900.0), 8 * MS, 12 * MS),
        ("dstpu/serving/engine_batch" + said(3000.0, 600.0), 30 * MS, 3 * MS),
        ("dstpu/serving/engine_dispatch" + said(3000.0, 0.0), 33 * MS, 3 * MS),
        ("dstpu/serving/engine_commit" + said(5000.0, 1000.0), 49 * MS, 5 * MS),
        ("dstpu/serving/decode#rows=3,steps=8#", 59 * MS, 4 * MS),
        ("dstpu/serving/loop_idle" + said(2000.0, 1990.0), 80 * MS, 2 * MS),
    ]
    if observe:
        spans.append(("dstpu/serving/engine_observe" + said(4000.0, 100.0), 39 * MS, 5 * MS))
    return spans


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_is_a_file_and_an_entry_appended_for_the_four_cells_and_no_pinned_one(name):
    manifest = loader.load_manifest()
    assert [m["name"] for m in manifest["per_layer"][-4:]] == NAMES, "appended at the end, in the issue's order"
    (entry, ) = [m for m in manifest["per_layer"] if m["name"] == name]
    metric = _metric(name)
    said = ("name", "unit", "better", "source", "layer", "moves")
    assert {k: entry[k] for k in said} == {k: metric[k] for k in said}
    assert (entry["layer"], entry["moves"], entry["better"], entry["unit"]) == (
        "Serving engine", "serve_tokens_per_s", "lower", "%")
    assert entry["workloads"] == CELLS and not set(CELLS) & set(PINNED)
    assert metric["reader"] == ("span_arg_ratio" if name.startswith("driver") else "span_gap_share")
    assert entry["source"] == ("program_counter" if name.startswith("driver") else "device_trace")
    for cell in manifest["workloads"]:
        resolved = loader.resolve_cell(cell["name"])
        assert (name in {m["name"] for m in resolved["layer_metrics"]}) == (cell["name"] in CELLS)
        if cell["name"] in CELLS:
            assert entry["moves"] in {m["name"] for m in resolved["end_to_end"]}


@pytest.mark.parametrize("name,want", [("host_gap_fetch_share.tput", 10.0), ("host_gap_launch_share.tput", 6.0),
                                       ("host_gap_observe_share.tput", 3.0)])
def test_each_share_is_the_idle_time_under_its_spans_over_the_window(tmp_path, name, want):
    assert _read(_write(tmp_path, _driver()), name) == pytest.approx(want)


def test_the_shares_the_no_work_share_and_the_uncovered_rest_add_up_to_the_idle_share(tmp_path):
    """``idle_no_work + host_gap_sched + host_gap_engine + host_gap_observe +
    idle under no span = device_idle_share``; the fetch and the launch are
    parts of the engine's share, whose rest is the commit and the step span's
    self time."""
    ctx = _write(tmp_path, _driver())
    fetch, launch, observe = (_read(ctx, n) for n in NAMES[:3])
    engine, sched = _read(ctx, "host_gap_engine_share.tput"), _read(ctx, "host_gap_sched_share.tput")
    no_work = _read(ctx, "idle_no_work_share.tail")
    uncovered = loader.load_module("readers", "span_gap_share").read(
        {**ctx, "args": {"spans": [program_spans.UNCOVERED]}})
    assert (engine, sched, no_work, uncovered) == pytest.approx((22.0, 0.0, 0.0, 5.0))
    assert engine - fetch - launch == pytest.approx(4.0 + 2.0), "the commit's 4 ms, the second call's own 2 ms"
    assert no_work + sched + engine + observe + uncovered == pytest.approx(_read(ctx, "device_idle_share.tput"))
    assert _read(ctx, "device_idle_share.tput") == pytest.approx(30.0)


def test_the_off_cpu_share_is_over_the_leaf_spans_that_never_block_on_purpose(tmp_path):
    metric = _metric("driver_offcpu_share.tput")
    for side in ("numerator", "denominator"):
        assert [t["span"] for t in metric["args"][side]] == LEAVES
        assert {tuple(t["product"]) for t in metric["args"][side]} == {
            ("offcpu_us" if side == "numerator" else "wall_us", )}
    # pull 250/1000, fanout 0/1000, dispatch 150/3000 and 0/3000, batch 600/3000, commit 1000/5000:
    # the fetch, the idle loop and the observation, which wait or are the trace's own, are not in it
    assert _read(_write(tmp_path, _driver()), "driver_offcpu_share.tput") == pytest.approx(100 * 2000 / 16000)


@pytest.mark.parametrize("observe,clocks", [(False, False), (False, True), (True, False)])
def test_a_trace_from_before_the_span_or_the_clocks_reads_0_and_nothing_and_raises_nothing(tmp_path, observe, clocks):
    ctx = _write(tmp_path, _driver(observe=observe, clocks=clocks))
    assert _read(ctx, "host_gap_observe_share.tput") == pytest.approx(3.0 if observe else 0.0)
    off = _read(ctx, "driver_offcpu_share.tput")
    assert off == pytest.approx(12.5) if clocks else off is None
    # the idle time the parent spent observing lay in the step span's self time: the engine's share
    assert _read(ctx, "host_gap_engine_share.tput") == pytest.approx(22.0 if observe else 25.0)
    untraced = {"reduced": None, "cell": {"root": str(tmp_path), "name": "none"}}
    assert [_read(untraced, n) for n in NAMES] == [None] * 4


@pytest.mark.parametrize("twin", ["tiny-mistral.decode-heavy", "tiny-mellum.decode-heavy",
                                  "tiny-trinity.decode-heavy-64", "tiny-sdar.block-diffusion-64"])
def test_a_rehearsed_twins_steps_observe_themselves_and_every_span_says_its_clocks(twin, tmp_path):
    """The twin's command on the CPU with the JSONL bus on (a CPU trace has no
    device plane, so no gap share can be read here): every step span encloses
    a ``serving/engine_observe`` on its thread, and every ``serving/`` span
    closes with ``wall_us >= offcpu_us >= 0``."""
    log = tmp_path / "spans.jsonl"
    code = ("import sys, runpy; sys.argv = ['run.py'] + sys.argv[1:]\n"
            "from deepspeed_tpu.monitor.trace import configure_tracer\n"
            f"configure_tracer(enabled=True, path={str(log)!r})\n"
            f"runpy.run_path({os.path.join(loader.ROOT, 'benchmark', 'run.py')!r}, run_name='__main__')\n")
    # not the checkout's own compile cache: another worker's test watches that directory
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("BENCH_RUN", None)
    out = subprocess.run([sys.executable, "-c", code, "--workload", twin, "--seed", "2147483693", "--seconds", "2",
                          "--trace", "0", "--rehearsal"], cwd=loader.ROOT, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    spans = [e for e in map(json.loads, log.read_text().splitlines())
             if e["ph"] == "X" and e["name"].startswith("serving/")]
    steps = [e for e in spans if e["name"] in ("serving/prefill", "serving/decode_step", "serving/decode")]
    observed = sorted((e["tid"], e["ts"], e["ts"] + e["dur"]) for e in spans
                      if e["name"] == "serving/engine_observe")
    assert steps and len(observed) == 2 * len(steps), "before the fetch and last in the step"
    for step in steps:
        inside = [o for o in observed if o[0] == step["tid"] and step["ts"] <= o[1]
                  and o[2] <= step["ts"] + step["dur"] + 1e-3]
        assert len(inside) == 2, step
    assert {"serving/loop_pull", "serving/loop_fanout", "serving/sched_step", "serving/engine_fetch"} <= {
        e["name"] for e in spans}
    for e in spans:
        assert e["args"]["wall_us"] >= e["args"]["offcpu_us"] >= 0, e
        assert e["args"]["wall_us"] == pytest.approx(e["dur"], abs=1.0)
