"""What PR 28 added to the benchmark: one per-layer metric,
``decode_kv_live_share.tput``, as a file and a manifest entry on the reader
that was there (``span_arg_ratio``): live (row, KV block) pairs over the block
slots the decode kernel's grid ran, from the ``kv_live`` and ``kv_steps`` the
program puts on its decode spans. A program from before those counts reads as
nothing, and a rehearsed decode-heavy twin's spans carry them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import loader, xplane, xplane_write

MS = 1_000_000  # ns
NAME = "decode_kv_live_share.tput"
CELLS = ["mistral-7b.decode-heavy", "mellum2-12b-a2.5b.decode-heavy"]


def _metric():
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "layer_metrics", NAME + ".json"))


def _ctx(tmp_path, decode_args, step_args, cell="cell"):
    planes = {
        "/device:TPU:0": {"XLA Ops": [("%paged_attn_kv_split = bf16[8] custom-call()", 0, 10 * MS)]},
        "/host:CPU": {"driver": [
            (f"dstpu/serving/decode#rows=31,bucket_rows=32,steps=10{decode_args}#", 0, 30 * MS),
            (f"dstpu/serving/decode_step#rows=32,bucket_rows=32,steps=1{step_args}#", 30 * MS, 3 * MS),
            # a put that prefills carries no such counts: the tiled grid is not the decode kernel's
            ("dstpu/serving/prefill#rows=3,bucket_rows=4,steps=1#", 40 * MS, 5 * MS)]},
    }
    d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xplane_write.encode_xspace(planes))
    return {"reduced": xplane.reduce_trace(xplane.read_trace(str(d / "host.xplane.pb"))),
            "cell": {"root": str(tmp_path), "name": cell}}


def _read(ctx):
    metric = _metric()
    return loader.load_module("readers", metric["reader"]).read({**ctx, "args": metric["args"]})


@pytest.mark.parametrize("decode_args,step_args,want", [
    # the parent's grid at 32 rows of about 8 live blocks: 8 splits x 32 rows x 9 columns a layer call
    (",kv_steps=460800,kv_live=50000", ",kv_steps=46080,kv_live=5000", 100.0 * 55000 / 506880),
    # the work list at two blocks a step: the odd tails and the pad row are all that is not live
    (",kv_steps=54400,kv_live=50000", ",kv_steps=5600,kv_live=5000", 100.0 * 55000 / 60000),
    # only the burst carries them (a decode_step the tiled grid took)
    (",kv_steps=54400,kv_live=50000", "", 100.0 * 50000 / 54400),
])
def test_the_share_is_live_pairs_over_grid_slots_on_both_decode_spans(tmp_path, decode_args, step_args, want):
    assert _read(_ctx(tmp_path, decode_args, step_args)) == pytest.approx(want)


def test_a_program_without_the_counts_reads_as_nothing(tmp_path):
    """The parent's spans have no ``kv_steps``: the metric is left out, nothing raises."""
    assert _read(_ctx(tmp_path, "", "")) is None
    assert _read({"reduced": None, "cell": {"root": str(tmp_path), "name": "none"}}) is None


def test_the_manifest_lists_the_metric_for_the_decode_heavy_cells_and_no_other():
    manifest = loader.load_manifest()
    (entry, ) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    metric = _metric()
    said = ("name", "unit", "better", "source", "layer", "moves")
    assert {k: entry[k] for k in said} == {k: metric[k] for k in said}
    assert entry["workloads"] == CELLS and manifest["per_layer"][-1] is entry, "appended, nothing moved"
    assert metric["reader"] == "span_arg_ratio"
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"] if m["name"] != NAME}
    for cell in manifest["workloads"]:
        resolved = loader.resolve_cell(cell["name"])
        listed = NAME in {m["name"] for m in resolved["layer_metrics"]}
        assert listed == (cell["name"] in CELLS)
        if listed:
            assert entry["moves"] in {m["name"] for m in resolved["end_to_end"]}


@pytest.mark.parametrize("twin", ["tiny-mistral.decode-heavy", "tiny-mellum.decode-heavy"])
def test_a_rehearsed_twins_decode_spans_carry_the_counts(twin, tmp_path):
    """The twin's command on the CPU with the JSONL bus on: every
    ``serving/decode`` span has both integers, live pairs never more than the
    slots, and off the TPU the slots are every table column of every bucket
    row (the twin's per-token grid and the gather walk them all)."""
    log = tmp_path / "spans.jsonl"
    code = ("import sys, runpy; sys.argv = ['run.py'] + sys.argv[1:]\n"
            "from deepspeed_tpu.monitor.trace import configure_tracer\n"
            f"configure_tracer(enabled=True, path={str(log)!r})\n"
            f"runpy.run_path({os.path.join(loader.ROOT, 'benchmark', 'run.py')!r}, run_name='__main__')\n")
    # not the checkout's own compile cache: another worker's test watches that directory
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("BENCH_RUN", None)
    out = subprocess.run([sys.executable, "-c", code, "--workload", twin, "--seed", "2147483659", "--seconds", "2",
                          "--trace", "0", "--rehearsal"], cwd=loader.ROOT, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    spans = [json.loads(line) for line in log.read_text().splitlines() if '"serving/decode' in line]
    bursts = [s["args"] for s in spans if s.get("name") == "serving/decode" and s.get("ph") == "X"]
    assert bursts, "the twin decodes"
    for a in bursts:
        assert 0 < a["kv_live"] <= a["kv_steps"], a
        assert not a["kernel"].startswith("paged_attn_kv_split"), "the CPU twin walks the whole table"
        assert a["kv_steps"] % (a["bucket_rows"] * a["steps"]) == 0
