"""The reader of the program's own spans (``benchmark/lib/program_spans.py``)
and the three readers built on it, on small traces written with the
benchmark's own XSpace writer: idle gaps go to the innermost program span of
the driver thread, the benchmark's ``bench/`` spans take no part, the shares
and the uncovered rest add up to the idle share that ``reduce_trace`` reads
from the same file, and every new per-layer metric is a file, a manifest
entry and a reader."""

import glob
import os

import pytest

from benchmark.lib import loader, program_spans, xplane, xplane_write

MS = 1_000_000  # ns
NEW_READERS = ("span_gap_share", "span_arg_mean", "span_arg_ratio")
# the twelve per-layer metrics that PR 24 added on those readers; later ones are their own PRs' to test
PR24_METRICS = ("admission_wait_mean_ms", "decode_horizon_mean", "decode_row_occupancy", "decode_rows_mixed_share",
                "host_gap_engine_share.tail", "host_gap_engine_share.tput", "host_gap_sched_share.tail",
                "host_gap_sched_share.tput", "host_gap_train_share.train", "idle_no_work_share.tail",
                "prefill_token_occupancy", "sched_pending_mean_ms")


def _write(tmp_path, planes, cell="cell"):
    d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xplane_write.encode_xspace(planes))
    return {"reduced": xplane.reduce_trace(xplane.read_trace(str(d / "host.xplane.pb"))),
            "cell": {"root": str(tmp_path), "name": cell}}


def _read(ctx, reader, args):
    module = loader.load_module("readers", reader)
    return module.read({**ctx, "args": args})


def _serving_planes():
    """One chip busy 0-10, 14-20, 30-40 and 46-50 ms of a 50 ms window: gaps
    10-14 (under a put: 1 ms engine_batch, 1 ms the put span's own time, 1 ms
    sched_step's own time, 1 ms loop_pull), 20-30 (8 ms loop_idle, 2 ms under
    nothing) and 40-46 (engine_fetch inside a decode call)."""
    driver = [
        ("dstpu/serving/sched_step#kind=put,rows=3,first_wait_ms=[2.0, 4.0]#", 10 * MS, 3 * MS),
        ("dstpu/serving/prefill#rows=3,rows_decode=2,tokens=130,bucket_tokens=256,bucket_rows=4,steps=1#",
         10 * MS, 2 * MS),
        ("dstpu/serving/engine_batch", 10 * MS, 1 * MS),
        ("dstpu/serving/loop_pull#pulled=2,wait_ms=[1.0, 3.0]#", 13 * MS, 1 * MS),
        ("dstpu/serving/loop_idle#paused=0#", 21 * MS, 8 * MS),
        ("dstpu/serving/sched_step#kind=decode,rows=3,first_wait_ms=[]#", 30 * MS, 17 * MS),
        ("dstpu/serving/decode#rows=3,bucket_rows=4,bucket_tokens=4,steps=8,tokens=24#", 30 * MS, 17 * MS),
        ("dstpu/serving/engine_fetch", 31 * MS, 15 * MS),
        ("dstpu/serving/decode_step#rows=2,rows_decode=2,tokens=2,bucket_tokens=4,bucket_rows=4,steps=1#",
         48 * MS, 1 * MS),
    ]
    return {
        "/device:TPU:0": {"XLA Ops": [("%fusion.1 = f32[] fusion()", 0, 10 * MS),
                                      ("%paged_attn_kv_split.2 = f32[] custom-call()", 14 * MS, 6 * MS),
                                      ("%fusion.3 = f32[] fusion()", 30 * MS, 10 * MS),
                                      ("%copy.4 = f32[] copy()", 46 * MS, 4 * MS)]},
        "/host:CPU": {
            "driver": driver,
            # another thread's program span and the benchmark's own span cover
            # the same gaps and must get none of them
            "http": [("dstpu/serving/generate", 0, 50 * MS)],
            "bench": [("bench/put", 10 * MS, 3 * MS), ("bench/decode", 30 * MS, 17 * MS)],
        },
    }


def test_gaps_go_to_the_innermost_program_span_of_the_driver_thread(tmp_path):
    ctx = _write(tmp_path, _serving_planes())
    trace = program_spans.for_run(ctx)
    shared = program_spans.gaps_by_span(trace)
    assert shared["window_s"] == pytest.approx(50e-3) and shared["idle_s"] == pytest.approx(20e-3)
    assert shared["by_span"] == pytest.approx({
        "serving/engine_batch": 1e-3, "serving/prefill": 1e-3, "serving/sched_step": 1e-3,
        "serving/loop_pull": 1e-3, "serving/loop_idle": 8e-3, "serving/engine_fetch": 6e-3})
    assert shared["uncovered_s"] == pytest.approx(2e-3)
    assert "serving/generate" not in shared["by_span"], "not the driver's thread"
    assert not any(name.startswith("bench/") for name in shared["by_span"])


def test_shares_and_the_uncovered_rest_add_up_to_the_idle_share(tmp_path):
    ctx = _write(tmp_path, _serving_planes())
    metrics = {m["name"]: m for m in map(loader._read_json, glob.glob(
        os.path.join(loader.ROOT, "benchmark", "layer_metrics", "*.json")))}
    share = lambda name: _read(ctx, metrics[name]["reader"], metrics[name]["args"])
    no_work, sched, engine = (share("idle_no_work_share.tail"), share("host_gap_sched_share.tail"),
                              share("host_gap_engine_share.tail"))
    assert (no_work, sched, engine) == pytest.approx((16.0, 4.0, 16.0))
    assert share("host_gap_sched_share.tput") == pytest.approx(sched)
    assert share("host_gap_engine_share.tput") == pytest.approx(engine)
    uncovered = _read(ctx, "span_gap_share", {"spans": [program_spans.UNCOVERED]})
    assert uncovered == pytest.approx(4.0)
    idle = _read(ctx, "device_idle_share", {})
    assert no_work + sched + engine + uncovered == pytest.approx(idle) and idle == pytest.approx(40.0)


def test_argument_readers_on_the_manifests_own_metric_files(tmp_path):
    ctx = _write(tmp_path, _serving_planes())
    value = lambda name: (lambda m: _read(ctx, m["reader"], m["args"]))(loader._read_json(
        os.path.join(loader.ROOT, "benchmark", "layer_metrics", name + ".json")))
    assert value("admission_wait_mean_ms") == pytest.approx(2.0)   # [1, 3]
    assert value("sched_pending_mean_ms") == pytest.approx(3.0)    # [2, 4] and an empty list
    assert value("decode_horizon_mean") == pytest.approx(8.0)
    assert value("decode_row_occupancy") == pytest.approx(75.0)    # 3 rows in a bucket of 4
    assert value("prefill_token_occupancy") == pytest.approx(100 * 130 / 256)
    # decode row-steps: 2 inside the put, 3 x 8 in the decode call, 2 in the decode step
    assert value("decode_rows_mixed_share") == pytest.approx(100 * 2 / (2 + 24 + 2))


def test_a_trace_without_program_spans_reads_as_nothing(tmp_path):
    planes = _serving_planes()
    planes["/host:CPU"] = {"bench": planes["/host:CPU"]["bench"]}
    ctx = _write(tmp_path, planes)
    assert program_spans.for_run(ctx) is None
    assert _read(ctx, "span_gap_share", {"spans": ["serving/loop_idle"]}) is None
    assert _read(ctx, "span_arg_mean", {"span": "serving/decode", "arg": "steps"}) is None
    assert _read(ctx, "span_arg_ratio", {"numerator": [], "denominator": []}) is None
    assert _read({"reduced": None, "cell": ctx["cell"]}, "span_gap_share", {"spans": []}) is None


def test_training_gaps_under_input_wait_and_dispatch(tmp_path):
    planes = {
        "/device:TPU:0": {"XLA Ops": [("%fusion.1 = f32[] fusion()", 0, 40 * MS),
                                      ("%fusion.2 = f32[] fusion()", 50 * MS, 50 * MS)]},
        "/host:CPU": {"main": [("dstpu/input_wait#step=3,prefetched=1#", 40 * MS, 2 * MS),
                               ("dstpu/train/dispatch#step=3,compiled=0#", 42 * MS, 3 * MS),
                               ("bench/train_batch", 40 * MS, 6 * MS)]},
    }
    ctx = _write(tmp_path, planes)
    metric = loader._read_json(os.path.join(loader.ROOT, "benchmark", "layer_metrics",
                                            "host_gap_train_share.train.json"))
    assert _read(ctx, metric["reader"], metric["args"]) == pytest.approx(5.0)  # 5 of the 10 idle ms, of 100
    (span, ) = program_spans.spans_named(program_spans.for_run(ctx), "train/dispatch")
    assert span.args == {"step": 3, "compiled": 0}


def test_arguments_in_the_name_are_parsed_like_stats():
    split = program_spans._split_name
    assert split("dstpu/serving/loop_idle") == ("dstpu/serving/loop_idle", {})
    name, args = split("dstpu/serving/decode#rows=3,kernel=paged_attn_kv_split:8:heuristic:long_table,"
                       "uids=[1, 2, 3],mean=1.5,blocked=1#")
    assert name == "dstpu/serving/decode"
    assert args == {"rows": 3, "kernel": "paged_attn_kv_split:8:heuristic:long_table", "uids": [1, 2, 3],
                    "mean": 1.5, "blocked": 1}
    assert program_spans.numbers(args["uids"]) == [1.0, 2.0, 3.0] and program_spans.numbers("x") == []


def test_every_new_layer_metric_has_its_entry_and_an_existing_reader():
    manifest = {m["name"]: m for m in loader.load_manifest()["per_layer"]}
    cells = {w["name"]: w for w in loader.load_manifest()["workloads"]}
    new = [m for m in map(loader._read_json, sorted(glob.glob(
        os.path.join(loader.ROOT, "benchmark", "layer_metrics", "*.json")))) if m["name"] in PR24_METRICS]
    assert len(new) == 12
    for metric in new:
        assert metric["reader"] in NEW_READERS
        assert os.path.isfile(os.path.join(loader.ROOT, "benchmark", "readers", metric["reader"] + ".py"))
        entry = manifest[metric["name"]]
        said = ("name", "unit", "better", "source", "layer", "moves")
        assert {k: entry[k] for k in said} == {k: metric[k] for k in said} and set(entry) == {*said, "workloads"}
        assert entry["source"] in ("device_trace", "program_counter")
        for cell in entry["workloads"]:
            resolved = loader.resolve_cell(cell)
            assert metric["name"] in {m["name"] for m in resolved["layer_metrics"]}
            assert entry["moves"] in {m["name"] for m in resolved["end_to_end"]}, (metric["name"], cell)
            assert cells[cell]["name"] == cell
    # they stay one block where PR 24 appended them: a later PR appends too, and puts nothing in the middle
    names = [m["name"] for m in loader.load_manifest()["per_layer"]]
    assert set(names[25:37]) == set(PR24_METRICS)
