"""What PR 54 added to the benchmark, data only: one per-layer metric,
``index_keys_live_share.tput``, as a file and a manifest entry on the reader
that was there (``span_arg_ratio``): the (query token, kv head, pooled key)
triples the model asked its selection's indexer for (``index_keys``) over those
the indexer's program scored (``index_keys_scored``), on the three step spans.
A program from before the second count (the parent's) reads as nothing."""

import os

import pytest

from benchmark.lib import loader, xplane, xplane_write

MS = 1_000_000  # ns
NAME = "index_keys_live_share.tput"
CELLS = ["minicpm-sala.longctx"]
PARENT_METRICS = 72  # the per-layer entries the manifest held before this one


def _metric():
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "layer_metrics", NAME + ".json"))


def _ctx(tmp_path, prefill, step, decode, cell="cell"):
    planes = {
        "/device:TPU:0": {"XLA Ops": [("%sparse_index_scores = f32[8] custom-call()", 0, 10 * MS)]},
        "/host:CPU": {"driver": [
            (f"dstpu/serving/prefill#rows=8,bucket_rows=8,steps=1{prefill}#", 0, 30 * MS),
            (f"dstpu/serving/decode_step#rows=8,bucket_rows=8,steps=1{step}#", 30 * MS, 3 * MS),
            (f"dstpu/serving/decode#rows=8,bucket_rows=8,steps=4{decode}#", 40 * MS, 5 * MS)]},
    }
    d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xplane_write.encode_xspace(planes))
    return {"reduced": xplane.reduce_trace(xplane.read_trace(str(d / "host.xplane.pb"))),
            "cell": {"root": str(tmp_path), "name": cell}}


def _read(ctx):
    metric = _metric()
    return loader.load_module("readers", metric["reader"]).read({**ctx, "args": metric["args"]})


@pytest.mark.parametrize("prefill,step,decode,want", [
    # a chunk step whose work list covers little more than was asked, beside steps of one-token tiles on the rectangle
    (",index_keys=7000000,index_keys_scored=8000000", ",index_keys=20000,index_keys_scored=500000",
     ",index_keys=80000,index_keys_scored=2000000", 100.0 * 7100000 / 10500000),
    # the chunk steps alone
    (",index_keys=7000000,index_keys_scored=8000000", "", "", 100.0 * 7 / 8),
    # steps under dense_len ask for nothing and score nothing: they move neither sum
    (",index_keys=0,index_keys_scored=0", ",index_keys=20000,index_keys_scored=500000", "", 100.0 * 20000 / 500000),
])
def test_the_share_is_keys_asked_over_keys_scored_on_the_three_step_spans(tmp_path, prefill, step, decode, want):
    assert _read(_ctx(tmp_path, prefill, step, decode)) == pytest.approx(want)


def test_a_program_without_the_second_count_reads_as_nothing(tmp_path):
    """The parent's spans say ``index_keys`` alone: the metric is left out, nothing raises."""
    assert _read(_ctx(tmp_path, ",index_keys=7000000", ",index_keys=20000", "")) is None
    assert _read(_ctx(tmp_path, "", "", "", cell="older")) is None
    assert _read({"reduced": None, "cell": {"root": str(tmp_path), "name": "none"}}) is None


def test_the_manifest_lists_the_metric_for_the_selections_cell_and_no_other():
    manifest = loader.load_manifest()
    (entry, ) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    metric = _metric()
    said = ("name", "unit", "better", "source", "layer", "moves")
    assert {k: entry[k] for k in said} == {k: metric[k] for k in said}
    assert manifest["per_layer"][PARENT_METRICS] is entry, "appended behind what the parent held"
    assert (entry["layer"], entry["moves"], entry["better"], entry["unit"], entry["source"]) == (
        "Kernels: selection indexer", "serve_tokens_per_s", "higher", "%", "program_counter")
    assert entry["workloads"] == CELLS and metric["reader"] == "span_arg_ratio"
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"][:PARENT_METRICS]}, "a layer the manifest names"
    spans = {"serving/decode", "serving/decode_step", "serving/prefill"}
    for side, arg in (("numerator", "index_keys"), ("denominator", "index_keys_scored")):
        assert {t["span"] for t in metric["args"][side]} == spans and all(t["product"] == [arg] for t in metric["args"][side])
    for cell in manifest["workloads"]:
        resolved = loader.resolve_cell(cell["name"])
        listed = NAME in {m["name"] for m in resolved["layer_metrics"]}
        assert listed == (cell["name"] in CELLS)
        if listed:
            assert entry["moves"] in {m["name"] for m in resolved["end_to_end"]}
