"""The harness takes every later cell as data: a configuration, a traffic mix
and a per-layer metric added as NEW files (and manifest entries) are found by
name, with no existing file edited; and the committed manifest agrees with
the files it names and keeps inside the contract's limits."""

import glob
import hashlib
import json
import os
import re
import shutil

import pytest

from benchmark.lib import loader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _digests(root):
    out = {}
    for path in glob.glob(os.path.join(root, "benchmark", "**", "*"), recursive=True):
        if os.path.isfile(path) and "__pycache__" not in path:
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def _drivers_and_chips(cells, root):
    """What the rehearsal twins have to cover: each cell's driver and chips."""
    return sorted((loader._read_json(os.path.join(root, "benchmark", "traffic", w["traffic"] + ".json"))["driver"],
                   w["chips"]) for w in cells)


def _write_json(path, value):
    with open(path, "w") as f:
        json.dump(value, f)


def test_a_new_configuration_traffic_mix_and_layer_metric_are_added_files_only(tmp_path):
    """What a ``model_config`` PR adds: a configuration that names its own
    reference module, a traffic mix, a rehearsal twin, a per-layer metric with
    its reader, and an existing per-layer metric applied to the new cell by
    the manifest alone. Every file that was there keeps its digest."""
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(loader.ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    before = _digests(root)
    assert [k for k in before if k.startswith("benchmark/rehearsal/")]

    bench = os.path.join(root, "benchmark")
    config = loader._read_json(os.path.join(bench, "configs", "pythia-410m.json"))
    config.update(name="pythia-160m", hidden_size=768, num_attention_heads=12, num_hidden_layers=12,
                  intermediate_size=3072, reference="reference_sparse")
    _write_json(os.path.join(bench, "configs", "pythia-160m.json"), config)
    with open(os.path.join(bench, "lib", "reference_sparse.py"), "w") as f:
        f.write("def hyper_from_published(cf):\n    return {'hidden': cf['hidden_size']}\n\n\n"
                "def forward_logits(hp, params, ids, positions):\n    return 'own logits'\n\n\n"
                "def loss_and_grad_norm(hp, params, ids):\n    return 'own loss', 'own norm'\n")
    _write_json(os.path.join(bench, "traffic", "pretrain-8k.json"),
                {"driver": "train_steps", "seq_len": 8192, "global_batch_tokens": 65536,
                 "micro_batch_per_chip": 1, "warmup_steps": 2, "batch_pool": 4,
                 "check_sequences_per_chip": 1, "trace_steps": 2})
    _write_json(os.path.join(bench, "rehearsal", "tiny-neox.long.json"),
                {"config": "tiny-neox", "traffic": "pretrain-tiny", "chips": 1})
    steps_counted = {"name": "steps_counted", "layer": "Train engine", "unit": "count", "better": "higher",
                     "source": "program_counter", "moves": "train_tokens_per_s_per_chip"}
    _write_json(os.path.join(bench, "layer_metrics", "steps_counted.json"),
                {**steps_counted, "reader": "count_fences", "args": {"less": 1}})
    with open(os.path.join(bench, "readers", "count_fences.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx['fences']) - ctx['args']['less']\n")
    # the manifest gains entries and names at the end of lists; nothing it had is changed or moved
    manifest = loader.load_manifest(root)
    manifest["workloads"].append({"name": "pythia-160m.long", "config": "pythia-160m",
                                  "traffic": "pretrain-8k", "chips": 1, "why": "test"})
    for metric in manifest["end_to_end"]:
        if metric["name"] == "train_tokens_per_s_per_chip":
            metric["workloads"] = metric["workloads"] + ["pythia-160m.long"]
    for metric in manifest["per_layer"]:  # an existing metric reaches the new cell by the manifest alone
        if metric["name"] == "train_mfu":
            metric["workloads"] = metric["workloads"] + ["pythia-160m.long"]
    manifest["per_layer"].append({**steps_counted, "workloads": ["pythia-160m.long"]})
    _write_json(os.path.join(root, "BENCHMARK.json"), manifest)

    cell = loader.resolve_cell("pythia-160m.long", root)
    assert cell["config_file"]["hidden_size"] == 768 and cell["traffic_file"]["seq_len"] == 8192
    assert [m["name"] for m in cell["end_to_end"]] == ["train_tokens_per_s_per_chip", "setup_s"]
    # the new metric, the old one the manifest applied, and the one every cell has; no other cell's metric
    assert [m["name"] for m in cell["layer_metrics"]] == ["compiles_in_window", "steps_counted", "train_mfu"]
    old = {m["name"]: m for m in loader.resolve_cell("pythia-410m.pretrain", root)["layer_metrics"]}
    assert cell["layer_metrics"][2] == old["train_mfu"] and "steps_counted" not in old
    got = loader.read_layer_metrics({**cell, "layer_metrics": [m for m in cell["layer_metrics"]
                                                               if m["name"] == "steps_counted"]},
                                    {"fences": [0.0, 1.0, 2.0, 3.0]})
    assert got == {"steps_counted": {"value": 3.0, "unit": "count"}}
    assert loader.load_module("drivers", cell["traffic_file"]["driver"], root).run
    assert loader.load_module("builders", cell["config_file"]["builder"], root).build
    # the configuration's own reference, and the one every configuration without the key has
    own = loader.load_reference(cell)
    assert own.hyper_from_published(cell["config_file"]) == {"hidden": 768}
    assert own.forward_logits(None, None, None, None) == "own logits"
    assert own.loss_and_grad_norm(None, None, None) == ("own loss", "own norm")
    default = loader.load_reference(loader.resolve_cell("pythia-410m.pretrain", root))
    assert default.__file__ == os.path.join(bench, "lib", "reference.py")
    # the twins still cover the cells, and the new twin resolves
    assert _drivers_and_chips(loader.rehearsal_cells(root), root) == _drivers_and_chips(manifest["workloads"], root)
    assert loader.resolve_cell("tiny-neox.long", root, rehearsal=True)["config"] == "tiny-neox"
    # every file that was there is untouched
    after = _digests(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/pythia-160m.json", "benchmark/layer_metrics/steps_counted.json",
        "benchmark/lib/reference_sparse.py", "benchmark/readers/count_fences.py",
        "benchmark/rehearsal/tiny-neox.long.json", "benchmark/traffic/pretrain-8k.json"]


def test_a_layer_metric_file_that_the_manifest_does_not_list_is_an_error(tmp_path):
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(loader.ROOT, "benchmark", "layer_metrics"),
                    os.path.join(root, "benchmark", "layer_metrics"))
    for kind in ("configs", "traffic"):
        shutil.copytree(os.path.join(loader.ROOT, "benchmark", kind), os.path.join(root, "benchmark", kind))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    cell = loader.load_manifest(root)["workloads"][0]["name"]
    loader.resolve_cell(cell, root)
    metric = loader._read_json(os.path.join(root, "benchmark", "layer_metrics", "compiles_in_window.json"))
    _write_json(os.path.join(root, "benchmark", "layer_metrics", "stray.json"), {**metric, "name": "stray"})
    with pytest.raises(KeyError, match="stray"):
        loader.resolve_cell(cell, root)


# what each cell resolved to before the manifest alone said where a metric applies (PR 25's tree)
PINNED_LAYER_METRICS = {
    "pythia-410m.pretrain": [
        "compiles_in_window", "device_idle_share.train", "flash_roofline_share", "flash_time_share",
        "host_gap_train_share.train", "peak_hbm_bytes.train", "train_mfu", "train_step_p50_ms"],
    "mistral-7b.longprompt": [
        "closed_ttft_p50_ms", "compiles_in_window", "device_idle_share.tput", "host_gap_engine_share.tput",
        "host_gap_sched_share.tput", "paged_prefill_roofline_share", "paged_prefill_time_share",
        "peak_hbm_bytes.tput", "prefill_step_tokens_mean", "prefill_token_occupancy", "prefill_tokens_per_s"],
    "pythia-1.4b.zero3-x4": [
        "collective_exposed_share", "compiles_in_window", "device_idle_share.train", "flash_roofline_share",
        "flash_time_share", "host_gap_train_share.train", "peak_hbm_bytes.train", "train_mfu",
        "train_step_p50_ms"],
    "mistral-7b.chat": [
        "admission_wait_mean_ms", "compiles_in_window", "decode_batch_mean", "decode_horizon_mean",
        "decode_row_occupancy", "decode_rows_mixed_share", "decode_step_p50_ms", "device_idle_share.tail",
        "gen_late_p95_ms", "host_gap_engine_share.tail", "host_gap_sched_share.tail", "idle_no_work_share.tail",
        "paged_decode_roofline_share", "paged_decode_time_share", "peak_hbm_bytes.tail", "queue_wait_p95_ms",
        "sched_pending_mean_ms", "ttft_p50_ms", "ttft_p95_ms"],
}


@pytest.mark.parametrize("cell", sorted(PINNED_LAYER_METRICS))
def test_the_first_four_cells_resolve_to_the_layer_metrics_they_always_had(cell):
    assert [m["name"] for m in loader.resolve_cell(cell)["layer_metrics"]] == PINNED_LAYER_METRICS[cell]


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    cell = loader.resolve_cell("pythia-410m.pretrain")
    metrics = [m for m in cell["layer_metrics"] if m["name"] in ("device_idle_share.train", "train_step_p50_ms")]
    got = loader.read_layer_metrics({**cell, "layer_metrics": metrics},
                                    {"reduced": None, "fences": [1.0, 2.5, 4.0]})
    assert got == {"train_step_p50_ms": {"value": 1500.0, "unit": "ms"}}


def test_unknown_names_are_errors():
    with pytest.raises(KeyError, match="no workload"):
        loader.resolve_cell("no-such.cell")
    with pytest.raises(FileNotFoundError, match="no reader"):
        loader.load_module("readers", "no_such_reader")


def test_no_code_of_the_harness_names_a_cell_a_configuration_or_a_mix():
    manifest = loader.load_manifest()
    names = {w["name"] for w in manifest["workloads"]} | {c["name"] for c in manifest["configs"]} \
        | {w["traffic"] for w in manifest["workloads"]}
    for path in glob.glob(os.path.join(loader.ROOT, "benchmark", "**", "*.py"), recursive=True):
        with open(path) as f:
            code = "\n".join(line for line in f if not line.lstrip().startswith("#"))
        code = re.sub(r'"""[\s\S]*?"""', "", code)
        for name in names:
            assert f'"{name}"' not in code and f"'{name}'" not in code, (path, name)


def test_the_manifest_agrees_with_the_files_and_the_contracts_limits():
    manifest = loader.load_manifest()
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"] and 1 <= manifest["run_seconds"] <= 51
    cells = [w["name"] for w in manifest["workloads"]]
    configs = {c["name"]: c for c in manifest["configs"]}
    assert len(set(cells)) == len(cells) >= 1
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(1, len(cells) // 4)
    assert {w["config"] for w in manifest["workloads"]} == set(configs)
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(loader.ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and len(c["why"]) <= 200
        held = loader._read_json(os.path.join(loader.ROOT, c["file"]))
        assert held["source"] == c["source"] and held["reduced"] == c["reduced"]
        assert not [k for k in c["reduced"] if re.search(r"(_dim|_rank|_size)$", k)]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    files = {}
    for path in glob.glob(os.path.join(loader.ROOT, "benchmark", "layer_metrics", "*.json")):
        f = loader._read_json(path)
        assert os.path.basename(path) == f["name"] + ".json"
        files[f["name"]] = f
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert set(listed) == set(files)
    for name, m in listed.items():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        f = files[name]
        assert "workloads" not in f, name  # the manifest alone says where a metric applies
        assert {k: m[k] for k in m if k != "workloads"} == {k: f[k] for k in m if k != "workloads"}, name
        assert set(m.get("workloads", [])) <= set(cells), name
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["moves"] in e2e
        assert os.path.isfile(os.path.join(loader.ROOT, "benchmark", "readers", f["reader"] + ".py"))
        for cell in m.get("workloads", cells):  # the metric it moves is reported wherever it is
            assert "workloads" not in e2e[m["moves"]] or cell in e2e[m["moves"]]["workloads"], (name, cell)
    for cell in cells:  # every cell reports setup_s, another end-to-end metric and a per-layer metric
        assert [m for m in e2e.values() if m["name"] != "setup_s" and cell in m.get("workloads", [])]
        assert [m for m in listed.values() if cell in m.get("workloads", [cell])]
