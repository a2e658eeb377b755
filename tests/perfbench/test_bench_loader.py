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


def test_a_new_configuration_traffic_mix_and_layer_metric_are_added_files_only(tmp_path):
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(loader.ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(loader.ROOT, "BENCHMARK.json"), root)
    before = _digests(root)

    bench = os.path.join(root, "benchmark")
    config = loader._read_json(os.path.join(bench, "configs", "pythia-410m.json"))
    config.update(name="pythia-160m", hidden_size=768, num_attention_heads=12, num_hidden_layers=12,
                  intermediate_size=3072)
    with open(os.path.join(bench, "configs", "pythia-160m.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "pretrain-8k.json"), "w") as f:
        json.dump({"driver": "train_steps", "seq_len": 8192, "global_batch_tokens": 65536,
                   "micro_batch_per_chip": 1, "warmup_steps": 2, "batch_pool": 4,
                   "check_sequences_per_chip": 1, "trace_steps": 2}, f)
    with open(os.path.join(bench, "layer_metrics", "steps_counted.json"), "w") as f:
        json.dump({"name": "steps_counted", "layer": "Train engine", "unit": "count", "better": "higher",
                   "source": "program_counter", "moves": "train_tokens_per_s_per_chip",
                   "workloads": ["pythia-160m.long"], "reader": "count_fences", "args": {"less": 1}}, f)
    with open(os.path.join(bench, "readers", "count_fences.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx['fences']) - ctx['args']['less']\n")
    # the manifest gains entries; none it had is changed
    manifest = loader.load_manifest(root)
    manifest["workloads"].append({"name": "pythia-160m.long", "config": "pythia-160m",
                                  "traffic": "pretrain-8k", "chips": 1, "why": "test"})
    for metric in manifest["end_to_end"]:
        if metric["name"] == "train_tokens_per_s_per_chip":
            metric["workloads"] = metric["workloads"] + ["pythia-160m.long"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    cell = loader.resolve_cell("pythia-160m.long", root)
    assert cell["config_file"]["hidden_size"] == 768 and cell["traffic_file"]["seq_len"] == 8192
    assert [m["name"] for m in cell["end_to_end"]] == ["train_tokens_per_s_per_chip", "setup_s"]
    # the new metric and the one metric that every cell has; no other cell's metric
    assert [m["name"] for m in cell["layer_metrics"]] == ["compiles_in_window", "steps_counted"]
    got = loader.read_layer_metrics({**cell, "layer_metrics": [m for m in cell["layer_metrics"]
                                                               if m["name"] == "steps_counted"]},
                                    {"fences": [0.0, 1.0, 2.0, 3.0]})
    assert got == {"steps_counted": {"value": 3.0, "unit": "count"}}
    assert loader.load_module("drivers", cell["traffic_file"]["driver"], root).run
    assert loader.load_module("builders", cell["config_file"]["builder"], root).build
    # an existing cell is untouched by the additions, and so is every file that was there
    assert "steps_counted" not in [m["name"] for m in loader.resolve_cell("pythia-410m.pretrain", root)["layer_metrics"]]
    after = _digests(root)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/pythia-160m.json", "benchmark/layer_metrics/steps_counted.json",
        "benchmark/readers/count_fences.py", "benchmark/traffic/pretrain-8k.json"]


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
    assert set(listed) <= set(files)
    for name, m in listed.items():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        f = files[name]
        assert {k: m[k] for k in m} == {k: f[k] for k in m}, name
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["moves"] in e2e
        assert os.path.isfile(os.path.join(loader.ROOT, "benchmark", "readers", f["reader"] + ".py"))
        for cell in m.get("workloads", cells):  # the metric it moves is reported wherever it is
            assert "workloads" not in e2e[m["moves"]] or cell in e2e[m["moves"]]["workloads"], (name, cell)
    for cell in cells:  # every cell reports setup_s, another end-to-end metric and a per-layer metric
        assert [m for m in e2e.values() if m["name"] != "setup_s" and cell in m.get("workloads", [])]
        assert [m for m in listed.values() if cell in m.get("workloads", [cell])]
