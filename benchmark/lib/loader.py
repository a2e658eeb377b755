"""Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives. Nothing here, or in ``run.py``, lists cells,
configurations, mixes, metrics, builders, drivers, readers or references: a
later PR adds files and manifest entries, and edits none.

    benchmark/configs/<config>.json          sizes, source, builder, reference, engine settings
    benchmark/traffic/<traffic>.json         driver and its parameters
    benchmark/layer_metrics/<metric>.json    layer, unit, moves, reader, arguments
    benchmark/builders/<builder>.py          build(cell) -> the system under test
    benchmark/drivers/<driver>.py            run(cell, args) -> result
    benchmark/readers/<reader>.py            read(ctx) -> number or None
    benchmark/lib/<reference>.py             the configuration's plain reference
    benchmark/rehearsal/<twin>.json          config, traffic, chips of a tiny CPU twin

Three rules keep an addition from forcing an edit:

* The cells a per-layer metric applies to are said once, in the manifest's
  ``per_layer`` entry of its name (no ``workloads`` key: every cell). The
  metric's file says how it is read, never where; a file that the manifest
  does not list is an error, not a silent metric.
* A configuration names its reference, ``"reference": "<module>"`` for
  ``benchmark/lib/<module>.py``; without the key it is ``reference``. Such a
  module gives what the builders and drivers call: ``hyper_from_published(cf)``,
  ``forward_logits(hp, params, ids, positions)`` and, for a configuration
  that is trained, ``loss_and_grad_norm(hp, params, ids)``.
* A rehearsal twin (a tiny cell that rehearses a cell's command on the CPU,
  is in no ``workloads`` and reports no metric) is one file, named after the
  twin. The tests ask for one twin for every cell's driver and chip count.
"""

import glob
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = "BENCHMARK.json"


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, MANIFEST))


def _applies(entry: dict, cell_name: str) -> bool:
    """A manifest metric applies to a cell that it lists, and to every cell
    when it has no ``workloads`` key."""
    return "workloads" not in entry or cell_name in entry["workloads"]


def _named_files(root: str, kind: str) -> dict:
    """``benchmark/<kind>/<name>.json`` by name, in the order of the names."""
    paths = sorted(glob.glob(os.path.join(root, "benchmark", kind, "*.json")))
    return {os.path.basename(p)[:-len(".json")]: _read_json(p) for p in paths}


def rehearsal_cells(root: str = ROOT) -> list:
    """The rehearsal twins, one ``benchmark/rehearsal/<name>.json`` each."""
    return [dict(twin, name=name) for name, twin in _named_files(root, "rehearsal").items()]


def resolve_cell(name: str, root: str = ROOT, rehearsal: bool = False) -> dict:
    """The cell ``name`` with its configuration, traffic and per-layer metric
    files loaded. A rehearsal cell comes from ``benchmark/rehearsal/`` and
    reports no metric."""
    manifest = load_manifest(root)
    table = rehearsal_cells(root) if rehearsal else manifest["workloads"]
    cells = [w for w in table if w["name"] == name]
    if len(cells) != 1:
        raise KeyError(f"no workload {name!r} among {[w['name'] for w in table]}")
    cell = dict(cells[0])
    bench = os.path.join(root, "benchmark")
    cell["config_file"] = _read_json(os.path.join(bench, "configs", cell["config"] + ".json"))
    cell["traffic_file"] = _read_json(os.path.join(bench, "traffic", cell["traffic"] + ".json"))
    cell["end_to_end"], cell["layer_metrics"] = [], []
    if not rehearsal:
        listed = {m["name"]: m for m in manifest["per_layer"]}
        files = _named_files(root, "layer_metrics")
        if set(files) != set(listed):
            raise KeyError(f"per-layer metrics: {sorted(set(files) ^ set(listed))} are in only one of "
                           f"{MANIFEST}'s per_layer and benchmark/layer_metrics/")
        cell["end_to_end"] = [m for m in manifest["end_to_end"] if _applies(m, name)]
        cell["layer_metrics"] = [metric for metric_name, metric in files.items()
                                 if _applies(listed[metric_name], name)]
    cell["run_seconds"] = manifest["run_seconds"]
    cell["root"] = root
    return cell


def load_module(kind: str, name: str, root: str = ROOT):
    """``benchmark/<kind>/<name>.py`` as a module, by its path."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind.rstrip('s')} {name!r}: expected {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reference(cell: dict):
    """The plain reference that the cell's configuration names."""
    return load_module("lib", cell["config_file"].get("reference", "reference"), cell["root"])


def read_layer_metrics(cell: dict, ctx: dict) -> dict:
    """Each of the cell's per-layer metrics through its reader. A reader that
    finds nothing to read returns ``None`` and the metric is left out."""
    out = {}
    for metric in cell["layer_metrics"]:
        reader = load_module("readers", metric["reader"], cell["root"])
        value = reader.read({**ctx, "metric": metric, "args": metric.get("args", {})})
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out
