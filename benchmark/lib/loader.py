"""Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives. Nothing here, or in ``run.py``, lists cells,
configurations, mixes, metrics, builders, drivers or readers: a later PR adds
files and manifest entries, and edits none.

    benchmark/configs/<config>.json          sizes, source, builder, engine settings
    benchmark/traffic/<traffic>.json         driver and its parameters
    benchmark/layer_metrics/<metric>.json    layer, unit, moves, cells, reader, arguments
    benchmark/builders/<builder>.py          build(cell) -> the system under test
    benchmark/drivers/<driver>.py            run(cell, args) -> result
    benchmark/readers/<reader>.py            read(ctx) -> number or None
"""

import glob
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = "BENCHMARK.json"
REHEARSALS = "benchmark/rehearsal.json"


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, MANIFEST))


def _applies(entry: dict, cell_name: str) -> bool:
    """A manifest metric or a layer-metric file applies to a cell that it
    lists, and to every cell when it has no ``workloads`` key."""
    return "workloads" not in entry or cell_name in entry["workloads"]


def resolve_cell(name: str, root: str = ROOT, rehearsal: bool = False) -> dict:
    """The cell ``name`` with its configuration, traffic and per-layer metric
    files loaded. A rehearsal cell comes from ``benchmark/rehearsal.json``
    (tiny configurations that are not in ``workloads``) and reports no
    metric."""
    manifest = load_manifest(root)
    table = _read_json(os.path.join(root, REHEARSALS))["workloads"] if rehearsal else manifest["workloads"]
    cells = [w for w in table if w["name"] == name]
    if len(cells) != 1:
        raise KeyError(f"no workload {name!r} among {[w['name'] for w in table]}")
    cell = dict(cells[0])
    bench = os.path.join(root, "benchmark")
    cell["config_file"] = _read_json(os.path.join(bench, "configs", cell["config"] + ".json"))
    cell["traffic_file"] = _read_json(os.path.join(bench, "traffic", cell["traffic"] + ".json"))
    cell["end_to_end"] = [] if rehearsal else [m for m in manifest["end_to_end"] if _applies(m, name)]
    cell["layer_metrics"] = []
    if not rehearsal:
        for path in sorted(glob.glob(os.path.join(bench, "layer_metrics", "*.json"))):
            metric = _read_json(path)
            if _applies(metric, name):
                cell["layer_metrics"].append(metric)
    cell["run_seconds"] = manifest["run_seconds"]
    cell["root"] = root
    return cell


def load_module(kind: str, name: str, root: str = ROOT):
    """``benchmark/<kind>/<name>.py`` as a module, by its path."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: expected {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


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
