"""``BENCHMARK.json`` and the files it names.

A cell is resolved by names alone: the manifest's ``workloads`` entry
gives a configuration and a traffic mix; the configuration's file names
its generator, the mix's file names its operation, and every metric of
the manifest that the cell reports has a reader of its own name.  No
name of a cell, configuration, mix or metric appears in the harness.
"""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path):
    """The Python file at ``path`` as a module (file names follow the
    manifest's names, which need not be Python identifiers)."""
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(manifest, group, cell_name):
    """The metrics of ``group`` ("end_to_end" or "per_layer") that the
    cell reports: those that list it under ``workloads``; of those
    with no such key every end-to-end metric, and every per-layer
    metric that ``moves`` an end-to-end metric the cell reports."""
    def listed(m):
        return "workloads" not in m or cell_name in m["workloads"]

    if group == "end_to_end":
        return [m for m in manifest[group] if listed(m)]
    moved = {m["name"] for m in manifest["end_to_end"] if listed(m)}
    return [m for m in manifest[group]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def resolve(manifest, cell_name, root=ROOT):
    """Paths of everything the cell is made of, under ``root`` (a
    checkout: the manifest's ``file`` entries are relative to it, and
    the benchmark's folders lie in its directory of this one's name);
    raises KeyError for a cell the manifest does not have."""
    bench_dir = os.path.join(root, os.path.basename(BENCH_DIR))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json; it has "
                       f"{sorted(cells)}")
    cell = cells[cell_name]
    config_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config_path = os.path.join(root, config_entry["file"])
    config = load_json(config_path)
    traffic_path = os.path.join(bench_dir, "traffic",
                                cell["traffic"] + ".json")
    traffic = load_json(traffic_path)
    return {
        "cell": cell,
        "config": config, "config_path": config_path,
        "traffic": traffic, "traffic_path": traffic_path,
        "generator_path": os.path.join(
            bench_dir, "generators", config["generator"]["name"] + ".py"),
        "operation_path": os.path.join(
            bench_dir, "operations", traffic["operation"] + ".py"),
        "end_to_end": metrics_of(manifest, "end_to_end", cell_name),
        "per_layer": metrics_of(manifest, "per_layer", cell_name),
        "layer_metric_paths": {
            m["name"]: os.path.join(bench_dir, "layer_metrics",
                                    m["name"] + ".py")
            for m in metrics_of(manifest, "per_layer", cell_name)},
    }
