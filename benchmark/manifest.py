"""Finds everything a cell needs by the names in ``BENCHMARK.json``: the
cell's entry, its traffic file, its configuration file and plain reference,
its runner, and the per-layer metrics that list it.  A later PR adds files
and entries; nothing here names a cell, a configuration or a metric."""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """A module from a file path (file names here may hold ``-`` and ``.``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_json() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json "
                     f"(have: {', '.join(e['name'] for e in entries)})")


def cell(name: str) -> dict:
    """One cell: its BENCHMARK.json entry, traffic parameters, configuration
    and the metrics it has to report."""
    bj = benchmark_json()
    entry = _by_name(bj["workloads"], name, "workload")
    conf_entry = _by_name(bj["configs"], entry["config"], "config")
    traffic = _json(os.path.join(BENCH, "workloads",
                                 entry["traffic"] + ".json"))
    limits = (_json(os.path.join(ROOT, traffic["limits"]))
              if traffic.get("limits") else {})

    def wanted(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name,
        "chips": entry["chips"],
        "traffic": traffic,
        "limits": limits,
        "config": _json(os.path.join(ROOT, conf_entry["file"])),
        "end_to_end": [m for m in bj["end_to_end"] if wanted(m)],
        "per_layer": [m for m in bj["per_layer"] if wanted(m)],
    }


def reference(config: dict):
    """The configuration's plain reference, which also owns its shapes: the
    feed (``batch``), ``real_tokens`` and ``step_flops``."""
    return load_module(os.path.join(ROOT, config["reference"]),
                       "bench_reference")


def program(config: dict):
    """The file that builds the system under test for a configuration."""
    return load_module(os.path.join(ROOT, config["program"]),
                       "bench_program")


def runner(kind: str):
    return load_module(os.path.join(BENCH, "runners", kind + ".py"),
                       "bench_runner_" + kind)


def layer_metric_reader(name: str):
    """``(read, args)`` of one per-layer metric: ``layer_metrics/<name>.json``
    names the reader file beside it and the arguments it is called with."""
    spec = _json(os.path.join(BENCH, "layer_metrics", name + ".json"))
    mod = load_module(os.path.join(BENCH, "layer_metrics", "readers",
                                   spec["reader"] + ".py"),
                      "bench_reader_" + spec["reader"])
    return mod.read, spec.get("args", {})


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"benchmark: no published peaks for device kind "
                         f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]
