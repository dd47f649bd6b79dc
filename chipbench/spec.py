"""Where the benchmark's files are, found by the names in BENCHMARK.json."""
from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def benchmark():
    return load_json("BENCHMARK.json")


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(there are: {', '.join(e['name'] for e in entries)})")


def cell(bench, workload):
    """(workload entry, configuration file, traffic file) of one cell."""
    wl = by_name(bench["workloads"], workload, "workload")
    cfg_entry = by_name(bench["configs"], wl["config"], "configuration")
    config = load_json(cfg_entry["file"])
    traffic = load_json("chipbench", "traffic", wl["traffic"] + ".json")
    return wl, config, traffic


def metrics_of(bench, workload, kind):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    whose ``workloads`` lists it, or that list none (every cell)."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def plugin(kind, name):
    """``chipbench/<kind>/<name>.py``, found by the name a data file gives."""
    return importlib.import_module(f"chipbench.{kind}.{name}")


def peaks(device_kind):
    table = load_json("chipbench", "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       "chipbench/peaks.json: add its published peaks with "
                       "their source, do not assume another chip's")
    return table[device_kind]
