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


def check_cut(entry, config, reference):
    """Refuse a configuration whose cut is not written down. ``entry`` is its
    entry in BENCHMARK.json, ``config`` its file, ``reference`` its reference
    module. ``reduced`` lists every key changed from the source, the same in
    both places, and may be empty. Where it is not, every key it names is a
    top-level key of the file (which holds the value as run), the file's
    ``published`` object gives the source's value of each, its ``deployment``
    is an object that says over how many chips each layer is divided
    (``chips_per_layer``) and ``how``, and no key is a width: one the
    reference module declares (``WIDTH_KEYS``: hidden, head, latent,
    feed-forward and expert sizes, experts per token, window and top-k
    sizes), or one that ends in ``_dim`` or ``_rank``."""
    name, reduced = entry["name"], config.get("reduced")
    if reduced != entry["reduced"] or not isinstance(reduced, list):
        raise ValueError(f"{name}: 'reduced' is {reduced} in the file and "
                         f"{entry['reduced']} in BENCHMARK.json")
    if not reduced:
        return
    published = config.get("published")
    if not isinstance(published, dict):
        raise ValueError(f"{name}: a cut configuration states the source's "
                         "values in a 'published' object")
    for key in reduced:
        if key in reference.WIDTH_KEYS or key.endswith(("_dim", "_rank")):
            raise ValueError(f"{name}: 'reduced' names the width {key!r}; "
                             "no width is ever cut")
        if key not in config:
            raise ValueError(f"{name}: 'reduced' names {key!r}, which is no "
                             "top-level key of the file")
        if key not in published:
            raise ValueError(f"{name}: 'published' lacks the source's value "
                             f"of {key!r}")
    dep = config.get("deployment")
    if not (isinstance(dep, dict) and isinstance(dep.get("how"), str)
            and dep["how"] and isinstance(dep.get("chips_per_layer"), int)
            and dep["chips_per_layer"] >= 1):
        raise ValueError(f"{name}: a cut configuration's 'deployment' is an "
                         "object with 'chips_per_layer' (a whole number) and "
                         "'how' (what of a layer each chip holds)")


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
