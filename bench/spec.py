"""Finds each piece of the benchmark by its name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``   a model configuration, as it is run
* ``bench/traffic/<mix>.json``      a traffic mix (data for ``generator``)
* ``bench/cells/<workload>.json``   a cell's correctness limits
* ``bench/metrics/<metric>.py``     one reader per metric (``read(ctx)``)
* ``bench/references/<name>.py``    a configuration's plain reference

A later cell, mix, configuration or metric is a new file and a new entry;
nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    return _named(bm["workloads"], name, "workload")


def config(bm: dict, name: str, root: str = ROOT) -> dict:
    entry = _named(bm["configs"], name, "configuration")
    return _json(os.path.join(root, entry["file"]))


def mix(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "bench", "traffic", f"{name}.json"))


def cell(name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, "bench", "cells", f"{name}.json"))


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The chip's peaks; a kind the table lacks is an error."""
    table = _json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def _load(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: str = ROOT):
    """The module that reads metric ``name``: ``read(ctx)`` returns a
    number, or None where the run gave it nothing to read."""
    return _load(os.path.join(root, "bench", "metrics", f"{name}.py"),
                 "bench_metric_" + name.replace(".", "_").replace("-", "_"))


def reference(name: str, root: str = ROOT):
    return _load(os.path.join(root, "bench", "references", f"{name}.py"),
                 "bench_reference_" + name)


def metrics_for(bm: dict, workload_name: str, trace: bool) -> List[dict]:
    """The metrics one run reports: the end-to-end ones without tracing,
    the per-layer ones with it, each where its ``workloads`` (if any)
    lists this cell."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload_name in m["workloads"]]
