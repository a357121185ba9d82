"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration's file
names its pipeline; a metric's file names its reader.  Nothing is listed in
code, so a later PR adds a cell by adding files and one manifest entry.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module of its own."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark_json() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> dict:
    """The manifest's entry for the cell, with its configuration and
    traffic files read in."""
    manifest = benchmark_json()
    for entry in manifest["workloads"]:
        if entry["name"] == name:
            break
    else:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (has: {known})")
    conf_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    with open(os.path.join(CHECKOUT, conf_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", f"{entry['traffic']}.json")
    return {
        "name": name,
        "chips": entry["chips"],
        "config": config,
        "traffic": traffic,
        "manifest": manifest,
    }


def metrics_for(cell_name: str, manifest: dict, section: str) -> list:
    """Names of the manifest's metrics of ``section`` that this cell reports:
    those that list it under ``workloads`` or list nothing."""
    return [
        m["name"]
        for m in manifest[section]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def resized(block: dict, rehearsal: bool) -> dict:
    """A configuration or traffic file's sizes, with its ``rehearsal`` keys
    laid over them for a tiny CPU run."""
    out = {k: v for k, v in block.items() if k != "rehearsal"}
    if rehearsal:
        out.update(block.get("rehearsal", {}))
    return out
