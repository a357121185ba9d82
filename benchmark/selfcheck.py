#!/usr/bin/env python3
"""Checks of the benchmark's own yardstick, on the CPU in seconds:

    python3 benchmark/selfcheck.py

* the trace reducer on the small recorded trace in ``benchmark/fixtures``
  gives the busy, idle and per-program times worked out by hand;
* each count function against a hand-worked shape;
* every name, unit and layer in ``BENCHMARK.json`` and in
  ``benchmark/metrics/*.json`` is made of the characters the driver takes,
  the two agree, and every file a name leads to is there.

Not part of the repo's tier-1 tests.  Exit code 0 and ``selfcheck ok`` mean
all of it held.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, CHECKOUT)

from benchmark.lib import manifest, xplane  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
}


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def close(a: float, b: float, what: str) -> None:
    check(abs(a - b) <= 1e-9 * max(1.0, abs(b)), f"{what}: {a} is not {b}")


def check_reducer() -> None:
    """The fixture: one device plane, a window of 1,000,000 ns between the
    markers.  Ops: conv 100,000-300,000; a fusion inside it 150,000-200,000
    (nested, adds no busy time); solve 500,000-900,000; one op that starts
    before the window, 0 (clipped from -50,000)-20,000.  Busy is 200,000 +
    400,000 + 20,000 = 620,000; idle 38%; gaps 20,000-100,000,
    300,000-500,000, 900,000-1,000,000."""
    with open(os.path.join(BENCH_DIR, "fixtures", "small_trace.json")) as f:
        plain = json.load(f)
    marks = xplane.markers(plain)
    check(len(marks) == 2, f"markers: {marks}")
    red = xplane.reduce_trace(plain)
    close(red["window_ns"], 1_000_000, "window")
    close(red["busy_ns"], 620_000, "busy")
    dev = red["devices"][0]
    check(
        dev["gaps"] == [(1_020_000.0, 1_100_000.0), (1_300_000.0, 1_500_000.0), (1_900_000.0, 2_000_000.0)],
        f"gaps: {dev['gaps']}",
    )
    close(dev["modules"]["jit___call__"], 200_000, "featurizer program time")
    close(dev["modules"]["jit__fused_bcd_impl"], 400_000, "solver program time")
    layers = xplane.layer_ns(
        dev["modules"],
        {"featurizers": [r"^jit___call__$"], "solvers": [r"^jit__fused_bcd"]},
    )
    close(layers["featurizers"], 200_000, "featurizers layer")
    close(layers["solvers"], 400_000, "solvers layer")
    close(layers["unmapped"], 20_000, "unmapped")
    spans = [("featurize", 1_000_000, 1_350_000), ("solve", 1_350_000, 1_950_000)]
    gaps = dict(xplane.attribute_gaps(dev["gaps"], spans))
    close(gaps["featurize"], 80_000e-9 + 0.0, "idle inside featurize (first gap)")
    close(gaps["solve"], 300_000e-9, "idle inside solve (the gap's middle decides)")
    close(xplane.union_ns([(0, 10), (5, 20), (30, 40)]), 30, "union")


def check_counts() -> None:
    cifar = manifest.load_module("counts", "cifar_rp")
    # rows 10, blocks 4 and 2 wide, 3 classes, 2 epochs:
    # grams 2*10*16 + 2*10*4 = 400; Choleskys 64/3 + 8/3 = 24;
    # a block and epoch 4*10*w*3 + 2*w*w*3: (480 + 96) * 2 + (240 + 24) * 2 = 1680
    got = cifar.bcd(10, [4, 2], 3, 2)
    close(got["flops"], 400 + 24 + 1680, "bcd flops")
    # bytes: blocks 4*10*w*(1 + 2*2) = 800 + 400; a block and epoch
    # 4*(2*10*3 + w*w): 2*4*76 + 2*4*64 = 1120
    close(got["bytes"], 1200 + 1120, "bcd bytes")
    conf = {
        "image_size": 8, "patch_size": 3, "num_channels": 2, "num_filters": 5,
        "pool_size": 4, "pool_stride": 3,
    }
    # 6x6 positions, patch 18 wide: 2*36*18*5 = 6480 an image; pools
    # ceil((6 - 2) / 3) = 2 a side: 2*2*2*5 = 40 features
    close(cifar.feature_width(conf), 40, "feature width")
    got = cifar.conv(conf, 7)
    close(got["flops"], 6480 * 7, "conv flops")
    close(got["bytes"], 4 * (7 * 128 + 90 + 7 * 40), "conv bytes")
    check(cifar.block_widths(10000, 4096) == [4096, 4096, 1808], "block widths")
    close(cifar.predict(10, 6, 3)["flops"], 360, "predict flops")
    timit = manifest.load_module("counts", "timit_rf")
    tconf = {"dimension": 4, "num_cosine_features": 8, "num_cosines": 3}
    got = timit.cosine(tconf, 10)
    close(got["flops"], 2 * 10 * 4 * 8 * 3, "cosine flops")
    close(got["bytes"], 4 * 3 * (40 + 32 + 80), "cosine bytes")


def check_names() -> None:
    bench = manifest.benchmark_json()
    check(
        set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
        f"BENCHMARK.json keys: {sorted(bench)}",
    )
    for section, allowed in ENTRY_KEYS.items():
        for entry in bench[section]:
            extra = set(entry) - allowed
            check(not extra, f"{section} {entry.get('name')}: keys {extra} are not taken")
            check(NAME.match(entry["name"]), f"{section}: name {entry['name']!r}")
    for path in bench["paths"]:
        check(PATH.match(path) and not path.startswith("/") and ".." not in path, f"path {path!r}")
    for word in bench["command"]:
        check(0 < len(word) <= 200 and "\t" not in word and "\n" not in word, f"command word {word!r}")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    check(len(names) == len(set(names)), "two metrics share a name")
    e2e = {m["name"] for m in bench["end_to_end"]}
    check("setup_s" in e2e, "no setup_s")
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    for conf in bench["configs"]:
        check(conf["file"].startswith(tuple(p + "/" for p in bench["paths"])), conf["file"])
        check(os.path.exists(os.path.join(CHECKOUT, conf["file"])), f"missing {conf['file']}")
        for key in conf["reduced"]:
            check(NAME.match(key), f"reduced key {key!r}")
            check(not re.search(r"(_dim|_rank|_size)$", key), f"reduced key {key!r} names a width")
        with open(os.path.join(CHECKOUT, conf["file"])) as f:
            body = json.load(f)
        check(sorted(body["reduced"]) == sorted(conf["reduced"]), f"{conf['name']}: reduced differs from its file")
        for key in ("source", "deployment", "precision", "expected_tier", "assumed", "pipeline", "limits"):
            check(key in body, f"{conf['file']} states no {key}")
        for kind in ("pipelines", "reference", "counts"):
            check(
                os.path.exists(os.path.join(BENCH_DIR, kind, body["pipeline"] + ".py")),
                f"{conf['name']}: no {kind}/{body['pipeline']}.py",
            )
        check(0 < len(conf["why"]) <= 200 and 0 < len(conf["source"]) <= 200, f"{conf['name']}: why or source too long")
    for cell in bench["workloads"]:
        check(cell["config"] in configs, f"{cell['name']}: config {cell['config']!r}")
        check(NAME.match(cell["traffic"]), f"traffic {cell['traffic']!r}")
        check(os.path.exists(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")), f"no traffic file for {cell['name']}")
        check(cell["chips"] in (1, 4) and 0 < len(cell["why"]) <= 200, f"{cell['name']}: chips or why")
    for m in bench["end_to_end"]:
        check(UNIT.match(m["unit"]), f"{m['name']}: unit {m['unit']!r}")
        check(m["better"] in ("lower", "higher") and 0 < m["bound"] <= 0.1, f"{m['name']}: better or bound")
        check(m["source"] in ("host_clock", "device_trace"), f"{m['name']}: source")
    for m in bench["per_layer"]:
        check(UNIT.match(m["unit"]), f"{m['name']}: unit {m['unit']!r}")
        check(NAME.match(m["layer"]), f"{m['name']}: layer {m['layer']!r} (a layer's name holds no space)")
        check(m["moves"] in e2e and m["source"] in SOURCES, f"{m['name']}: moves or source")
        check(set(m.get("workloads", ())) <= cells, f"{m['name']}: workloads")
        if "roofline" in m["name"] or "mfu" in m["name"]:
            check(m["unit"] == "%", f"{m['name']}: a share is in %")
        spec = manifest.load_json("metrics", m["name"] + ".json")
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            check(spec[key] == m[key], f"metrics/{m['name']}.json: {key} differs from BENCHMARK.json")
        check(spec.get("workloads") == m.get("workloads"), f"metrics/{m['name']}.json: workloads differ")
        check(os.path.exists(os.path.join(BENCH_DIR, "readers", spec["reader"] + ".py")), f"no reader {spec['reader']}")
    listed = {m["name"] for m in bench["per_layer"]}
    for path in glob.glob(os.path.join(BENCH_DIR, "metrics", "*.json")):
        check(os.path.basename(path)[:-5] in listed, f"{path} is no metric of BENCHMARK.json")
    for root, _, files in os.walk(BENCH_DIR):
        for name in files:
            rel = os.path.relpath(os.path.join(root, name), CHECKOUT)
            if "__pycache__" in rel:
                continue
            check(PATH.match(rel), f"file name {rel!r}")
    size = os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json"))
    check(size <= 64 * 1024, f"BENCHMARK.json is {size} bytes")
    runs = 2 + 14 * 24
    budget = runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    check(budget <= 43200, f"run_seconds {bench['run_seconds']}: a full check of 24 cells takes {budget} s")


def main() -> int:
    for part in (check_reducer, check_counts, check_names):
        part()
        print(f"{part.__name__} ok")
    print("selfcheck ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
