#!/usr/bin/env python3
"""The readings behind the limits of ``correct`` for a cell whose reference
cannot make a whole fit of its own at the cell's size, on the chip, in one
process:

    python3 benchmark/tools/stage_control.py --workload <cell> --seeds 1 2 3

For each seed: the data, one fit through the pipeline's timed entry, what it
produced, and every number of the comparison (``kind`` ``program``: the lower
readings).  Then the control stage by stage: the reference's ``control``
replaces each stage's output with what the reference makes of the same
upstream in the configuration's ``control_precision``, and the same
comparison reads it (``kind`` ``control_<precision>``: the upper readings).
One JSON line a reading on standard output.  ``readings.py`` is the tool for
cells whose reference can fit alone.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, CHECKOUT)

from benchmark import run as bench  # noqa: E402
from benchmark.lib import manifest  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--rehearsal", action="store_true")
    a = p.parse_args(argv)

    cell = manifest.cell(a.workload)
    conf = manifest.resized(cell["config"], a.rehearsal)
    traffic = manifest.resized(cell["traffic"], a.rehearsal)
    for key, value in conf.get("env", {}).items():
        os.environ[key] = value
    device, _ = bench.look_for_chip(cell["chips"], a.rehearsal)
    import jax

    pipeline = manifest.load_module("pipelines", conf["pipeline"])
    datagen = manifest.load_module("datagen", pipeline.DATAGEN)
    reference = manifest.load_module("reference", pipeline.REFERENCE)
    work = os.path.join(bench.WORK, a.workload + ".control")
    os.makedirs(work, exist_ok=True)
    precision = conf["compare"]["control_precision"]
    limits = conf.get("limits", {})

    def emit(kind, seed, values, **more):
        over = sorted(n for n, v in values.items() if n in limits and not v <= limits[n])
        print(json.dumps(bench.finite(dict(
            kind=kind, workload=a.workload, seed=seed, device=device["kind"],
            rehearsal=a.rehearsal, over_limit=over, values=values, **more))), flush=True)

    for seed in a.seeds:
        data = pipeline.place_data(datagen.generate(conf["data"], traffic["rows"], seed))
        t0 = time.perf_counter()
        out = pipeline.fit(conf, data, pipeline.program_seed(seed), os.path.join(work, "fit"))
        t1 = time.perf_counter()
        got = pipeline.produced(out, conf, data, seed)
        del out
        gc.collect()
        t2 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            emit("program", seed, reference.compare(conf, data, seed, got, {}),
                 fit_s=t1 - t0, produced_s=t2 - t1, compare_s=time.perf_counter() - t2)
            t3 = time.perf_counter()
            ctl = reference.control(conf, data, got, precision)
            t4 = time.perf_counter()
            emit("control_" + precision, seed, reference.compare(conf, data, seed, ctl, {}),
                 control_s=t4 - t3, compare_s=time.perf_counter() - t4)
        del data, got, ctl
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
