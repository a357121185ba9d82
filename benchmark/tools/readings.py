#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, on the chip at
the cell's own size, in one process:

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1 2 3 ... \
        --control-seeds 3 --fault-seeds 3

For each seed: one fit through the pipeline's timed entry, the plain
reference on the same data, and every number of the comparison (the lower
readings).  For the first ``--control-seeds`` seeds also the control, the
reference in the precision below the configuration's, put in the program's
place (the upper readings); for the first ``--fault-seeds`` the planted
faults.  One JSON line a reading on standard output.
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
from benchmark.lib import faults, manifest  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--fault-seeds", type=int, default=0)
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
    work = os.path.join(bench.WORK, a.workload + ".readings")
    os.makedirs(work, exist_ok=True)
    control = conf["compare"]["control_precision"]

    def emit(kind, seed, values, **more):
        print(json.dumps(bench.finite(dict(
            kind=kind, workload=a.workload, seed=seed, device=device["kind"],
            rehearsal=a.rehearsal, values=values, **more))), flush=True)

    def program(fit, seed, data, produced_fn=None):
        out = fit(conf, data, pipeline.program_seed(seed), os.path.join(work, "fit"))
        report = pipeline.fit_report(out)
        got = (produced_fn or pipeline.produced)(out, conf, data, seed)
        del out
        gc.collect()
        return got, report

    for i, seed in enumerate(a.seeds):
        data = pipeline.place_data(datagen.generate(conf["data"], traffic["rows"], seed))
        prog_seed = pipeline.program_seed(seed)
        t0 = time.perf_counter()
        got, report = program(pipeline.fit, seed, data)
        t1 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            ref = reference.fit(conf, data, prog_seed, "highest")
            t2 = time.perf_counter()
            emit("program", seed, reference.compare(conf, data, seed, got, ref),
                 report=report, fit_s=t1 - t0, reference_s=t2 - t1,
                 test_error=got["test_error"], ref_test_error=ref["test_error"])
            if i < a.control_seeds:
                for prec in (control, "bf16"):
                    ctl = reference.fit(conf, data, prog_seed, prec)
                    emit("control_" + prec, seed, reference.compare(conf, data, seed, ctl, ref),
                         test_error=ctl["test_error"])
                    del ctl
        # the faults run as the program runs, outside the reference's precision
        if i < a.fault_seeds:
            got, _ = program(faults.half_batch(pipeline.fit), seed, data)
            emit("fault_half_batch", seed, reference.compare(conf, data, seed, got, ref))
            with faults.state_unchanged():
                got, _ = program(pipeline.fit, seed, data)
            emit("fault_state_unchanged", seed, reference.compare(conf, data, seed, got, ref))
            got, _ = program(pipeline.fit, seed, data, faults.answer_altered(pipeline.produced))
            emit("fault_answer_altered", seed, reference.compare(conf, data, seed, got, ref))
        del data, got, ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
