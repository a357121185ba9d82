#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It finds the cell in ``BENCHMARK.json``, the cell's
configuration and traffic files by their names, the configuration's
pipeline under ``benchmark/pipelines`` and each per-layer metric's reader
under ``benchmark/readers``: no cell, configuration, pipeline or metric is
named in this file.

Set-up makes the data from ``--seed`` and runs one whole warm-up fit at the
cell's own shapes; ``setup_s`` runs from this file's first line to the
window's start, less the seconds ``jax.devices()`` takes to bring the chip's
runtime up.  Then whole fits run back to back until ``--seconds`` have
passed; the window ends when the fit running at that moment completes.  After
the window the plain reference fits the same data and what the last timed fit
produced is compared with it.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
traced, ``breakdown``; the numbers compared come last, under ``compared``.

Off a TPU it exits 2 before any work, unless ``--rehearsal``: tiny sizes
through the same code, every line labelled, never ``correct: true``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark.lib import manifest, xplane  # noqa: E402
from benchmark.lib.compile_meter import CompileMeter  # noqa: E402
from benchmark.lib.roofline import peaks_for  # noqa: E402

WORK = os.path.join(CHECKOUT, ".bench_work")


class Refused(Exception):
    """The run cannot be a measurement: no result line, exit code 2."""


def say(rehearsal: bool, text: str) -> None:
    print(("REHEARSAL " if rehearsal else "") + text, file=sys.stderr, flush=True)


def look_for_chip(chips: int, rehearsal: bool) -> tuple:
    """The device as JAX reports it, and the seconds ``jax.devices()`` took
    to bring the chip's runtime up: nothing of the repo runs in them, they
    swing by seconds between processes of one code, and ``setup_s`` leaves
    them out."""
    import jax

    from keystone_tpu.utils.platform import init_device

    t0 = time.perf_counter()
    jax.devices()
    chip_start_s = time.perf_counter() - t0
    device = init_device()
    if rehearsal:
        return device, chip_start_s
    if device["platform"] != "tpu":
        raise Refused(
            f"platform is {device['platform']!r}, not a TPU: a benchmark run "
            "measures the chip or nothing (use --rehearsal for a CPU walk-through)"
        )
    if device["count"] < chips:
        raise Refused(f"the cell asks for {chips} chip(s), JAX found {device['count']}")
    return device, chip_start_s


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for dev in jax.devices():
        try:
            stats = dev.memory_stats() or {}
        except Exception:  # noqa: BLE001 - backends without stats
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def judge_fit(report: dict, expected_tier: str) -> str | None:
    """Why a completed fit counts as failed, or None."""
    if report["tier"] != expected_tier:
        return f"solver tier {report['tier']!r}, not {expected_tier!r}"
    if report["denials"]:
        return f"admission denials: {report['denials']}"
    if report["oom_retries"]:
        return f"OOM retries: {report['oom_retries']}"
    return None


class Tracer:
    """The profiler and the program's own host spans around the first fits
    of the window."""

    def __init__(self, fits: int, logdir: str):
        self.fits = fits
        self.logdir = logdir
        self.active = False
        self.stop_seconds = 0.0
        self.trace_bytes = 0
        self.spans = []
        self.marks = []  # perf_counter at each marker's start

    def start(self) -> None:
        import jax

        from keystone_tpu.core import trace as ktrace

        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        ktrace.reset()
        ktrace.enable(os.path.join(self.logdir, "host_spans.json"))
        self.active = True

    def marker(self, index: int):
        import jax

        self.marks.append(time.perf_counter())
        return jax.profiler.TraceAnnotation(xplane.MARKER, fit=index)

    def stop(self) -> None:
        import jax

        from keystone_tpu.core import trace as ktrace

        t0 = time.perf_counter()
        offset = time.perf_counter() - ktrace.now_us() / 1e6
        self.spans = [
            (e["name"], e["ts"] / 1e6 + offset, (e["ts"] + e["dur"]) / 1e6 + offset)
            for e in ktrace.events()
            if e.get("ph") == "X" and e.get("cat") == "stage"
        ]
        ktrace.disable()
        ktrace.reset()
        jax.profiler.stop_trace()
        self.active = False
        self.stop_seconds = time.perf_counter() - t0

    def reduce(self, patterns: dict, dump: str | None = None) -> dict:
        path = xplane.find_xplane(self.logdir)
        self.trace_bytes = os.path.getsize(path)
        plain = xplane.plain_from_xplane(path)
        if dump:
            os.makedirs(os.path.dirname(os.path.abspath(dump)), exist_ok=True)
            with open(dump, "w") as f:
                json.dump(plain, f)
        reduced = xplane.reduce_trace(plain)
        marks = xplane.markers(plain)
        # the host spans are on perf_counter's clock; the first marker is on both
        shift = marks[0][1] - self.marks[0] * 1e9
        spans = [(n, s * 1e9 + shift, e * 1e9 + shift) for n, s, e in self.spans]
        first = reduced["devices"][0]
        modules = {}
        for dev in reduced["devices"]:
            for name, ns in dev["modules"].items():
                modules[name] = modules.get(name, 0.0) + ns / len(reduced["devices"])
        reduced["layers_ns"] = xplane.layer_ns(modules, patterns)
        reduced["modules_top"] = xplane.top(modules, 12)
        ops = xplane.top(first["ops"], 9)
        ops.append(["unmapped_programs", reduced["layers_ns"]["unmapped"] / 1e9])
        reduced["breakdown"] = {
            "device_ops": ops,
            "idle_gaps": xplane.attribute_gaps(first["gaps"], spans, 10),
        }
        return reduced


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    rehearsal: bool = False,
    chip_check: bool = True,
    wrap_fit=None,
    wrap_produced=None,
    dump_trace: str | None = None,
) -> dict:
    """One run of one cell; returns the result line's object.  ``chip_check``,
    ``wrap_fit`` and ``wrap_produced`` exist for the tests under
    ``benchmark/tests``: they skip the look for a chip and break the timed
    path underneath (``benchmark/lib/faults.py``)."""
    cell = manifest.cell(workload)
    conf = manifest.resized(cell["config"], rehearsal)
    traffic = manifest.resized(cell["traffic"], rehearsal)
    for key, value in conf.get("env", {}).items():
        os.environ[key] = value

    if chip_check:
        device, chip_start_s = look_for_chip(cell["chips"], rehearsal)
    else:
        from keystone_tpu.utils.platform import describe_device

        device, chip_start_s = describe_device(), 0.0
    import jax

    phases = {
        "imports": time.perf_counter() - T_START - chip_start_s,
        "chip_start": chip_start_s,
    }
    if device["platform"] != "cpu":
        # every program into the persistent cache, however quick its compile,
        # wherever the cache lies: the next run of the cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    meter = CompileMeter()
    pipeline = manifest.load_module("pipelines", conf["pipeline"])
    datagen = manifest.load_module("datagen", pipeline.DATAGEN)
    reference = manifest.load_module("reference", pipeline.REFERENCE)
    counts = manifest.load_module("counts", pipeline.COUNTS)
    rows = traffic["rows"]
    prog_seed = pipeline.program_seed(seed)
    fit_fn = wrap_fit(pipeline.fit) if wrap_fit else pipeline.fit

    work = os.path.join(WORK, workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work, exist_ok=True)

    data = pipeline.place_data(datagen.generate(conf["data"], rows, seed))
    jax.block_until_ready(data)
    phases["data"] = time.perf_counter() - T_START - sum(phases.values())
    warm = fit_fn(conf, data, prog_seed, os.path.join(work, "warm"))
    why = judge_fit(pipeline.fit_report(warm), conf["expected_tier"])
    if why:
        say(rehearsal, f"warm-up fit: {why}")
    del warm
    gc.collect()

    tracer = Tracer(traffic.get("trace_fits", 2), os.path.join(work, "trace")) if trace else None
    compile_before = meter.read()
    setup_compile = dict(compile_before)
    if tracer:
        tracer.start()  # part of set-up: the window is fits only
    phases["warm_fit"] = time.perf_counter() - T_START - sum(phases.values())
    setup_s = time.perf_counter() - T_START - chip_start_s

    # -- the window ------------------------------------------------------------
    walls, ends, untraced_walls, failures, last = [], [], [], [], None
    attempted = 0
    t_win = time.perf_counter()
    while True:
        index = attempted
        attempted += 1
        stem = os.path.join(work, f"fit_{index % 2}")
        traced = bool(tracer and tracer.active)
        t0 = time.perf_counter()
        try:
            if traced:
                with tracer.marker(index):
                    out = fit_fn(conf, data, prog_seed, stem)
            else:
                out = fit_fn(conf, data, prog_seed, stem)
            why = judge_fit(pipeline.fit_report(out), conf["expected_tier"])
        except Exception as e:  # noqa: BLE001 - a failed fit is counted, not fatal
            out, why = None, f"{type(e).__name__}: {e}"
        wall = time.perf_counter() - t0
        if why:
            failures.append(f"fit {index}: {why}")
        else:
            walls.append(wall)
            ends.append(t0 + wall - t_win - (tracer.stop_seconds if tracer else 0.0))
            if not traced:
                untraced_walls.append(wall)
            last = out
        del out
        if tracer and tracer.active and attempted >= tracer.fits:
            tracer.stop()
        if time.perf_counter() - t_win - (tracer.stop_seconds if tracer else 0.0) >= seconds:
            break
    if tracer and tracer.active:
        tracer.stop()
    window_s = time.perf_counter() - t_win - (tracer.stop_seconds if tracer else 0.0)
    compile_in_window = CompileMeter.between(compile_before, meter.read())
    peak = memory_peak_bytes()

    # -- what the last timed fit produced, against the reference ------------------
    completed = len(walls)
    numbers, observed = {}, {}
    correct = False
    if last is not None:
        produced = (wrap_produced(pipeline.produced) if wrap_produced else pipeline.produced)(
            last, conf, data, seed
        )
        del last
        gc.collect()
        t_ref = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            ref = reference.fit(conf, data, prog_seed, "highest")
            values = reference.compare(conf, data, seed, produced, ref)
        reference_s = time.perf_counter() - t_ref
        limits = conf.get("limits", {})
        for name, value in values.items():
            if name in limits:
                ok = math.isfinite(value) and value <= limits[name]
                numbers[name] = {"value": value, "limit": limits[name], "ok": ok}
            else:
                observed[name] = value
        observed["reference_s"] = reference_s
        observed["test_error_pct"] = float(produced["test_error"])
        correct = bool(numbers) and all(n["ok"] for n in numbers.values())

    # -- metrics ---------------------------------------------------------------
    manifest_json = cell["manifest"]
    units = {
        m["name"]: m["unit"]
        for m in manifest_json["end_to_end"] + manifest_json["per_layer"]
    }
    fit_counts = counts.fit(conf, rows)
    values = {}
    device_out = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if not trace:
        values["setup_s"] = setup_s
        if completed:
            values["fit_examples_per_s"] = rows["train"] * completed / window_s
        wanted = manifest.metrics_for(workload, manifest_json, "end_to_end")
    else:
        ctx = {
            "cell": workload,
            "chips": cell["chips"],
            "conf": conf,
            "rows": rows,
            "fits_completed": completed,
            "window_s": window_s,
            "untraced_walls": untraced_walls,
            "compile_in_window": compile_in_window,
            "memory_peak_bytes": peak,
            "fit_flops": fit_counts["total_flops"],
            "kernels": counts.kernels(conf, rows),
            "peaks": peaks_for(device["kind"]) if not rehearsal else None,
            "trace": None,
            "traced_fits": 0,
        }
        try:
            ctx["trace"] = tracer.reduce(pipeline.PROGRAMS, dump_trace)
            ctx["traced_fits"] = min(tracer.fits, attempted)
            device_out["busy_s"] = ctx["trace"]["busy_ns"] / 1e9
            device_out["window_s"] = ctx["trace"]["window_ns"] / 1e9
            breakdown = ctx["trace"]["breakdown"]
            observed["layers_s"] = {
                k: v / 1e9 for k, v in ctx["trace"]["layers_ns"].items()
            }
            observed["modules_top"] = ctx["trace"]["modules_top"]
            observed["trace_stop_s"] = tracer.stop_seconds
            observed["trace_bytes"] = tracer.trace_bytes
        except (FileNotFoundError, ValueError) as e:
            say(rehearsal, f"trace: {e}")
        wanted = manifest.metrics_for(workload, manifest_json, "per_layer")
        for name in wanted:
            spec = manifest.load_json("metrics", f"{name}.json")
            reader = manifest.load_module("readers", spec["reader"])
            value = reader.read(spec, ctx)
            if value is not None:
                values[name] = value
        observed.update(ctx.get("notes", {}))
    shutil.rmtree(work, ignore_errors=True)

    observed.update(
        fit_wall_median_s=statistics.median(walls) if walls else None,
        fit_wall_min_s=min(walls) if walls else None,
        fit_wall_max_s=max(walls) if walls else None,
        fits_completed=completed,
        fit_ends_s=[round(e, 3) for e in ends],
        window_s=window_s,
        setup_phases_s=phases,
        setup_compile_s=setup_compile["seconds"],
        setup_compile_requests=setup_compile["requests"],
        setup_cache_hits=setup_compile["hits"],
        compile_requests_in_window=compile_in_window["requests"],
        fit_tflop=fit_counts["total_flops"] / 1e12,
        failures=failures[:5],
    )
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": len(failures),
        "metrics": {
            n: {"value": values[n], "unit": units[n]} for n in wanted if n in values
        },
        "device": device_out,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["observed"] = observed
    result["compared"] = {
        n: {"value": v["value"], "limit": v["limit"]} for n, v in numbers.items()
    }
    return result


def finite(obj):
    """The object with every float that JSON cannot carry turned to None."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true")
    p.add_argument("--dump-trace", default=None, help="write the traced run's plain trace here")
    a = p.parse_args(argv)
    try:
        result = run_cell(
            a.workload, a.seed, a.seconds, bool(a.trace), rehearsal=a.rehearsal,
            dump_trace=a.dump_trace,
        )
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    compared = result["compared"]
    if a.rehearsal:
        result["rehearsal"] = True
        result["rehearsal_correct"] = result["correct"]
        result["correct"] = False
        result["compared"] = result.pop("compared")  # keep it last
    for name, n in compared.items():
        verdict = "ok" if n["value"] <= n["limit"] else "OVER"  # NaN is over
        say(a.rehearsal, f"compared {name} = {n['value']:.6g} (limit {n['limit']:.6g}) {verdict}")
    say(a.rehearsal, f"correct = {result['correct']}")
    line = json.dumps(finite(result))
    print(("REHEARSAL " if a.rehearsal else "") + line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
