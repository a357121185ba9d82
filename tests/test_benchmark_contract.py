"""Every name the benchmark reads out of the program still exists in it.

``benchmark/`` finds the program's work by name: device programs by
``^jit_<function>`` patterns (``pipelines/*.py`` ``PROGRAMS``), stage walls
by ``stage_timer`` names (``metrics/*.json`` ``stages``), counters and
histogram families by their registry names.  A rename in the package does
not fail there: the reader returns ``None``, and on the chip a per-layer
metric that had a value turns ``null``.  Each case below fails here instead,
on the CPU, in seconds: it checks that ``benchmark/`` still asks for the
name (so this table cannot go stale in silence) and that the package still
has it.  Nothing is trained.
"""

import ast
import functools
import importlib
import inspect
import os
import re

import numpy as np
import pytest

from benchmark.lib import manifest
from keystone_tpu.core import trace
from keystone_tpu.core.logging import stage_timer

#: ``PROGRAMS`` patterns that name a jitted function of the package (the
#: rest name jax's own eager programs): pattern -> module that holds it.
PROGRAMS = {
    r"^jit___call": "keystone_tpu.workloads.cifar_random_patch",
    r"^jit_sharded_moments_jit$": "keystone_tpu.parallel.collectives",
    r"^jit__fused_bcd_impl$": "keystone_tpu.solvers.block",
    r"^jit__bcd_": "keystone_tpu.solvers.block",
    r"^jit__hs_block": "keystone_tpu.solvers.block",
    r"^jit__describe_chunk": "keystone_tpu.workloads.fv_common",
    r"^jit__sample_chunk": "keystone_tpu.workloads.fv_common",
    r"^jit__encode_chunk": "keystone_tpu.workloads.fv_common",
    r"^jit__gather_samples": "keystone_tpu.workloads.fv_common",
    r"^jit__em_fit": "keystone_tpu.solvers.gmm",
    r"^jit__describe_lcs_chunk": "keystone_tpu.workloads.fv_common",
    r"^jit__prepare_rows": "keystone_tpu.workloads.imagenet_sift_lcs_fv",
    r"^jit__fused_bwls": "keystone_tpu.solvers.weighted",
    r"^jit__class_solves": "keystone_tpu.solvers.weighted",
    r"^jit__split_bwls_impl$": "keystone_tpu.solvers.weighted",
    r"^jit__block_apply": "keystone_tpu.solvers.block",
    r"^jit__block_moments$": "keystone_tpu.solvers.block",
    r"^jit__make_block$": "keystone_tpu.solvers.block",
    r"^jit__hold_blocks$": "keystone_tpu.solvers.block",
    r"^jit__block_step$": "keystone_tpu.solvers.block",
    r"^jit__draw_cosine_blocks$": "keystone_tpu.workloads.timit",
    r"^jit__draw_sign_blocks$": "keystone_tpu.workloads.mnist_random_fft",
    r"^jit__confusion_counts$": "keystone_tpu.evaluation.multiclass",
}

#: pipeline of the cell -> the workload module its window drives, and the
#: stages of that workload a metric names.  (``timit_rf`` drives a copy of
#: ``timit.run``'s calls kept in ``benchmark/pipelines``: its stages are
#: not the package's.)
STAGES = {
    "cifar_rp": (
        "keystone_tpu.workloads.cifar_random_patch",
        ["learn_filters", "warm_featurizer", "featurize", "scale",
         "featurize_test", "solve", "eval"],
    ),
    "cifar_rp_mesh": (
        "keystone_tpu.workloads.cifar_random_patch",
        ["learn_filters", "warm_featurizer", "featurize", "scale",
         "featurize_test", "solve", "eval"],
    ),
    "voc_fv": (
        "keystone_tpu.workloads.voc_sift_fisher",
        ["sample_descriptors", "pca", "gmm", "featurize",
         "featurize_test", "solve", "eval"],
    ),
    "imagenet_fv": (
        "keystone_tpu.workloads.imagenet_sift_lcs_fv",
        ["sample_descriptors", "featurize", "featurize_test", "solve", "eval"],
    ),
    "imagenet_fv_mesh": (
        "keystone_tpu.workloads.imagenet_sift_lcs_fv",
        ["featurize", "featurize_test", "solve", "eval"],
    ),
    "timit_rf_full": (  # drives ``timit.run`` itself
        "keystone_tpu.workloads.timit", ["featurize", "solve", "eval"],
    ),
    "mnist_fft": (  # drives ``mnist_random_fft.run`` itself
        "keystone_tpu.workloads.mnist_random_fft", ["featurize", "solve", "eval"],
    ),
}

COUNTERS = {
    "fv.descriptor_passes": "keystone_tpu.workloads.voc_sift_fisher",
    "gmm.iterations": "keystone_tpu.workloads.voc_sift_fisher",
    "mesh.psum_bytes": "keystone_tpu.parallel.collectives",
    "bwls.regroup_bytes": "keystone_tpu.solvers.weighted",
    "bcd.block_rows_made": "keystone_tpu.solvers.block",
    "bcd.block_rows_applied": "keystone_tpu.solvers.block",
}

HISTOGRAMS = ["stage_ms", "stage_wait_ms", "stage_h2d_mb"]

#: the family beneath a stage that ``readers/host_sections.py`` cuts by
#: prefix: its constant there -> a name a probed stage must record
SECTION_FAMILIES = {
    "HOST": "stage_host_ms.contract_probe.dispatch",
    "COUNT": "stage_host_n.contract_probe.dispatch",
    "LONGEST": "stage_max_ms.contract_probe.wait",
}

CASES = (
    [("program", p, m) for p, m in PROGRAMS.items()]
    + [
        ("stage", f"{pipeline}:{s}", module)
        for pipeline, (module, stages) in STAGES.items() for s in stages
    ]
    + [("counter", c, m) for c, m in COUNTERS.items()]
    + [("histogram", h, "keystone_tpu.core.trace") for h in HISTOGRAMS]
    + [("section_family", f, "keystone_tpu.core.trace") for f in SECTION_FAMILIES]
)


@functools.lru_cache(maxsize=None)
def _metrics() -> tuple:
    return tuple(
        manifest.load_json("metrics", name)
        for name in sorted(os.listdir(os.path.join(manifest.BENCH_DIR, "metrics")))
        if name.endswith(".json")
    )


@functools.lru_cache(maxsize=None)
def _pipelines_of_cells() -> dict:
    """``{cell: pipeline}`` from BENCHMARK.json and the configs' files."""
    return {
        w["name"]: manifest.cell(w["name"])["config"]["pipeline"]
        for w in manifest.benchmark_json()["workloads"]
    }


@functools.lru_cache(maxsize=None)
def _asked_programs() -> frozenset:
    """Every pattern of every pipeline's ``PROGRAMS``."""
    return frozenset(
        p
        for name in set(_pipelines_of_cells().values())
        for ps in manifest.load_module("pipelines", name).PROGRAMS.values()
        for p in ps
    )


def _program_names(module) -> set:
    """``jit_<name>`` of every jitted function the module holds: the name
    its compiled program carries in a device trace."""
    return {
        "jit_" + getattr(v, "__name__", "")
        for v in vars(module).values()
        if callable(v) and hasattr(v, "lower") and hasattr(v, "__wrapped__")
    }


def _literal_first_args(module, callee: str) -> set:
    """First arguments, where they are string literals, of every call of
    ``callee`` (a name or a dotted attribute's tail) in the module."""
    tree = ast.parse(inspect.getsource(module))
    out = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        name = ast.unparse(node.func)
        first = node.args[0]
        if (name == callee or name.endswith("." + callee)) and isinstance(
            first, ast.Constant
        ) and isinstance(first.value, str):
            out.add(first.value)
    return out


def _check_program(pattern, module):
    assert pattern in _asked_programs(), (
        f"no PROGRAMS list asks for {pattern} any more"
    )
    names = _program_names(importlib.import_module(module))
    assert any(re.search(pattern, n) for n in names), (
        f"{module} holds no jitted function whose program {pattern} finds; "
        f"it holds {sorted(names)}"
    )


def _check_stage(name, module):
    pipeline, stage = name.split(":")
    cells = {c for c, p in _pipelines_of_cells().items() if p == pipeline}
    assert cells, f"no cell drives the pipeline {pipeline}"
    asked = {
        s
        for m in _metrics()
        if cells & set(m.get("workloads") or cells)
        for s in (m.get("stages") or [])
    }
    assert stage in asked, (
        f"no metric of {sorted(cells)} lists the stage {stage} any more"
    )
    timers = _literal_first_args(importlib.import_module(module), "stage_timer")
    assert stage in timers, (
        f"{module} has no stage_timer({stage!r}); it has {sorted(timers)}"
    )


def _check_counter(name, module):
    read = {m.get("counter") for m in _metrics()} | {
        c for m in _metrics() for c in m.get("counters", ())
    }
    assert name in read, (
        f"no metric reads the counter {name} any more"
    )
    incs = _literal_first_args(importlib.import_module(module), "metrics.inc")
    assert name in incs, f"{module} counts {sorted(incs)}, not {name}"


def _check_histogram(name, _module):
    kinds = manifest.load_module("readers", "stage_samples").KINDS
    asked = {m.get("hist") for m in _metrics()} | set(kinds)
    assert name in asked, f"no reader takes the histograms {name}.* any more"
    with stage_timer("contract_probe"):
        trace.wait(np.zeros(1), "contract_probe")
        with trace.h2d("contract_probe", 8):
            pass
    hists = trace.metrics.snapshot()["histograms"]
    assert f"{name}.contract_probe" in hists, (
        f"a stage records {sorted(h for h in hists if 'contract_probe' in h)}"
    )


def _check_section_family(constant, _module):
    prefix = getattr(manifest.load_module("readers", "host_sections"), constant)
    recorded = SECTION_FAMILIES[constant]
    assert recorded.startswith(prefix), f"the reader cuts {prefix}*, not {recorded}"
    kinds = manifest.load_module("readers", "stage_samples").KINDS
    assert not prefix.startswith(tuple(k + "." for k in kinds)), (
        f"{prefix} would read as a stage of one of {kinds}"
    )
    with stage_timer("contract_probe"):
        trace.wait(np.zeros(1), "contract_probe")
        with trace.host("dispatch", "contract_probe"):
            pass
    hists = trace.metrics.snapshot()["histograms"]
    assert recorded in hists, (
        f"a stage records {sorted(h for h in hists if 'contract_probe' in h)}"
    )


_CHECKS = {
    "program": _check_program,
    "stage": _check_stage,
    "counter": _check_counter,
    "histogram": _check_histogram,
    "section_family": _check_section_family,
}


@pytest.mark.parametrize(
    "kind,name,module", CASES, ids=[f"{k}:{n}" for k, n, _ in CASES]
)
def test_benchmark_reads_a_name_the_program_has(kind, name, module):
    _CHECKS[kind](name, module)
