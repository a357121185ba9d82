"""RandomPatchCifar across the chips of one host: whole fits through
``keystone_tpu.workloads.cifar_random_patch.run(conf, train, test, mesh=)``,
the entry ``--mesh`` reaches, on the mesh the configuration names (``"4"``:
rows over a 4-way data axis).  Everything but the mesh is
``pipelines/cifar_rp.py``'s, loaded from that file."""

from __future__ import annotations

import glob
import os

from benchmark.lib.manifest import load_module

_one_chip = load_module("pipelines", "cifar_rp")

REFERENCE = "cifar_rp_mesh"
COUNTS = "cifar_rp_mesh"
DATAGEN = _one_chip.DATAGEN

#: the one-chip pipeline's programs under their own names (an eager
#: operation on a sharded array keeps its name), and what only a mesh
#: runs: the chunks' local concatenation; the copies that bring a model
#: block and its means, which the solve left on one layout, to every chip
#: for the evaluator's eager apply (``jit_broadcast_in_dim``: 12.5 ms of
#: device time a fit, my chip run, PR 32); the confusion matrix's scatter
PROGRAMS = {
    "featurizers": _one_chip.PROGRAMS["featurizers"] + [r"^jit__join_local_rows$"],
    "solvers": _one_chip.PROGRAMS["solvers"],
    "evaluation": _one_chip.PROGRAMS["evaluation"]
    + [r"^jit_broadcast_in_dim$", r"^jit_scatter-add$"],
}

program_seed = _one_chip.program_seed
place_data = _one_chip.place_data
fit_report = _one_chip.fit_report
produced = _one_chip.produced


def require_mesh_kernel_form() -> None:
    """The configuration states the conv featurizer's kernel form on every
    chip.  A program whose featurizer has no form for a data mesh fits on
    the XLA form's bfloat16 activations and serves the saved chain, on one
    device, through the kernel form: on the chip its answers fail the
    comparison (``scores_rms_gap`` 0.39 against a limit of 0.008, at 1.28 s
    a fit; my chip run, PR 32, the parent commit under this file).  Such a
    program cannot run this configuration, and says so before any work."""
    from keystone_tpu.ops.conv_fused import FusedConvFeaturizer

    if not hasattr(FusedConvFeaturizer, "_sharded_kernel_form"):
        raise SystemExit(
            "this program's conv featurizer has no kernel form under a data "
            "mesh (ops/conv_fused.FusedConvFeaturizer._sharded_kernel_form): "
            "it cannot run cifar_rp_10k_mesh4 as the configuration states it"
        )


def fit(conf: dict, data: dict, seed: int, stem: str) -> dict:
    from keystone_tpu.loaders.cifar import LabeledImageBatch
    from keystone_tpu.parallel.mesh import parse_mesh
    from keystone_tpu.workloads import cifar_random_patch as cifar

    require_mesh_kernel_form()
    for old in glob.glob(stem + ".*"):
        os.remove(old)
    rc = cifar.RandomCifarConfig(
        num_filters=conf["num_filters"],
        patch_size=conf["patch_size"],
        patch_steps=conf["patch_steps"],
        pool_size=conf["pool_size"],
        pool_stride=conf["pool_stride"],
        alpha=conf["alpha"],
        lam=conf["lam"],
        seed=program_seed(seed),
        num_classes=conf["num_classes"],
        whitener_size=conf["whitener_size"],
        featurize_chunk=conf["featurize_chunk"],
        pipeline_file=stem,
    )
    train = LabeledImageBatch(data["train"]["x"], data["train"]["y"])
    test = LabeledImageBatch(data["test"]["x"], data["test"]["y"])
    results = cifar.run(rc, train, test, mesh=parse_mesh(conf["mesh"]))
    return {"results": results, "stem": stem, "rows": len(train)}
