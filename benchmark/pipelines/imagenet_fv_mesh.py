"""ImageNetSiftLcsFV across the chips of one host: whole fits through
``keystone_tpu.workloads.imagenet_sift_lcs_fv.run(conf, train, test, mesh=)``,
the entry ``--mesh`` reaches, on the mesh the configuration names (``"4"``:
every chunk's images over a 4-way data axis, the classes split over the
same axis in the weighted solve).  The data, the configuration's fields and
the fit's report are ``pipelines/imagenet_fv.py``'s, loaded from that file;
what a fit does not keep is made again, as there, by the program's chunk
helpers on the same mesh."""

from __future__ import annotations

import glob
import os

import numpy as np

from benchmark.lib.manifest import load_module

_one_chip = load_module("pipelines", "imagenet_fv")

REFERENCE = "imagenet_fv_mesh"
COUNTS = "imagenet_fv_mesh"
DATAGEN = _one_chip.DATAGEN

#: the one-chip pipeline's programs under their own names (an eager
#: operation on a sharded array keeps its name), and what only a mesh runs:
#: the class regroup's all_to_all and the class-split solve.  ``wsolve``
#: names the class-split solve alone, for the readers that take one
#: program's time chip by chip (the layer ``solvers`` takes it first).
PROGRAMS = {
    **_one_chip.PROGRAMS,
    "solvers": _one_chip.PROGRAMS["solvers"] + [r"^jit__split_bwls_impl$", r"^jit__regroup_rows$"],
    "wsolve": [r"^jit__split_bwls_impl$"],
}

program_seed = _one_chip.program_seed
place_data = _one_chip.place_data
fit_report = _one_chip.fit_report


def require_mesh_forms() -> None:
    """The configuration states each chip's share of the work: SIFT's
    assembly and the Fisher vector's statistics as their Pallas kernels
    under ``shard_map``, each chip its own images, and the weighted solve's
    class systems split over the data axis, each chip its own classes from
    its own rows.  A program without those forms runs every chunk's kernels
    in XLA's partitioned forms and every class system on every chip (or
    wherever GSPMD places them): it cannot run this configuration, and says
    so as this file is loaded, before any data is made."""
    from keystone_tpu.ops.fisher import FisherVector
    from keystone_tpu.ops.sift import SIFTExtractor
    from keystone_tpu.solvers import weighted

    missing = [
        name
        for name, has in (
            ("ops/sift.SIFTExtractor._sharded_kernel_form", hasattr(SIFTExtractor, "_sharded_kernel_form")),
            ("ops/fisher.FisherVector._sharded_kernel_form", hasattr(FisherVector, "_sharded_kernel_form")),
            ("solvers/weighted._execute_split_bwls", hasattr(weighted, "_execute_split_bwls")),
        )
        if not has
    ]
    if missing:
        raise SystemExit(
            "this program has no sharded form of " + ", ".join(missing)
            + ": it cannot run imagenet_sift_lcs_fv_16_mesh4 as the configuration states it"
        )


require_mesh_forms()


def _split_solve_unchanged():
    """``benchmark/lib/faults.state_unchanged`` plants its fault in the block
    solver's dispatch (``solvers.block._execute_fused_bcd``), which this
    pipeline's solver never calls.  While that fault is planted, the same
    fault stands in the class-split solve's dispatch: the models handed back
    at their initial zeros, the intercept the labels' joint mean.  Returns a
    function that takes it out again."""
    import jax.numpy as jnp

    from keystone_tpu.solvers import block, weighted

    if block._execute_fused_bcd.__module__ == block.__name__:
        return lambda: None
    real = weighted._execute_split_bwls

    def unchanged(args, statics):
        labels_sorted, joint_label_mean = args[1], args[9]
        widths = statics[4]
        return (
            jnp.zeros((len(widths), max(widths), labels_sorted.shape[1]), labels_sorted.dtype),
            joint_label_mean,
        )

    weighted._execute_split_bwls = unchanged

    def restore():
        weighted._execute_split_bwls = real

    return restore


def fit(conf: dict, data: dict, seed: int, stem: str) -> dict:
    from keystone_tpu.core.trace import metrics
    from keystone_tpu.parallel.mesh import parse_mesh
    from keystone_tpu.workloads import imagenet_sift_lcs_fv as inet

    require_mesh_forms()
    for old in glob.glob(stem + ".*"):
        os.remove(old)
    before = metrics.get("bwls.class_solves")
    restore = _split_solve_unchanged()
    try:
        results = inet.run(
            _one_chip._config(conf, seed, stem), _one_chip._split(data["train"]),
            _one_chip._split(data["test"]), mesh=parse_mesh(conf["mesh"]),
        )
    finally:
        restore()
    blocks = -(-2 * 2 * conf["desc_dim"] * conf["vocab_size"] // conf["solver_block"])
    solved, due = metrics.get("bwls.class_solves") - before, conf["num_classes"] * blocks * conf["num_epochs"]
    if solved != due:
        raise RuntimeError(f"the fit solved {solved} class systems, not {due}")
    return {"results": results, "seed": seed, "rows": len(data["train"]["x"])}


def produced(out: dict, conf: dict, data: dict, seed: int) -> dict:
    """``pipelines/imagenet_fv.produced`` on the fit's mesh: the samples and
    the features made again by the chunk programs of the window, each chip
    its own images; the compared images drawn from every chip's share
    (``reference/imagenet_fv_mesh.compare_rows``)."""
    import jax.numpy as jnp

    from keystone_tpu.parallel.mesh import DATA_AXIS, parse_mesh
    from keystone_tpu.solvers.gmm import _em_step
    from keystone_tpu.workloads import fv_common, imagenet_sift_lcs_fv as inet

    reference = load_module("reference", REFERENCE)
    mesh = parse_mesh(conf["mesh"])
    res = out["results"]
    chain = res["pipeline"]
    ic = _one_chip._config(conf, out["seed"], None)
    branches = inet.descriptor_branches(ic)
    names = [b.name for b in branches]
    train, test = data["train"]["x"], data["test"]["x"]

    plan = fv_common.plan_chunks(train, branches, ic.desc_dim, ic.vocab_size, mesh)
    draws = [list(inet.branch_draws(ic, plan, b, name).values()) for b, name in enumerate(names)]
    sampled = fv_common.sample_descriptor_columns(plan, train, branches, draws, mesh)
    fitted, centres = {}, {}
    for name, (pca_samples, gmm_raw) in zip(names, sampled):
        pca, gmm = chain[f"{name}_pca"], chain[f"{name}_gmm"]
        centre = chain[f"{name}_centre"].centre if f"{name}_centre" in chain else None
        centres[name] = centre
        gmm_samples = inet._prepare_rows(inet.branch_preparation(name, centre), gmm_raw) @ pca.pca_mat
        floor = 1e-3 * jnp.mean(jnp.var(gmm_samples, axis=0))
        stepped = _em_step(gmm_samples, gmm.means, gmm.variances, gmm.weights, floor, 1 << 18)[:3]
        fitted[name] = {
            "pca_samples": pca_samples,
            "gmm_raw": gmm_raw,
            "pca_mat": np.asarray(pca.pca_mat),
            "centre": None if centre is None else np.asarray(centre),
            "gmm_samples": gmm_samples,
            "gmm": (np.asarray(gmm.means), np.asarray(gmm.variances), np.asarray(gmm.weights)),
            "gmm_iterations": res.get("gmm_iterations", {}).get(name, 0),
            "em_step": stepped,
        }
    del sampled

    pcas = [inet.branch_projection(name, chain[f"{name}_pca"], centres[name]) for name in names]
    gmms = [chain[f"{name}_gmm"] for name in names]
    train_features = _one_chip._in_image_order(
        fv_common.featurize_chunks(plan, train, branches, pcas, gmms, mesh), plan.order
    )
    test_plan = fv_common.plan_chunks(test, branches, ic.desc_dim, ic.vocab_size, mesh)
    test_features = _one_chip._in_image_order(
        fv_common.featurize_chunks(test_plan, test, branches, pcas, gmms, mesh), test_plan.order
    )
    model = chain["model"]
    return {
        "compare_rows": reference.compare_rows(
            conf, train, seed, plan.index, plan.chunk, mesh.shape[DATA_AXIS]
        ),
        "sample_seed": ic.seed,
        "branches": fitted,
        "train_features": train_features,
        "test_features": test_features,
        "weights": np.concatenate([np.asarray(x) for x in model.xs], axis=0),
        "intercept": np.asarray(model.b),
        "test_scores": np.asarray(res["test_scores"]),
        "top5_error": float(res["top5_err_percent"]) / 100.0,
        "test_error": float(res["top5_err_percent"]),
    }
