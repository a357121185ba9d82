"""ImageNetSiftLcsFV across chips (`imagenet_sift_lcs_fv_16_mesh4`, cell
`imagenet_fv_fit_mesh4`), the parts that need no fit: a chip's share of the
counts, the check that refuses a program without the sharded forms, the
compared images drawn from every chip's share, and the new metrics' readers
on a trace of four device planes and on one.  The cell's whole rehearsal,
four ways, is ``benchmark/tests/test_imagenet_mesh_cell.py``."""

import numpy as np
import pytest

from benchmark.lib import manifest

CELL = "imagenet_fv_fit_mesh4"


def _conf_rows():
    cell = manifest.cell(CELL)
    return manifest.resized(cell["config"], False), cell["traffic"]["rows"]


def test_a_chips_share_of_the_counts():
    """The chain on a quarter of the images; of the weighted solve, the
    population's work on a quarter of the rows with every class's column,
    and a quarter of the class systems; the whole fit's work not divided."""
    one_chip = manifest.load_module("counts", "imagenet_fv")
    shares = manifest.load_module("counts", "imagenet_fv_mesh")
    conf, rows = _conf_rows()
    assert shares.chips(conf) == 4 and rows == {"train": 16000, "test": 4000}
    whole, share = one_chip.kernels(conf, rows), shares.kernels(conf, rows)
    for kind in ("flops", "bytes"):
        assert 4 * share["fv2_chain"][kind] == pytest.approx(whole["fv2_chain"][kind])
    assert share["fv2_chain"]["layer"] == "featurizers" and share["wsolve"]["layer"] == "solvers"
    quarter = one_chip.weighted_solve(4000, [4096], 250, 1)
    assert share["wsolve"]["flops"] > quarter["flops"]  # every class's X^T R column
    assert share["wsolve"]["flops"] < 0.26 * whole["wsolve"]["flops"]
    systems = shares.class_systems([4096], 250, 1)["flops"]
    assert systems == pytest.approx(250 * (4096**3 / 3 + 7 * 4096**2))
    assert shares.fit(conf, rows) == one_chip.fit(conf, rows)


def test_the_mesh_configuration_is_a_deployment_of_its_own():
    """Every number of the one-chip file but the rows; a source that names
    the part of the reference this layout follows, so that no two
    configurations share both their source and their cuts."""
    configs = manifest.benchmark_json()["configs"]
    pairs = [(c["source"], tuple(sorted(c["reduced"]))) for c in configs]
    assert len(pairs) == len(set(pairs))
    mesh = manifest.load_json("configs", "imagenet_sift_lcs_fv_16_mesh4.json")
    one = manifest.load_json("configs", "imagenet_sift_lcs_fv_16.json")
    assert "BlockWeightedLeastSquares.scala:324-361" in mesh["source"]
    numbers = [k for k, v in one.items() if isinstance(v, (int, float)) and k != "rows"]
    assert {k: mesh[k] for k in numbers} == {k: one[k] for k in numbers}
    assert mesh["reduced"] == one["reduced"] and mesh["published"] == one["published"]
    assert mesh["rows"] == {k: 4 * v for k, v in one["rows"].items()}


@pytest.mark.parametrize("module,holder,name", [
    ("keystone_tpu.ops.sift", "SIFTExtractor", "_sharded_kernel_form"),
    ("keystone_tpu.ops.fisher", "FisherVector", "_sharded_kernel_form"),
    ("keystone_tpu.solvers.weighted", None, "_execute_split_bwls"),
])
def test_a_program_without_the_mesh_forms_is_refused(monkeypatch, module, holder, name):
    """The cell's pipeline file may be laid over an older program, whose SIFT
    and Fisher vector keep their XLA forms under a mesh and whose weighted
    solve splits no class over the chips: loading the file stops it before
    any data is made."""
    import importlib

    manifest.load_module("pipelines", "imagenet_fv_mesh")  # this program has them
    target = importlib.import_module(module)
    monkeypatch.delattr(getattr(target, holder) if holder else target, name)
    with pytest.raises(SystemExit, match=f"no sharded form of .*{name}"):
        manifest.load_module("pipelines", "imagenet_fv_mesh")


def test_compared_images_come_from_every_chip():
    """Two buckets of 300 and 70 images in chunks of 128 and 72 over four
    chips: each chip's share gives a quarter of the compared images, each
    bucket its share of those and at least one."""
    reference = manifest.load_module("reference", "imagenet_fv_mesh")
    images = [np.zeros((8, 6, 3) if i % 5 else (6, 8, 3), np.uint8) for i in range(370)]
    index = {}
    for i, img in enumerate(images):
        index.setdefault(img.shape[:2], []).append(i)
    index = {s: np.asarray(v) for s, v in index.items()}
    chunks = {(8, 6): 128, (6, 8): 72}
    chip = reference.shards_of(index, chunks, 4)
    big = index[(8, 6)]
    assert list(chip[big[:128]]) == [k for k in range(4) for _ in range(32)]
    assert list(chip[big[256:]]) == [k for k in range(4) for _ in range(32)][: len(big) - 256]
    rows = reference.compare_rows({"compare": {"images": 40}}, images, 7, index, chunks, 4)
    by_chip = np.bincount(chip[rows], minlength=4)
    assert by_chip.min() >= 8 and by_chip.sum() == len(rows) == len(set(rows.tolist()))
    assert set(rows) & set(index[(6, 8)].tolist())


def _trace(per_device_ns, extra=None):
    """A reduced trace of one device plane a chip: the class-split solve and
    a chunk program each with the given device time."""
    return {
        "devices": [
            {"modules": {"jit__split_bwls_impl": ns, "jit__encode_chunk": 2 * ns, **(extra or {})},
             "busy_ns": 3 * ns, "ops": {}}
            for ns in per_device_ns
        ],
        "layers_ns": {"featurizers": 2 * float(np.mean(per_device_ns)), "solvers": float(np.mean(per_device_ns))},
    }


@pytest.fixture
def ctx():
    conf, rows = _conf_rows()
    counts = manifest.load_module("counts", "imagenet_fv_mesh")
    return {
        "conf": conf, "rows": rows, "traced_fits": 1,
        "kernels": counts.kernels(conf, rows),
        "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": _trace([4.0e8, 4.2e8, 4.4e8, 4.6e8]),
    }


@pytest.mark.parametrize("name", ["mesh_fv2_chain_roofline", "mesh_wsolve_roofline", "wsolve_skew_pct"])
def test_trace_readers_read_four_chips(ctx, name):
    spec = manifest.load_json("metrics", f"{name}.json")
    assert spec["workloads"] == [CELL]
    value = manifest.load_module("readers", spec["reader"]).read(spec, ctx)
    assert value is not None and 0 < value < 100, value
    if name == "wsolve_skew_pct":
        assert value == pytest.approx(100 * 0.6e8 / 4.3e8)
    if name == "mesh_wsolve_roofline":  # a chip's share over the mean of the four
        from benchmark.lib.roofline import least_seconds

        kernel = ctx["kernels"]["wsolve"]
        least, _bound = least_seconds(kernel["flops"], kernel["bytes"], ctx["peaks"])
        assert value == pytest.approx(100 * least / 0.43)


@pytest.mark.parametrize("name", ["mesh_wsolve_roofline", "wsolve_skew_pct", "class_regroup_mb"])
def test_readers_find_nothing_where_nothing_ran(ctx, name, monkeypatch):
    """These files may be laid over an older checkout, which runs the other
    cells traced: one device plane, no class-split solve, no counter."""
    from keystone_tpu.core import trace

    monkeypatch.setattr(trace, "metrics", trace.Metrics())
    spec = manifest.load_json("metrics", f"{name}.json")
    reader = manifest.load_module("readers", spec["reader"])
    one = dict(ctx, trace=_trace([4.0e8]))
    other = dict(ctx, trace={
        "devices": [{"modules": {"jit__fused_bwls_impl": 1e8}, "busy_ns": 1e8, "ops": {}}] * 4,
        "layers_ns": {"solvers": 1e8},
    })
    assert reader.read(spec, other) is None
    if name != "mesh_wsolve_roofline":
        assert reader.read(spec, one) is None
    assert reader.read(spec, dict(ctx, trace=None, traced_fits=0)) is None


def test_class_regroup_mb_reads_the_counter(monkeypatch):
    from keystone_tpu.core import trace

    monkeypatch.setattr(trace, "metrics", trace.Metrics())
    spec = manifest.load_json("metrics", "class_regroup_mb.json")
    reader = manifest.load_module("readers", spec["reader"])
    trace.metrics.inc("bwls.regroup_bytes", 3 * 245_000_000)
    for _ in range(3):
        trace.metrics.observe("stage_ms.solve", 1.0)
    assert reader.read(spec, {"rows": {"train": 1}}) == pytest.approx(245.0)
