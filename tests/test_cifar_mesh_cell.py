"""RandomPatchCifar across chips (`cifar_rp_10k_mesh4`, cell
`cifar_rp_fit_mesh4`) at small sizes on four of the suite's eight CPU
devices: the row-sharded fit against the plain reference that the cell's
``correct`` rests on and against the mesh-free fit, the reference's
block-at-a-time form against the whole form, a chip's share of the counts,
and what the mesh path records."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import manifest
from keystone_tpu.core import trace
from keystone_tpu.parallel.mesh import parse_mesh, row_sharding, rows_by_device
from keystone_tpu.workloads import cifar_random_patch as cifar

CELL = "cifar_rp_fit_mesh4"
SEED = 2_147_483_711
ROWS = {"train": 384, "test": 128}


def _conf(**over) -> dict:
    conf = manifest.resized(manifest.cell(CELL)["config"], True)
    conf.update(mesh="4", expected_tier="fused[mesh 4x1]", **over)
    return conf


@pytest.fixture(scope="module")
def data():
    datagen = manifest.load_module("datagen", "class_images")
    return datagen.generate(_conf()["data"], ROWS, SEED)


@pytest.fixture(scope="module")
def mesh_fit(data, tmp_path_factory):
    """One fit through the cell's pipeline file on a 4-way data mesh, what
    it produced, and what the registry and the flight ring saw of it."""
    pipeline = manifest.load_module("pipelines", "cifar_rp_mesh")
    conf = _conf()
    before = dict(trace.metrics.counters())
    out = pipeline.fit(conf, data, pipeline.program_seed(SEED),
                       str(tmp_path_factory.mktemp("mesh") / "fit"))
    after = dict(trace.metrics.counters())
    hists = trace.metrics.hist_windows()
    plans = [e for e in trace.flight_events() if e["name"] == "mesh_plan"]
    chunks = [
        e for e in trace.flight_events()
        if e["name"] == "chunk" and e.get("cat") == "h2d"
    ]
    return {
        "conf": conf, "pipeline": pipeline, "out": out,
        "produced": pipeline.produced(out, conf, data, SEED),
        "counted": {k: after.get(k, 0) - before.get(k, 0) for k in after},
        "plan": plans[-1]["args"], "chunks": chunks, "hists": hists,
    }


def test_mesh_fit_lands_on_the_mesh_tier(mesh_fit):
    report = mesh_fit["pipeline"].fit_report(mesh_fit["out"])
    assert report == {"tier": "fused[mesh 4x1]", "denials": [], "oom_retries": []}
    by_device = mesh_fit["out"]["results"]["feature_rows_by_device"]
    assert sorted(by_device.values()) == [[96 * k, 96 * (k + 1)] for k in range(4)]


def test_mesh_fit_is_inside_the_cells_limits(mesh_fit, data):
    """Against ``benchmark/reference/cifar_rp_mesh.py``, by the comparison
    and the limits that decide the cell's ``correct``."""
    reference = manifest.load_module("reference", "cifar_rp_mesh")
    conf = mesh_fit["conf"]
    with jax.default_matmul_precision("highest"):
        ref = reference.fit(conf, data, mesh_fit["pipeline"].program_seed(SEED), "highest")
        values = reference.compare(conf, data, SEED, mesh_fit["produced"], ref)
    assert conf["limits"]
    for name, limit in conf["limits"].items():
        assert values[name] <= limit, (name, values)


def test_mesh_fit_agrees_with_the_mesh_free_fit(mesh_fit, data, tmp_path):
    one_chip = manifest.load_module("pipelines", "cifar_rp")
    out = one_chip.fit(mesh_fit["conf"], data, one_chip.program_seed(SEED), str(tmp_path / "fit"))
    assert one_chip.fit_report(out)["tier"] == "fused"
    alone = one_chip.produced(out, mesh_fit["conf"], data, SEED)
    mesh = mesh_fit["produced"]
    np.testing.assert_array_equal(mesh["filters"], alone["filters"])
    np.testing.assert_array_equal(mesh["test_predictions"], alone["test_predictions"])
    for name in ("scaler_mean", "scaler_std", "weights", "test_scores_sample"):
        gap = np.linalg.norm(mesh[name] - alone[name]) / np.linalg.norm(alone[name])
        assert gap < 1e-3, (name, gap)


def test_mesh_fit_counts_its_psums_and_says_its_plan(mesh_fit):
    """``mesh.psum_bytes`` from the shapes where the sharded programs are
    called: the scaler's two column sums (128 columns), the solve's gram
    and cross term of its one 128-column block, its block means and label
    mean, in float32."""
    counted = mesh_fit["counted"]
    assert counted["mesh.psum_bytes"] == 4 * (2 * 128 + 128 * 128 + 128 * 10 + 128 + 10)
    assert counted["mesh.devices"] == 4
    assert mesh_fit["plan"] == {
        "mesh": "4x1", "rows_per_device": 96,
        "design_bytes_per_device": 96 * 128 * 4, "tier": "fused[mesh 4x1]",
    }
    # every chunk crossed to its four shards in one h2d span
    assert mesh_fit["chunks"] and all(
        e["args"]["shards"] == 4 for e in mesh_fit["chunks"][-8:]
    )


@pytest.mark.parametrize("stage, sections", [
    ("solve", {"search", "plan", "place", "dispatch", "finish"}),
    ("featurize", {"stack", "dispatch", "concat"}),
])
def test_mesh_fit_charges_its_host_sections(mesh_fit, stage, sections):
    """The mesh tier's search, plans, operand placement and dispatch, and the
    row-sharded featurizer's chunks, are named sections of their stages; the
    stage's parts sum to its self time."""
    from test_host_sections import newest_parts

    parts = newest_parts([stage], mesh_fit["hists"])[stage]
    assert sections <= {part for part, (_, n) in parts.items() if n}, parts


@pytest.mark.parametrize("rows,chunk", [(384, 64), (200, 64), (96, 128)])
def test_row_sharded_featurize_equals_the_plain_one(rng, devices, rows, chunk):
    """Chip k is fed the rows it will hold; the result is the mesh-free
    result, row for row, with every chip holding its own quarter."""
    mesh = parse_mesh("4")
    imgs = rng.uniform(0, 255, (rows, 32, 32, 3)).astype(np.float32)

    @jax.jit
    def fn(batch):  # a row's features depend on the row alone
        flat = batch.reshape(batch.shape[0], -1)
        return flat[:, :24] - 0.5 * flat[:, 24:48]

    plain = np.asarray(cifar.featurize_chunked(fn, imgs, chunk))
    got = cifar.featurize_chunked(fn, imgs, chunk, mesh=mesh)
    assert got.sharding.is_equivalent_to(row_sharding(mesh), 2)
    assert sorted(rows_by_device(got).values()) == [
        [k * rows // 4, (k + 1) * rows // 4] for k in range(4)
    ]
    np.testing.assert_array_equal(np.asarray(got), plain)


def test_rows_that_do_not_split_evenly_still_featurize(rng, devices):
    mesh = parse_mesh("4")
    imgs = rng.uniform(0, 255, (70, 8, 8, 3)).astype(np.float32)
    fn = jax.jit(lambda b: b.reshape(b.shape[0], -1) * 2.0)
    got = cifar.featurize_chunked(fn, imgs, 32, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), imgs.reshape(70, -1) * 2.0)


@pytest.mark.parametrize("epochs", [1, 2])
def test_reference_block_form_equals_the_whole_form(epochs):
    """``reference/cifar_rp_mesh.py`` makes the features again a solver
    block at a time; at a size where the whole form fits, with blocks that
    straddle pool cells, signs and filter windows, the two agree to float32
    rounding."""
    whole = manifest.load_module("reference", "cifar_rp")
    blocks = manifest.load_module("reference", "cifar_rp_mesh")
    datagen = manifest.load_module("datagen", "class_images")
    conf = _conf(num_filters=20, solver_block=24, num_epochs=epochs, reference_chunk=50)
    small = datagen.generate(conf["data"], {"train": 230, "test": 70}, SEED)
    assert blocks.block_runs(conf, 24, 48) == [
        (0, 0, -1.0, 4, 20), (0, 1, 1.0, 0, 8)
    ]
    with jax.default_matmul_precision("highest"):
        a = whole.fit(conf, small, 5, "highest")
        b = blocks.fit(conf, small, 5, "highest")
    assert set(a) == set(b)
    for name in a:
        scale = max(float(np.abs(a[name]).max()), 1.0)  # block means of scaled columns are rounding around 0
        np.testing.assert_allclose(b[name], a[name], rtol=0, atol=1e-4 * scale, err_msg=name)
    values = whole.compare(conf, small, SEED, b, a)
    assert max(values.values()) < 1e-4, values


@pytest.mark.parametrize("kernel", ["conv", "bcd"])
def test_four_shares_of_the_kernels_add_up(kernel):
    """A chip's share of the kernels' work, four times, is the one-chip
    counts' at the same shapes; the whole fit's work is not divided."""
    one_chip = manifest.load_module("counts", "cifar_rp")
    shares = manifest.load_module("counts", "cifar_rp_mesh")
    cell = manifest.cell(CELL)
    conf, rows = manifest.resized(cell["config"], False), cell["traffic"]["rows"]
    assert shares.chips(conf) == 4 and shares.chips(dict(conf, mesh="2x2")) == 4
    whole, share = one_chip.kernels(conf, rows)[kernel], shares.kernels(conf, rows)[kernel]
    assert share["layer"] == whole["layer"]
    assert 4 * share["flops"] == pytest.approx(whole["flops"])
    assert 4 * share["bytes"] == pytest.approx(whole["bytes"])
    assert shares.fit(conf, rows) == one_chip.fit(conf, rows)
    assert shares.feature_width(conf) == 80_000


def test_a_program_without_the_mesh_kernel_form_is_refused(monkeypatch, data, tmp_path):
    """The cell's pipeline file lies over the parent commit too, whose conv
    featurizer keeps the XLA form under a mesh: its fit runs, slowly, and
    its served chain fails the comparison.  It is told so at once."""
    from keystone_tpu.ops.conv_fused import FusedConvFeaturizer

    pipeline = manifest.load_module("pipelines", "cifar_rp_mesh")
    pipeline.require_mesh_kernel_form()  # this program has it
    monkeypatch.delattr(FusedConvFeaturizer, "_sharded_kernel_form")
    with pytest.raises(SystemExit, match="no kernel form under a data mesh"):
        pipeline.fit(_conf(), data, 1, str(tmp_path / "fit"))

