"""The cell ``wdamds-d3.clusters-32k`` (ISSUE 34) through the harness on the
CPU at 512 points: ``correct`` as the configuration is written, false under
its bfloat16 control and under each of three planted faults; the work
function against a hand count; the four kernel readers on a reduction they
can and cannot read; the parent's ``WDAMDS`` fails the driver at once."""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import compare, harness
from tests.benchmark import tiny
from tests.benchmark.test_als_cell import _rewrite
from tests.benchmark.test_faults import _patch_driver

CELL = "wdamds-d3.clusters-32k"
READERS = ("mds_bc_ms", "mds_bc_roofline_share", "mds_matvec_ms",
           "mds_matvec_roofline_share")
# tiny traffic: the point count, and a target at the schedule's end (the
# cell's own ends a job in its 13th call); the mixture, the cut, every width
# and the schedule stay. This seed's stress reads 0.06372 at iteration 304,
# the last above T = 0, and 0.06326 at 305: the job ends in its 31st call
_POINTS = 512
_SEED = tiny.SEED + 3
_TARGET = 0.0635
# limits at this size on the CPU, where float32 is float32 (over three seeds
# the program reads at most 7e-7, 4.5e-7, 1.4e-6; the bfloat16 control at
# least 1.3e-5, 1.6e-3, 3.4e-3)
_LIMITS = {"quality_gap": 3e-6, "step1_diff": 5e-5, "step3_diff": 1e-4}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny.build(str(tmp_path_factory.mktemp("bench")))

    def cut(doc):
        doc["params"]["points"] = _POINTS
        doc["target"]["at_most"] = _TARGET
        doc["max_epochs"] = 470

    _rewrite(os.path.join(root, "benchmark", "workloads", CELL + ".json"), cut)
    _rewrite(os.path.join(root, "benchmark", "configs", "wdamds-d3.json"),
             lambda doc: doc.update(limits=_LIMITS))
    return root


def _run(tree, seed=_SEED, trace=False):
    return harness.run_cell(CELL, seed, 0.3, trace,
                            require_accelerator=False, root=tree)


def test_the_cell_is_in_the_manifest_as_the_issue_states_it():
    cell = harness.find_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "wdamds-d3"
    assert cell.traffic["generator"] == "gaussian_mixture"
    assert cell.traffic["params"] == {
        "points": 32768, "components": 30, "center_scale": 1.0,
        "noise_scale": 0.3, "init_offset": 0.0, "structure_seed": 7}
    assert cell.traffic["epochs_per_call"] == 10
    assert cell.traffic["target"]["quality"] == "stress"
    # a job is 13 calls (PERF.md section 4); max_epochs 1.5 times that
    assert cell.traffic["max_epochs"] == 195
    assert cell.traffic["target"]["at_most"] == 0.1133
    c = cell.config
    assert c["model"] == "harp_tpu.models.mds.WDAMDS"
    assert (c["target_dim"], c["cg_iters"], c["alpha"], c["level_iterations"],
            c["t_floor"], c["distance_cut"]) == (3, 10, 0.95, 4, 0.02, 15.2)
    assert (c["weights_dtype"], c["distances_dtype"]) == ("bfloat16",
                                                          "float32")
    assert (c["dim"], c["num_centroids"]) == (100, 1)   # the generator's
    assert c["device_op_names"] == {"bc": ["mds_bc_stress"],
                                    "matvec": ["mds_laplacian_matvec"]}
    assert c["control"] == {**c["control"], "kind": "reference",
                            "products": "bfloat16"}
    assert c["reduced"] == [] and len(c["assumed"]) >= 6
    assert len(c["source"]) <= 200 and "eScience 2013" in c["source"]
    assert cell.part("driver").FIELDS == (
        "target_dim", "cg_iters", "alpha", "level_iterations", "t_floor")
    # all five numbers are held: the program's own stress curve among them
    assert set(cell.limits) == set(compare.NUMBERS)
    assert cell.limits["quality_gap"] == 1.4e-6
    entry = next(c for c in cell.manifest["configs"]
                 if c["name"] == "wdamds-d3")
    assert entry["source"] == c["source"] and entry["reduced"] == []
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {*READERS, "step_mfu", "update_roofline_share",
            "device_idle_share", "peak_hbm_gib", "epochs_to_target",
            "data_prep_s", "backend_compile_s"} == names
    for m in cell.manifest["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["source"] == "device_trace"
            assert (m["layer"], m["moves"]) == ("models / kernels",
                                                "samples_per_s")
    for other in tiny.CELLS + ("als-k100.ml10m", "ccd-k100.ml10m"):
        assert not set(READERS) & {
            m["name"] for m in harness.find_cell(other).metrics("per_layer")}


_ADDED = {"configs": ("wdamds-d3",), "workloads": (CELL,),
          "per_layer": READERS}


def _before_this_cell(manifest):
    """The manifest less this cell's entries, which have to be the END of
    their lists: what the benchmark held before PR 34."""
    before = dict(manifest)
    for group, added in _ADDED.items():
        names = [e["name"] for e in manifest[group]]
        assert tuple(names[-len(added):]) == added      # appended, in order
        before[group] = manifest[group][:-len(added)]
    return before


def test_what_the_benchmark_had_is_a_prefix_of_every_list():
    manifest = harness.find_cell(CELL).manifest
    before = _before_this_cell(manifest)
    assert [c["name"] for c in before["configs"]] == [
        "kmeans-d100", "sgdmf-k100", "als-k100", "ccd-k100"]
    assert [w["name"] for w in before["workloads"]] == [
        *tiny.CELLS, "als-k100.ml10m", "ccd-k100.ml10m"]
    assert [m["name"] for m in before["per_layer"]][-4:] == [
        "als_solve_roofline_share", "als_solve_ms", "ccd_sweep_ms",
        "ccd_sweep_roofline_share"]
    assert len(manifest["configs"]) == 5 and len(manifest["workloads"]) == 6


def test_ccd_cells_accepted_manifest_test_holds_on_what_was_there(monkeypatch):
    """``test_ccd_cell.py``'s manifest test holds ``ccd-k100``'s entries to
    the LAST place of their lists, and the benchmark's contract puts a new
    cell's at the end: with this cell appended that test cannot pass as
    written (``tests/conftest.py`` expects its failure, by name). Every one of
    its asserts, the three of position too, is held here on the manifest less
    this cell's tail."""
    from tests.benchmark import test_ccd_cell as accepted

    find_cell = harness.find_cell

    def without_this_cell(name, root=harness.ROOT):
        cell = find_cell(name, root)
        return dataclasses.replace(
            cell, manifest=_before_this_cell(cell.manifest))

    monkeypatch.setattr(harness, "find_cell", without_this_cell)
    accepted.test_the_cell_is_in_the_manifest_as_the_issue_states_it()


def test_the_kernels_bear_the_names_the_configuration_lists():
    from harp_tpu.ops import mds_kernels

    names = harness.find_cell(CELL).config["device_op_names"]
    assert names == {"bc": [mds_kernels.BC_NAME],
                     "matvec": [mds_kernels.MATVEC_NAME]}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(tiny.BENCH, "configs", "wdamds-d3.reference.py")
    with open(path) as fh:
        imports = [line for line in fh if line.startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "harp_tpu" in line]


def test_an_untraced_run_is_correct_and_the_job_ends_at_the_first_t_zero(tree):
    line = json.loads(json.dumps(_run(tree)))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s", "time_to_target_s",
                                    "call_ms_p95", "setup_s"}
    # iteration 305's embedding (0-based) is the first made at T = 0
    assert line["window"]["epochs_to_target"] == [306]
    assert line["window"]["compiles_in_window"] == 0
    assert line["window"]["epochs"] == 10 * line["window"]["calls"]
    held = {k for k, v in line["compared"].items() if v["limit"] is not None}
    assert held == set(_LIMITS)
    for v in line["compared"].values():
        assert v["value"] < 1e-5


def test_a_traced_run_reads_the_old_metrics_and_no_kernel_it_cannot_see(
        tree, monkeypatch):
    """The recorded trace is K-means': no operation of it bears a name the
    configuration lists, so the four readers report nothing, never 0."""
    tiny.as_v5e(monkeypatch, harness)
    tiny.recorded_trace(monkeypatch, harness)
    line = _run(tree, trace=True)
    assert line["correct"] is True
    assert {"step_mfu", "update_roofline_share", "device_idle_share",
            "peak_hbm_gib", "epochs_to_target", "data_prep_s",
            "backend_compile_s"} == set(line["metrics"])
    assert line["metrics"]["epochs_to_target"]["value"] == 306


def test_the_bfloat16_control_fails_every_limit_the_tiny_cell_holds(tree):
    cell = harness.find_cell(CELL, tree)
    data = harness.make_data(cell, tiny.SEED + 5)
    first, reference = harness.follow_reference(cell, data)
    _, control = harness.follow_reference(cell, data, products=jnp.bfloat16)
    read = compare.numbers(first, control, reference)
    assert not compare.verdict(read, _LIMITS)[0]
    for name, limit in _LIMITS.items():
        assert read[name] > 3 * limit, (name, read[name])


def test_fault_a_state_returned_unchanged(tree, monkeypatch):
    def unchanged(call):
        def broken(self, state):
            _, quality = call(self, state)
            return state, quality
        return broken

    _patch_driver(monkeypatch, tree, CELL, call=unchanged)
    line = _run(tree)
    assert line["correct"] is False
    assert line["compared"]["step1_diff"]["value"] == pytest.approx(1.0)
    # the curve of one iteration, ten times: the job never ends
    assert line["failed"] >= 1 and line["window"]["epochs_to_target"] == []


def test_fault_the_temperature_held_at_its_first_value(tree, monkeypatch):
    def never_cooling(init):
        def broken(self, config, cell_traffic, data, chips, overrides=None):
            init(self, config, cell_traffic, data, chips,
                 {"level_iterations": 10 ** 6})
        return broken

    _patch_driver(monkeypatch, tree, CELL, __init__=never_cooling)
    line = _run(tree)
    assert line["correct"] is False and line["failed"] >= 1
    assert line["compared"]["quality_gap"]["value"] > 0.1
    assert line["compared"]["step3_diff"]["value"] > 0.1


def test_fault_the_matvec_at_one_bfloat16_term(tree, monkeypatch):
    """Inside the program: the CG's direction cut to its upper bfloat16 term,
    what the chip's default precision makes of a float32 product."""
    from harp_tpu.ops import mds_kernels

    real = mds_kernels.matvec_operand

    def one_term(pt, dtype):
        bits = jax.lax.bitcast_convert_type(pt, jnp.uint32)
        return real(jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32), dtype)

    monkeypatch.setattr(mds_kernels, "matvec_operand", one_term)
    line = _run(tree)
    assert line["correct"] is False
    assert line["compared"]["step1_diff"]["value"] > 10 * _LIMITS["step1_diff"]
    assert line["compared"]["step3_diff"]["value"] > 10 * _LIMITS["step3_diff"]


def test_the_parents_api_fails_the_driver_at_once(tree, monkeypatch):
    """The parent's ``MDSConfig`` knows no schedule and its ``WDAMDS`` no
    ``train_prepared``: the driver ends before any matrix is made."""
    from harp_tpu.models import mds

    @dataclasses.dataclass(frozen=True)
    class OldConfig:
        dim: int = 2
        iterations: int = 50
        cg_iters: int = 10

    cell = harness.find_cell(CELL, tree)
    data = harness.make_data(cell, tiny.SEED + 6)
    build = cell.part("driver").Driver
    monkeypatch.setattr(mds, "MDSConfig", OldConfig)
    with pytest.raises(TypeError, match="alpha"):
        build(cell.config, cell.traffic, data, 1)
    monkeypatch.undo()
    driver = build(cell.config, cell.traffic, data, 1)
    monkeypatch.delattr(mds, "distance_matrix")
    with pytest.raises(AttributeError, match="distance_matrix"):
        driver.prepare()
    monkeypatch.undo()
    assert hasattr(mds.WDAMDS, "train_prepared")


def test_work_against_a_hand_count():
    cell = harness.find_cell(CELL)
    work = cell.part("work").work(cell.config, cell.traffic)
    n = 32768
    cells = n * n
    assert work["samples_per_epoch"] == n * (n - 1)
    # delta float32 and w bfloat16 once; w once more for each of 11 matvecs
    assert work["bc_bytes_per_epoch"] == 6 * cells
    assert work["matvec_bytes_per_epoch"] == 11 * 2 * cells
    assert work["bytes_per_epoch"] == 28 * cells == 30_064_771_072
    assert work["bc_flops_per_epoch"] == 25 * cells
    assert work["matvec_flops_per_epoch"] == 6 * 11 * cells
    assert work["flops_per_epoch"] == 91 * cells
    # the stated stored type alone: float32 weights double their bytes
    wide = cell.part("work").work({**cell.config, "weights_dtype": "float32"},
                                  cell.traffic)
    assert wide["bytes_per_epoch"] == (8 + 11 * 4) * cells
    assert wide["flops_per_epoch"] == work["flops_per_epoch"]


def _reader(name):
    cell = harness.find_cell(CELL)
    return cell, harness.load_module(os.path.join(
        cell.bench_dir, "metrics", name + ".py"))


def _ctx(cell, device_ops, epochs=100):
    return types.SimpleNamespace(
        cell=cell, window=types.SimpleNamespace(epochs=epochs),
        trace=types.SimpleNamespace(device_ops=device_ops),
        work=cell.part("work").work(cell.config, cell.traffic),
        peak=lambda: harness.peak_of(cell.bench_dir, "TPU v5 lite"))


def test_the_readers_sum_each_kernels_events_by_its_fixed_name():
    cell, _ = _reader(READERS[0])
    ops = [("fusion.7", 0.3), ("mds_bc_stress.10", 1.0),
           ("mds_laplacian_matvec.16", 0.3), ("mds_laplacian_matvec.17", 3.0),
           ("mds_bc_stress_like.2", 9.0)]
    ctx = _ctx(cell, ops)
    read = {name: _reader(name)[1].read(ctx) for name in READERS}
    assert read["mds_bc_ms"] == pytest.approx(10.0)
    assert read["mds_matvec_ms"] == pytest.approx(33.0)
    # HBM bound: 6 and 22 bytes a cell of 32768^2 at 819 GB/s
    assert read["mds_bc_roofline_share"] == pytest.approx(
        100 * 7.866 / 10.0, rel=1e-3)
    assert read["mds_matvec_roofline_share"] == pytest.approx(
        100 * 28.841 / 33.0, rel=1e-3)


@pytest.mark.parametrize("ops", [[], [("fusion.19", 1.0), ("copy.3", 0.5)]])
def test_the_readers_report_nothing_where_no_kernel_ran(ops):
    """A program without the kernels, a run without a trace: nothing is
    reported, never 0."""
    cell, _ = _reader(READERS[0])
    ctx = _ctx(cell, ops)
    assert [_reader(name)[1].read(ctx) for name in READERS] == [None] * 4
    ctx.trace = None
    assert [_reader(name)[1].read(ctx) for name in READERS] == [None] * 4
