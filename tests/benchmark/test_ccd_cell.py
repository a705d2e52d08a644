"""The cell ``ccd-k100.ml10m`` (ISSUE 31) through the harness on the CPU at a
tiny shape: ``correct`` as the configuration is written, false under each of
three planted faults; the work function against a hand count; the two sweep
readers on a reduction they can and cannot read."""

import json
import os
import types

import jax.numpy as jnp
import pytest

from benchmark import harness, traffic
from tests.benchmark import tiny
from tests.benchmark.test_als_cell import _rewrite
from tests.benchmark.test_faults import _patch_driver

CELL = "ccd-k100.ml10m"
# tiny traffic: sizes only; the generator and every width stay. Every seed
# passes RMSE 0.0105 between its second (0.0119-0.0131) and its third
# (0.0074-0.0078) outer iteration: rank 100 fits 20,000 ratings closely
_PARAMS = {"rows": 704, "cols": 300, "ratings": 20000,
           "row_offset": 30, "col_offset": 10}
_TARGET = 0.0105
# limits at this size on the CPU, where so few ratings a row let the
# bfloat16 operands of the prediction show (over eight seeds the program
# reads at most 0.029, 0.025, 0.036; the float8 control at least 0.58, 0.081,
# 0.112)
_LIMITS = {"quality_gap": 0.1, "step1_diff": 0.05, "step3_diff": 0.065}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny.build(str(tmp_path_factory.mktemp("bench")))

    def cut(doc):
        doc["params"].update(_PARAMS)
        doc["target"]["at_most"] = _TARGET

    _rewrite(os.path.join(root, "benchmark", "workloads", CELL + ".json"), cut)
    _rewrite(os.path.join(root, "benchmark", "configs", "ccd-k100.json"),
             lambda doc: doc.update(limits=_LIMITS))
    return root


def _run(tree, seed=tiny.SEED + 3, trace=False):
    return harness.run_cell(CELL, seed, 0.3, trace,
                            require_accelerator=False, root=tree)


def test_the_cell_is_in_the_manifest_as_the_issue_states_it():
    cell = harness.find_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "ccd-k100"
    sgdmf = harness.find_cell(tiny.ML10M)
    assert cell.traffic["generator"] == "planted_ratings"
    assert cell.traffic["params"] == sgdmf.traffic["params"]
    assert cell.traffic["epochs_per_call"] == 1
    assert cell.traffic["max_epochs"] == 30
    assert cell.traffic["target"]["quality"] == "rmse"
    c = cell.config
    assert (c["rank"], c["lam"], c["inner_iterations"]) == (100, 0.05, 2)
    assert c["model"] == "harp_tpu.models.ccd.CCD"
    assert c["device_op_names"] == {"sweep": ["ccd_rank1_sweep"]}
    assert c["reduced"] == [] and c["control"]["products"] == "float8_e4m3fn"
    assert c["control"]["kind"] == "reference"
    assert len(c["source"]) <= 200 and "ICDM 2012" in c["source"]
    assert cell.part("driver").FIELDS == ("rank", "lam", "inner_iterations")
    assert set(cell.limits) <= {"quality_gap", "step1_norm_gap",
                                "step3_norm_gap", "step1_diff", "step3_diff"}
    entry = next(w for w in cell.manifest["workloads"] if w["name"] == CELL)
    assert cell.manifest["workloads"][-1] == entry      # put at the end
    assert cell.manifest["configs"][-1]["name"] == "ccd-k100"
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"ccd_sweep_ms", "ccd_sweep_roofline_share", "step_mfu",
            "update_roofline_share", "device_idle_share", "peak_hbm_gib",
            "epochs_to_target", "data_prep_s", "backend_compile_s"} == names
    for m in cell.manifest["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        assert (m["layer"], m["moves"]) == ("models / kernels",
                                            "samples_per_s")
    for other in tiny.CELLS + ("als-k100.ml10m",):
        assert "ccd_sweep_ms" not in {
            m["name"] for m in harness.find_cell(other).metrics("per_layer")}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(tiny.BENCH, "configs", "ccd-k100.reference.py")
    with open(path) as fh:
        imports = [line for line in fh if line.startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "harp_tpu" in line]


def test_an_untraced_run_is_correct_and_every_job_takes_three_iterations(tree):
    line = json.loads(json.dumps(_run(tree)))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s", "time_to_target_s",
                                    "call_ms_p95", "setup_s"}
    assert line["window"]["epochs_to_target"] == [3]
    assert line["window"]["compiles_in_window"] == 0
    assert line["window"]["calls"] == line["window"]["epochs"]
    held = {k for k, v in line["compared"].items() if v["limit"] is not None}
    assert held == set(_LIMITS)


def test_a_traced_run_reads_the_old_metrics_and_no_sweep_it_cannot_see(
        tree, monkeypatch):
    """The recorded trace is K-means': no operation of it bears the sweep's
    name, so both sweep readers report nothing, never 0."""
    tiny.as_v5e(monkeypatch, harness)
    tiny.recorded_trace(monkeypatch, harness)
    line = _run(tree, seed=tiny.SEED + 4, trace=True)
    assert line["correct"] is True
    assert {"step_mfu", "update_roofline_share", "device_idle_share",
            "peak_hbm_gib", "epochs_to_target", "data_prep_s",
            "backend_compile_s"} == set(line["metrics"])
    assert line["metrics"]["epochs_to_target"]["value"] == 3


def test_the_float8_control_fails_a_held_limit(tree):
    from benchmark import compare

    cell = harness.find_cell(CELL, tree)
    data = harness.make_data(cell, tiny.SEED + 5)
    first, reference = harness.follow_reference(cell, data)
    _, control = harness.follow_reference(cell, data,
                                          products=jnp.float8_e4m3fn)
    read = compare.numbers(first, control, reference)
    correct, _ = compare.verdict(read, _LIMITS)
    assert not correct and read["step1_diff"] > 1.5 * _LIMITS["step1_diff"]


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


def test_fault_half_of_the_ratings_left_out(tree, monkeypatch):
    def on_half(init):
        def broken(self, config, cell_traffic, data, chips, overrides=None):
            init(self, config, cell_traffic, traffic.halved(data), chips,
                 overrides)
        return broken

    _patch_driver(monkeypatch, tree, CELL, __init__=on_half)
    line = _run(tree)
    assert line["correct"] is False
    assert line["compared"]["quality_gap"]["value"] > 3 * _LIMITS["quality_gap"]
    assert line["compared"]["step1_diff"]["value"] > 3 * _LIMITS["step1_diff"]


def test_fault_one_feature_never_updated(tree, monkeypatch):
    """Inside the program: feature 3 of either side keeps the seed's values
    through every pass. One feature in a hundred, yet the worst leaf is a
    quarter of its change away from the reference's."""
    from harp_tpu.models import ccd

    real = ccd._half_step

    def skipping(plane, side, mine, other, t, lam, axis_name):
        new = real(plane, side, mine, other, t, lam, axis_name)
        return tuple(jnp.where(t == 3, old, x) for old, x in zip(mine, new))

    monkeypatch.setattr(ccd, "_half_step", skipping)
    line = _run(tree)
    assert line["correct"] is False
    assert line["compared"]["step1_diff"]["value"] > 2 * _LIMITS["step1_diff"]
    assert line["compared"]["step3_diff"]["value"] > 2 * _LIMITS["step3_diff"]


def test_the_driver_calls_only_the_new_entry_points(tree):
    """On a program without ``prepare`` the cell ends at once with an
    ``AttributeError``: it never reaches the old ``fit`` at this shape."""
    from harp_tpu.models import ccd

    cell = harness.find_cell(CELL, tree)
    data = harness.make_data(cell, tiny.SEED + 6)
    driver = cell.part("driver").Driver(cell.config, cell.traffic, data, 1)

    class Old:
        def fit(self, *args, **kwargs):
            raise AssertionError("the old entry point was reached")

    driver.model = Old()
    with pytest.raises(AttributeError, match="prepare"):
        driver.prepare()
    assert hasattr(ccd.CCD, "prepare") and hasattr(ccd.CCD, "train_prepared")


def test_work_against_a_hand_count():
    cell = harness.find_cell(CELL)
    work = cell.part("work").work(cell.config, cell.traffic)
    nnz, k, inner = 10_000_054, 100, 2
    assert work["samples_per_epoch"] == nnz
    # per feature: 2 rounds x 2 sides x 4 FLOPs a rating, and 4 for the
    # residual's rank-one update; 2 x 2 x 12 bytes, and 8
    assert work["sweep_flops_per_epoch"] == k * 20 * nnz == 20_000_108_000
    assert work["sweep_bytes_per_epoch"] == k * 56 * nnz == 56_000_302_400
    assert work["flops_per_epoch"] == work["sweep_flops_per_epoch"] + 2 * nnz
    assert work["bytes_per_epoch"] == work["sweep_bytes_per_epoch"] + 4 * nnz
    # shapes alone: five rounds (libpmf's default) count five rounds
    five = cell.part("work").work({**cell.config, "inner_iterations": 5},
                                  cell.traffic)
    assert five["sweep_flops_per_epoch"] == k * 44 * nnz
    assert (k, inner) == (cell.config["rank"], cell.config["inner_iterations"])


def _reader(name):
    cell = harness.find_cell(CELL)
    return cell, harness.load_module(os.path.join(
        cell.bench_dir, "metrics", name + ".py"))


def _ctx(cell, device_ops, epochs=6):
    return types.SimpleNamespace(
        cell=cell, window=types.SimpleNamespace(epochs=epochs),
        trace=types.SimpleNamespace(device_ops=device_ops),
        work=cell.part("work").work(cell.config, cell.traffic),
        peak=lambda: harness.peak_of(cell.bench_dir, "TPU v5 lite"))


def test_the_sweep_readers_sum_the_kernels_events_by_their_fixed_name():
    cell, ms = _reader("ccd_sweep_ms")
    _, share = _reader("ccd_sweep_roofline_share")
    ops = [("fusion.7", 0.3), ("ccd_rank1_sweep.3", 3.0),
           ("ccd_rank1_sweep.2", 2.4), ("ccd_rank1_sweep_like.2", 9.0)]
    ctx = _ctx(cell, ops)
    assert ms.read(ctx) == pytest.approx(900.0)
    # HBM bound: 56 bytes x 100 features x 10,000,054 ratings at 819 GB/s
    assert share.read(ctx) == pytest.approx(100 * 68.376 / 900.0, rel=1e-3)
    assert 0 < share.read(ctx) < 100


@pytest.mark.parametrize("ops", [[], [("fusion.19", 1.0), ("copy.3", 0.5)]])
def test_the_sweep_readers_report_nothing_where_no_sweep_ran(ops):
    """The parent's program has no kernel of that name, a run without a
    trace no operations at all: nothing is reported, never 0."""
    cell, ms = _reader("ccd_sweep_ms")
    _, share = _reader("ccd_sweep_roofline_share")
    ctx = _ctx(cell, ops)
    assert ms.read(ctx) is None and share.read(ctx) is None
    ctx.trace = None
    assert ms.read(ctx) is None and share.read(ctx) is None
