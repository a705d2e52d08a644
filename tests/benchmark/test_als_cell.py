"""The cell ``als-k100.ml10m`` (ISSUE 27) through the harness on the CPU at a
tiny shape: ``correct`` as the configuration is written, false under each of
three planted faults; the work function against a hand count; the two solve
readers on a reduction they can and cannot read."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, traffic
from tests.benchmark import tiny
from tests.benchmark.test_faults import _patch_driver

CELL = "als-k100.ml10m"
# tiny traffic: sizes only; the generator and every width stay. Every seed
# passes fit_rmse 0.0135 between its third (0.0144-0.0148) and its fourth
# (0.0127-0.0130) iteration
_PARAMS = {"rows": 704, "cols": 300, "ratings": 20000,
           "row_offset": 30, "col_offset": 10}
_TARGET = 0.0135
# limits at this size on the CPU: with 300 items V'V is small beside the
# rated part and the bfloat16 outer products show (the program reads 0.033,
# 0.017, 0.17 over seeds; half of the ratings 0.32, 0.15, 0.82)
_LIMITS = {"quality_gap": 0.1, "step1_norm_gap": 0.06, "step1_diff": 0.4}


def _rewrite(path, change):
    with open(path) as fh:
        doc = json.load(fh)
    change(doc)
    with open(path, "w") as fh:
        json.dump(doc, fh)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny.build(str(tmp_path_factory.mktemp("bench")))

    def cut(doc):
        doc["params"].update(_PARAMS)
        doc["target"]["at_most"] = _TARGET

    _rewrite(os.path.join(root, "benchmark", "workloads", CELL + ".json"), cut)
    _rewrite(os.path.join(root, "benchmark", "configs", "als-k100.json"),
             lambda doc: doc.update(limits=_LIMITS))
    return root


def _run(tree, seed=tiny.SEED + 3, trace=False):
    return harness.run_cell(CELL, seed, 0.3, trace,
                            require_accelerator=False, root=tree)


def test_the_cell_is_in_the_manifest_as_the_issue_states_it():
    cell = harness.find_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "als-k100"
    sgdmf = harness.find_cell(tiny.ML10M)
    assert cell.traffic["generator"] == "planted_ratings"
    assert cell.traffic["params"] == sgdmf.traffic["params"]
    assert cell.traffic["epochs_per_call"] == 1
    assert cell.traffic["max_epochs"] == 30
    assert cell.traffic["target"]["quality"] == "fit_rmse"
    c = cell.config
    assert (c["rank"], c["lam"], c["alpha"], c["implicit"], c["layout"],
            c["solver"]) == (100, 0.05, 40.0, True, "dense", "auto")
    assert c["reduced"] == [] and c["control"]["products"] == "float8_e4m3fn"
    assert set(cell.limits) <= {"quality_gap", "step1_norm_gap",
                                "step3_norm_gap", "step1_diff", "step3_diff"}
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"als_solve_ms", "als_solve_roofline_share", "step_mfu",
            "update_roofline_share"} <= names
    assert "collective_exposed_ms" not in names
    for other in tiny.CELLS:
        assert "als_solve_ms" not in {
            m["name"] for m in harness.find_cell(other).metrics("per_layer")}


def test_an_untraced_run_is_correct_and_every_job_takes_four_iterations(tree):
    line = json.loads(json.dumps(_run(tree)))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s", "time_to_target_s",
                                    "call_ms_p95", "setup_s"}
    assert line["window"]["epochs_to_target"] == [4]
    assert line["window"]["compiles_in_window"] == 0
    assert line["window"]["calls"] == line["window"]["epochs"]
    held = {k for k, v in line["compared"].items() if v["limit"] is not None}
    assert held == set(_LIMITS)


def test_a_traced_run_reads_the_old_metrics_and_no_solve_it_cannot_see(
        tree, monkeypatch):
    """The recorded trace is K-means': no operation of it bears the solve's
    name, so both solve readers report nothing, never 0."""
    tiny.as_v5e(monkeypatch, harness)
    tiny.recorded_trace(monkeypatch, harness)
    line = _run(tree, seed=tiny.SEED + 4, trace=True)
    assert line["correct"] is True
    assert {"step_mfu", "update_roofline_share", "device_idle_share",
            "peak_hbm_gib", "epochs_to_target", "data_prep_s",
            "backend_compile_s"} == set(line["metrics"])
    assert line["metrics"]["epochs_to_target"]["value"] == 4


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
    assert line["compared"]["quality_gap"]["value"] > 2 * _LIMITS["quality_gap"]
    assert line["compared"]["step1_diff"]["value"] > 0.6


def test_fault_the_item_half_step_skipped(tree, monkeypatch):
    """Inside the program: the users are solved, the items keep their
    factors. After the first iteration the users' table is right and the
    items' has not moved, which the gaps hardly see (they are scaled by the
    tables' median change, and the users move a hundred times as far); the
    monitor, read with the old items, is far off, and so is everything from
    the second iteration on."""
    from harp_tpu.collectives import lax_ops
    from harp_tpu.models import als

    def users_only(u_plane, i_plane, u0, v0, u_rpw, i_rpw, cfg,
                   axis_name="workers"):
        def iteration(carry, _):
            u, v = carry
            u_block = als._half_step_dense(v, u_plane, u_rpw, cfg)
            u = lax_ops.allgather(u_block, axis_name)
            sse, cnt = als._monitor_dense(u_block, v, u_plane, cfg)
            return (u, v), jnp.sqrt(sse / jnp.maximum(cnt, 1.0))

        (u, v), rmse = jax.lax.scan(iteration, (u0, v0), None,
                                    length=cfg.iterations)
        return u, v, rmse

    monkeypatch.setattr(als, "_train_dense", users_only)
    line = _run(tree)
    assert line["correct"] is False
    assert line["compared"]["quality_gap"]["value"] > 5 * _LIMITS["quality_gap"]
    assert line["compared"]["step3_diff"]["value"] > 0.5


def test_work_against_a_hand_count():
    cell = harness.find_cell(CELL)
    work = cell.part("work").work(cell.config, cell.traffic)
    nnz, rows, cols, k = 10_000_054, 71_567, 10_681, 100
    per_system = 1_000_000 / 3 + 20_000          # k^3/3 + 2 k^2
    assert work["samples_per_epoch"] == nnz
    assert work["solve_flops_per_epoch"] == pytest.approx(
        82_248 * per_system, rel=1e-12)
    assert work["solve_bytes_per_epoch"] == 82_248 * 10_200 * 4
    assert work["flops_per_epoch"] == pytest.approx(
        2 * nnz * 20_200 + 82_248 * per_system + 2 * 82_248 * 10_000,
        rel=1e-12)
    assert work["bytes_per_epoch"] == 24 * nnz + 2 * 82_248 * 100 * 4
    # shapes alone: neither the layout nor the solver changes the count
    other = cell.part("work").work(
        {**cell.config, "layout": "sparse", "solver": "cholesky"},
        cell.traffic)
    assert other == work
    assert (rows + cols, k) == (82_248, cell.config["rank"])


def _reader(name):
    cell = harness.find_cell(CELL)
    return cell, harness.load_module(os.path.join(
        cell.bench_dir, "metrics", name + ".py"))


def _ctx(cell, device_ops, epochs=10):
    return types.SimpleNamespace(
        cell=cell, window=types.SimpleNamespace(epochs=epochs),
        trace=types.SimpleNamespace(device_ops=device_ops),
        work=cell.part("work").work(cell.config, cell.traffic),
        peak=lambda: harness.peak_of(cell.bench_dir, "TPU v5 lite"))


def test_the_solve_readers_sum_the_kernels_events_by_their_fixed_name():
    cell, ms = _reader("als_solve_ms")
    _, share = _reader("als_solve_roofline_share")
    ops = [("convolution_add_fusion.5", 2.3), ("als_spd_solve.14", 0.40),
           ("als_spd_solve.13", 0.10), ("als_spd_solve_like.2", 9.0)]
    ctx = _ctx(cell, ops)
    assert ms.read(ctx) == pytest.approx(50.0)
    # HBM bound: 82,248 systems x 10,200 float32 at 819 GB/s = 4.097 ms
    assert share.read(ctx) == pytest.approx(100 * 4.0973 / 50.0, rel=1e-3)
    assert 0 < share.read(ctx) < 100


@pytest.mark.parametrize("ops", [[], [("fusion.19", 1.0), ("copy.3", 0.5)]])
def test_the_solve_readers_report_nothing_where_no_solve_ran(ops):
    """The parent's program has no kernel of that name, a run without a
    trace no operations at all: nothing is reported, never 0."""
    cell, ms = _reader("als_solve_ms")
    _, share = _reader("als_solve_roofline_share")
    ctx = _ctx(cell, ops)
    assert ms.read(ctx) is None and share.read(ctx) is None
    ctx.trace = None
    assert ms.read(ctx) is None and share.read(ctx) is None
