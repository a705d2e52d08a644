"""The cell ``emgmm-k100d100.aniso-6m`` through the harness on the CPU at
20,000 points: ``correct`` as the configuration is written, false
under its bfloat16 control and under planted faults; the generator's shapes,
structure and determinism; the work function against a hand count; the two
kernel readers on a reduction they can and cannot read; an ``EMGMM``
without ``prepare`` fails the driver at once. Every entry is found by name."""

import hashlib
import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, harness, traffic
from tests.benchmark import tiny
from tests.benchmark.test_als_cell import _rewrite
from tests.benchmark.test_faults import _patch_driver

CELL = "emgmm-k100d100.aniso-6m"
CONFIG = "emgmm-k100d100"
READERS = ("em_estep_ms", "em_estep_roofline_share")
# tiny traffic: the point count, and uniform weights so that every one of
# the 100 components holds about 200 points, twice its dimension (at Zipf
# weights the smallest would hold 39, and its covariance no estimate); the
# structure, every width and the first model's rule stay
_POINTS = 20_000
_SEED = tiny.SEED + 39
# limits at this size on the CPU, where float32 is float32 (the program reads
# 1.1e-7 to 1.6e-7, 1.0e-6 and 0.6e-6 to 1.8e-6 on three seeds; the bfloat16
# control is not finite by its third call, and 4e-3 on step1_diff)
_LIMITS = {"quality_gap": 1e-6, "step1_diff": 1e-4, "step3_diff": 1e-4}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tiny.build(str(tmp_path_factory.mktemp("bench")))

    def cut(doc):
        doc["params"].update(points=_POINTS, weight_exponent=0.0)
        doc["target"]["at_most"] = 1e9       # every job ends at its first call

    _rewrite(os.path.join(root, "benchmark", "workloads", CELL + ".json"), cut)
    _rewrite(os.path.join(root, "benchmark", "configs", CONFIG + ".json"),
             lambda doc: doc.update(limits=_LIMITS))
    return root


def _run(tree, seed=_SEED, trace=False):
    return harness.run_cell(CELL, seed, 0.3, trace,
                            require_accelerator=False, root=tree)


def test_the_cell_is_in_the_manifest_as_specified():
    cell = harness.find_cell(CELL)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic["generator"] == "anisotropic_mixture"
    assert cell.traffic["params"] == {
        "points": 6_000_000, "components": 100, "weight_exponent": 1.0,
        "eig_min": 0.1, "eig_max": 10.0, "center_scale": 1.0,
        "init_offset": 0.5, "structure_seed": 7}
    assert cell.traffic["epochs_per_call"] == 1
    assert cell.traffic["target"]["quality"] == "neg_mean_loglik"
    job = cell.traffic["max_epochs"] // 3
    assert 1 <= job <= 8 and cell.traffic["max_epochs"] == 3 * job
    c = cell.config
    assert c["model"] == "harp_tpu.models.em.EMGMM"
    assert (c["num_components"], c["dim"], c["covariance"], c["reg"]) == (
        100, 100, "full", 1e-4)
    assert c["device_op_names"] == {"estep": ["em_estep"]}
    assert c["control"] == {**c["control"], "kind": "reference",
                            "products": "bfloat16"}
    assert c["reduced"] == [] and len(c["assumed"]) >= 6
    assert "jax_default_matmul_precision" not in c
    assert len(c["source"]) <= 200 and "daal_em" in c["source"]
    assert cell.part("driver").FIELDS == ("num_components", "reg")
    assert set(cell.limits) <= set(compare.NUMBERS) and cell.limits
    entries = {group: [e for e in cell.manifest[group] if e["name"] in names]
               for group, names in (("configs", {CONFIG}),
                                    ("workloads", {CELL}),
                                    ("per_layer", set(READERS)))}
    assert [len(v) for v in entries.values()] == [1, 1, 2]
    assert entries["configs"][0]["source"] == c["source"]
    assert entries["configs"][0]["reduced"] == []
    assert entries["workloads"][0]["traffic"] == "aniso-6m"
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {*READERS, "step_mfu", "update_roofline_share",
            "device_idle_share", "peak_hbm_gib", "epochs_to_target",
            "data_prep_s", "backend_compile_s"} == names
    for m in entries["per_layer"]:
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        assert (m["layer"], m["moves"]) == ("models / kernels",
                                            "samples_per_s")
    for other in cell.manifest["workloads"]:
        if other["name"] != CELL:
            assert not set(READERS) & {m["name"] for m in harness.find_cell(
                other["name"]).metrics("per_layer")}
    assert sum(w["chips"] == 4 for w in cell.manifest["workloads"]) == 1


def test_the_kernel_bears_the_name_the_configuration_lists():
    from harp_tpu.ops import em_kernels

    names = harness.find_cell(CELL).config["device_op_names"]
    assert names == {"estep": [em_kernels.NAME]}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(tiny.BENCH, "configs", CONFIG + ".reference.py")
    with open(path) as fh:
        imports = [line for line in fh if line.startswith(("import ", "from "))]
    assert imports and not [line for line in imports if "harp_tpu" in line]


# -- the generator ------------------------------------------------------------ #

_GEN = {"points": 64, "components": 100, "weight_exponent": 1.0,
        "eig_min": 0.1, "eig_max": 10.0, "center_scale": 1.0,
        "init_offset": 0.5, "structure_seed": 7}
_CFG = {"dim": 100, "num_components": 100}


def _generate(seed=tiny.SEED, **params):
    return traffic.generate({"generator": "anisotropic_mixture",
                             "params": {**_GEN, **params}}, _CFG, seed)


def test_the_generator_gives_the_shapes_and_one_data_set_a_seed():
    a, b = _generate(), _generate()
    assert a["points"].shape == (64, 100) and a["points"].dtype == np.float32
    assert a["samples_per_epoch"] == 64
    assert a["weights0"].shape == (100,) and np.all(a["weights0"] == 0.01)
    assert a["means0"].shape == (100, 100) and a["covs0"].shape == (
        100, 100, 100)
    for key in ("points", "means0", "covs0"):
        assert np.array_equal(a[key], b[key]), key
    c = _generate(seed=tiny.SEED + 1)
    assert not np.array_equal(a["points"], c["points"])
    assert np.array_equal(a["means0"], c["means0"])      # the structure's
    # the first model's covariances are the sample's, plus 1e-3 I, each alike
    want = np.cov(a["points"].astype(np.float64), rowvar=False)
    np.testing.assert_allclose(a["covs0"][7], want + 1e-3 * np.eye(100),
                               rtol=1e-5, atol=1e-6)
    assert np.array_equal(a["covs0"][0], a["covs0"][99])


def test_the_structure_is_the_stated_one():
    """Zipf weights (19.3 % the largest, 0.19 % the smallest at exponent 1),
    each covariance's eigenvalues in [0.1, 10] on orthonormal axes, and the
    means the centres plus 0.5 N(0, I); the draws of the structure's random
    numbers pinned (no BLAS in them)."""
    path = os.path.join(tiny.BENCH, "generators", "anisotropic_mixture.py")
    module = harness.load_module(path)
    centres, factors, weights, means0 = module._structure(_GEN, 100, 100)
    assert weights.max() == pytest.approx(0.19277, abs=1e-5)
    assert weights.min() == pytest.approx(0.0019277, abs=1e-7)
    cov = np.einsum("kij,klj->kil", factors.astype(np.float64),
                    factors.astype(np.float64))
    eig = np.linalg.eigvalsh(cov)
    assert eig.min() >= 0.1 * (1 - 1e-5) and eig.max() <= 10 * (1 + 1e-5)
    offset = (means0 - centres).std()
    assert 0.45 < offset < 0.55
    digest = hashlib.sha256(np.concatenate(
        [weights.astype(np.float32), centres.ravel(), means0.ravel()]
    ).tobytes()).hexdigest()
    assert digest == _STRUCTURE_DIGEST


_STRUCTURE_DIGEST = (
    "c40a85076dda37899db9122d2754de58a884360eb20f68b78dad3f2662248912")


def test_a_seed_beyond_32_bits_and_half_the_points():
    data = _generate(seed=2 ** 32 + 39, points=200)
    half = traffic.halved(data)
    assert half["points"].shape == (100, 100) and half["samples_per_epoch"] == 100
    assert half["means0"].shape == (100, 100)       # the model is not halved


# -- the cell on the CPU ---------------------------------------------------- #

def test_an_untraced_run_is_correct(tree):
    line = json.loads(json.dumps(_run(tree)))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"samples_per_s", "time_to_target_s",
                                    "call_ms_p95", "setup_s"}
    assert line["window"]["epochs_to_target"] == [1]
    assert line["window"]["compiles_in_window"] == 0
    held = {k for k, v in line["compared"].items() if v["limit"] is not None}
    assert held == set(_LIMITS)
    for name, limit in _LIMITS.items():
        assert line["compared"][name]["value"] < limit / 3, name


def test_a_traced_run_reads_the_old_metrics_and_no_kernel_it_cannot_see(
        tree, monkeypatch):
    """The recorded trace is K-means': no operation of it bears the name the
    configuration lists, so the two readers report nothing, never 0."""
    tiny.as_v5e(monkeypatch, harness)
    tiny.recorded_trace(monkeypatch, harness)
    line = _run(tree, trace=True)
    assert line["correct"] is True
    assert {"step_mfu", "update_roofline_share", "device_idle_share",
            "peak_hbm_gib", "epochs_to_target", "data_prep_s",
            "backend_compile_s"} == set(line["metrics"])


def test_the_bfloat16_control_fails_every_limit_the_tiny_cell_holds(tree):
    cell = harness.find_cell(CELL, tree)
    data = harness.make_data(cell, _SEED + 1)
    first, reference = harness.follow_reference(cell, data)
    _, control = harness.follow_reference(cell, data, products=jnp.bfloat16)
    read = compare.numbers(first, control, reference)
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


def test_fault_half_the_points_left_out(tree, monkeypatch):
    def halved(init):
        def broken(self, config, cell_traffic, data, chips, overrides=None):
            init(self, config, cell_traffic, traffic.halved(data), chips,
                 overrides)
        return broken

    _patch_driver(monkeypatch, tree, CELL, __init__=halved)
    line = _run(tree)
    assert line["correct"] is False
    assert line["compared"]["step1_diff"]["value"] > 10 * _LIMITS["step1_diff"]


def test_a_program_without_prepare_fails_the_driver_at_once(tree,
                                                            monkeypatch):
    """An ``EMGMM`` with no ``prepare`` (the one-call ``fit`` API): the
    driver ends at its first call into the program, before anything is
    placed."""
    from harp_tpu.models import em

    cell = harness.find_cell(CELL, tree)
    data = _generate(points=256)
    monkeypatch.delattr(em.EMGMM, "prepare")
    driver = cell.part("driver").Driver(cell.config, cell.traffic, data, 1)
    with pytest.raises(AttributeError, match="prepare"):
        driver.prepare()


def test_work_against_a_hand_count():
    cell = harness.find_cell(CELL)
    work = cell.part("work").work(cell.config, cell.traffic)
    n, k, d = 6_000_000, 100, 100
    assert work["samples_per_epoch"] == n
    assert work["flops_per_epoch"] == 4 * n * k * d * d + 4 * n * k * d
    assert work["flops_per_epoch"] == 2.424e13
    assert work["bytes_per_epoch"] == 4 * n * d
    assert work["estep_flops_per_epoch"] == work["flops_per_epoch"]
    assert work["estep_bytes_per_epoch"] == work["bytes_per_epoch"]


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


def test_the_readers_sum_the_kernels_events_by_its_fixed_name():
    cell, _ = _reader(READERS[0])
    ops = [("fusion.7", 0.3), ("em_estep.8", 9.0), ("em_estep.9", 1.5),
           ("em_estep_like.2", 5.0), ("custom-call.29", 0.2)]
    read = {name: _reader(name)[1].read(_ctx(cell, ops)) for name in READERS}
    assert read["em_estep_ms"] == pytest.approx(1050.0)
    # compute bound: 2.424e13 FLOPs at 197 TFLOP/s is 123.05 ms
    assert read["em_estep_roofline_share"] == pytest.approx(
        100 * 123.05 / 1050.0, rel=1e-3)


@pytest.mark.parametrize("ops", [[], [("fusion.19", 1.0), ("copy.3", 0.5)]])
def test_the_readers_report_nothing_where_no_kernel_ran(ops):
    cell, _ = _reader(READERS[0])
    ctx = _ctx(cell, ops)
    assert [_reader(name)[1].read(ctx) for name in READERS] == [None] * 2
    ctx.trace = None
    assert [_reader(name)[1].read(ctx) for name in READERS] == [None] * 2
