"""``BENCHMARK.json`` against the contract it is written to, and every file
the harness finds by name is there."""

import json
import os
import re

import pytest

from benchmark import harness
from tests.benchmark import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(tiny.REPO, "BENCHMARK.json")) < 65536
    assert 1 <= manifest["run_seconds"] <= 51
    cells = len(manifest["workloads"])
    # a full check with all 24 cells has to fit into 43200 s
    r = manifest["run_seconds"]
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(
        1, cells // 4)


def test_names_units_and_entry_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert w["name"] == f'{w["config"]}.{w["traffic"]}'
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        assert m["moves"] in {e["name"] for e in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    assert any("mfu" in n for n in names)


@pytest.mark.parametrize("cell_name", tiny.CELLS)
def test_every_file_of_a_cell_is_found_by_name(cell_name):
    cell = harness.find_cell(cell_name)
    for kind in ("driver", "reference", "work"):
        assert cell.part(kind) is not None
    assert hasattr(cell.part("driver"), "Driver")
    assert hasattr(cell.part("reference"), "Reference")
    for metric in cell.metrics("per_layer"):
        reader = harness.load_module(os.path.join(
            cell.bench_dir, "metrics", metric["name"] + ".py"))
        assert callable(reader.read)
    assert cell.limits, "no number is held to a limit"
    assert cell.config["control"]["kind"] in ("program", "reference")
    assert len(cell.metrics("end_to_end")) >= 2
    assert len(cell.metrics("per_layer")) >= 1


def test_configuration_files_state_every_field_the_driver_passes(manifest):
    for c in manifest["configs"]:
        cells = [harness.find_cell(w["name"]) for w in manifest["workloads"]
                 if w["config"] == c["name"]]
        found = cells[0]
        for field in found.part("driver").FIELDS:
            assert field in found.config, (c["name"], field)
        # a cut is the configuration's own: each key it names is a key of
        # the file or a parameter of one of its cells' traffic, with the
        # published value beside it and the deployment the cut stands for
        assert found.config["reduced"] == c["reduced"]
        params = {k for cell in cells for k in cell.traffic["params"]}
        for key in c["reduced"]:
            assert key in found.config or key in params, (c["name"], key)
            assert key in found.config.get("published", {}), (c["name"], key)
        if c["reduced"]:
            deployment = found.config.get("deployment")
            assert isinstance(deployment, str) and deployment.strip(), c["name"]


def _cut(tree, reduced, published=True):
    """``kmeans-d100`` in a copy of the benchmark, stated as cut in
    ``reduced`` (its entry and its file alike)."""
    path = os.path.join(tree, "benchmark", "configs", "kmeans-d100.json")
    with open(path) as fh:
        config = json.load(fh)
    config.update(reduced=reduced, deployment="a pod's 8M-point share of one chip")
    if published:
        config["published"] = {key: 33554432 for key in reduced}
    with open(path, "w") as fh:
        json.dump(config, fh)
    with open(os.path.join(tree, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    next(c for c in manifest["configs"]
         if c["name"] == "kmeans-d100")["reduced"] = reduced
    return manifest


def test_a_traffic_parameter_cut_with_its_published_value_passes(
        tmp_path, monkeypatch):
    tree = tiny.found_in(monkeypatch, harness, tiny.copy(str(tmp_path)))
    test_configuration_files_state_every_field_the_driver_passes(
        _cut(tree, ["points"]))


def test_a_cut_without_its_published_value_fails(tmp_path, monkeypatch):
    tree = tiny.found_in(monkeypatch, harness, tiny.copy(str(tmp_path)))
    manifest = _cut(tree, ["points"], published=False)
    with pytest.raises(AssertionError):
        test_configuration_files_state_every_field_the_driver_passes(manifest)


def test_a_cut_of_a_key_the_configuration_does_not_have_fails(
        tmp_path, monkeypatch):
    tree = tiny.found_in(monkeypatch, harness, tiny.copy(str(tmp_path)))
    manifest = _cut(tree, ["num_points"])
    with pytest.raises(AssertionError, match="num_points"):
        test_configuration_files_state_every_field_the_driver_passes(manifest)