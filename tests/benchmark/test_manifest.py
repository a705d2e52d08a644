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
        cell = next(w for w in manifest["workloads"] if w["config"] == c["name"])
        found = harness.find_cell(cell["name"])
        for field in found.part("driver").FIELDS:
            assert field in found.config, (c["name"], field)
        assert found.config["reduced"] == c["reduced"] == []
