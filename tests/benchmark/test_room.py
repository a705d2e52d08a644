"""Room for a seventh cell: every accepted test of ``BENCHMARK.json``'s
entries holds on a manifest that has one more configuration, its one-chip
cell and two per-layer metrics listed only there, whether those entries are
appended or put first. The new entries are ``kmeans-d100``'s files copied
under a new name, as a later PR would add them: no file is edited. The new
cell's traffic names either the built-in generator it was copied with or a
generator file written into the tree, a new kind of data."""

import json
import os
import shutil

import pytest

from benchmark import harness
from tests.benchmark import test_als_cell as als_tests
from tests.benchmark import test_call_ledger_cells as ledger_tests
from tests.benchmark import test_ccd_cell as ccd_tests
from tests.benchmark import test_manifest as manifest_tests
from tests.benchmark import test_traffic as traffic_tests
from tests.benchmark import test_wdamds_cell as wdamds_tests
from tests.benchmark import tiny

CONFIG = "room-d100"
CELL = CONFIG + ".overlap-8m"
# two readers, each a copy of a committed one under a new name
READERS = {"room_epochs": "epochs_to_target", "room_prep_s": "data_prep_s"}
ENTRIES = {
    "configs": {"name": CONFIG, "source": "a copy of kmeans-d100",
                "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
                "why": "a seventh configuration"},
    "workloads": {"name": CELL, "config": CONFIG, "traffic": "overlap-8m",
                  "chips": 1, "why": "a seventh cell"},
    "per_layer": [
        {"name": "room_epochs", "unit": "epochs", "better": "lower",
         "source": "program_counter", "layer": "models",
         "moves": "time_to_target_s", "workloads": [CELL]},
        {"name": "room_prep_s", "unit": "s", "better": "lower",
         "source": "host_clock", "layer": "launcher / session",
         "moves": "setup_s", "workloads": [CELL]}],
}
# a new kind of data: a generator of its own, as a file in the tree
GENERATOR_FILE = "room_mixture"
_GENERATOR = '''
import numpy as np


def generate(params, config, seed):
    rng = np.random.default_rng([int(seed), 38])
    points = rng.standard_normal((int(params["points"]), int(config["dim"])))
    return {"points": points.astype(np.float32),
            "samples_per_epoch": int(params["points"])}
'''


def _grow(root: str, placement: str, generator: str) -> dict:
    """Copy ``kmeans-d100``'s files under the new names into the tree at
    ``root``, its traffic on ``generator``, and list them in its
    ``BENCHMARK.json``; returns the manifest."""
    bench = os.path.join(root, "benchmark")
    for part in ("json", "driver.py", "reference.py", "work.py"):
        shutil.copy(os.path.join(bench, "configs", f"kmeans-d100.{part}"),
                    os.path.join(bench, "configs", f"{CONFIG}.{part}"))
    with open(os.path.join(bench, "workloads", tiny.KMEANS + ".json")) as fh:
        cell_traffic = json.load(fh)
    if generator == GENERATOR_FILE:
        os.makedirs(os.path.join(bench, "generators"), exist_ok=True)
        with open(os.path.join(bench, "generators", generator + ".py"),
                  "w") as fh:
            fh.write(_GENERATOR)
    cell_traffic["generator"] = generator
    with open(os.path.join(bench, "workloads", CELL + ".json"), "w") as fh:
        json.dump(cell_traffic, fh)
    for name, source in READERS.items():
        shutil.copy(os.path.join(bench, "metrics", source + ".py"),
                    os.path.join(bench, "metrics", name + ".py"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        manifest = json.load(fh)
    for group, entries in ENTRIES.items():
        entries = entries if isinstance(entries, list) else [entries]
        manifest[group] = (manifest[group] + entries if placement == "appended"
                           else entries + manifest[group])
    with open(path, "w") as fh:
        json.dump(manifest, fh, indent=1)
    return manifest


def _files_found(manifest, monkeypatch):
    for w in manifest["workloads"]:
        manifest_tests.test_every_file_of_a_cell_is_found_by_name(w["name"])


def _generators_resolve(manifest, monkeypatch):
    for w in manifest["workloads"]:
        traffic_tests.test_every_cell_of_the_manifest_names_a_generator_that_resolves(
            w["name"])
    for cell in tiny.CELLS:
        traffic_tests.test_every_committed_cell_names_a_generator_that_exists(
            cell)


# every accepted test that reads the manifest's entries
ACCEPTED = {
    "ccd.in_the_manifest":
        lambda m, mp: ccd_tests.test_the_cell_is_in_the_manifest_as_the_issue_states_it(),
    "als.in_the_manifest":
        lambda m, mp: als_tests.test_the_cell_is_in_the_manifest_as_the_issue_states_it(),
    "wdamds.in_the_manifest":
        lambda m, mp: wdamds_tests.test_the_cell_is_in_the_manifest_as_the_issue_states_it(),
    "wdamds.what_the_benchmark_had":
        lambda m, mp: wdamds_tests.test_what_the_benchmark_had_is_still_listed_in_its_order(),
    "wdamds.ccd_holds_on_what_was_there":
        lambda m, mp: wdamds_tests.test_ccd_cells_accepted_manifest_test_holds_on_what_was_there(mp),
    "call_ledger.manifest_is_the_parents":
        lambda m, mp: ledger_tests.test_the_manifest_is_the_parents(),
    "manifest.top_level_keys_and_sizes":
        lambda m, mp: manifest_tests.test_top_level_keys_and_sizes(m),
    "manifest.names_units_and_entry_keys":
        lambda m, mp: manifest_tests.test_names_units_and_entry_keys(m),
    "manifest.every_file_found_by_name": _files_found,
    "manifest.configuration_files_state_every_field":
        lambda m, mp: manifest_tests.test_configuration_files_state_every_field_the_driver_passes(m),
    "traffic.every_generator_resolves": _generators_resolve,
}


@pytest.mark.parametrize("generator", ("gaussian_mixture", GENERATOR_FILE))
@pytest.mark.parametrize("placement", ("appended", "first"))
@pytest.mark.parametrize("accepted", sorted(ACCEPTED))
def test_an_accepted_manifest_test_holds_with_a_seventh_cell(
        accepted, placement, generator, tmp_path, monkeypatch):
    root = tiny.found_in(monkeypatch, harness, tiny.copy(str(tmp_path)))
    committed = harness.find_cell(tiny.KMEANS).manifest
    manifest = _grow(root, placement, generator)
    seen = harness.find_cell(CELL)
    assert seen.traffic["generator"] == generator
    assert len(seen.manifest["workloads"]) == len(committed["workloads"]) + 1
    assert seen.manifest["workloads"][0 if placement == "first" else -1][
        "name"] == CELL
    assert set(READERS) <= {m["name"] for m in seen.metrics("per_layer")}
    assert not set(READERS) & {
        m["name"] for m in harness.find_cell(tiny.KMEANS).metrics("per_layer")}
    ACCEPTED[accepted](manifest, monkeypatch)
