"""A later PR adds a configuration, a cell and a per-layer metric as new
files and edits none that is there: copies under new names make a runnable
cell, found from ``BENCHMARK.json`` alone."""

import json
import os
import shutil

import pytest

from benchmark import harness
from tests.benchmark import tiny


@pytest.fixture()
def grown(tmp_path):
    root = tiny.build(str(tmp_path))
    bench = os.path.join(root, "benchmark")
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _, files in os.walk(bench) for p in files}
    # a new configuration: the four files beside each other, under its name
    for part in ("json", "driver.py", "reference.py", "work.py"):
        shutil.copy(os.path.join(bench, "configs", f"kmeans-d100.{part}"),
                    os.path.join(bench, "configs", f"kmeans-new.{part}"))
    # a new cell: one traffic file
    shutil.copy(os.path.join(bench, "workloads", tiny.KMEANS + ".json"),
                os.path.join(bench, "workloads", "kmeans-new.blobs.json"))
    # a new per-layer metric: one reader
    with open(os.path.join(bench, "metrics", "calls_per_job.py"), "w") as fh:
        fh.write("def read(ctx):\n"
                 "    jobs = len(ctx.window.jobs)\n"
                 "    return len(ctx.window.call_s) / jobs if jobs else None\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        manifest = json.load(fh)
    manifest["configs"].append({
        "name": "kmeans-new", "source": "a copy", "reduced": [], "why": "test",
        "file": "benchmark/configs/kmeans-new.json"})
    manifest["workloads"].append({
        "name": "kmeans-new.blobs", "config": "kmeans-new", "traffic": "blobs",
        "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "calls_per_job", "unit": "calls", "better": "lower",
        "source": "program_counter", "layer": "models",
        "moves": "time_to_target_s", "workloads": ["kmeans-new.blobs"]})
    with open(path, "w") as fh:
        json.dump(manifest, fh)
    after = {p: os.path.getmtime(os.path.join(d, p))
             for d, _, files in os.walk(bench) for p in files if p in before}
    assert after == before, "an existing file was edited"
    return root


def test_new_files_alone_make_a_runnable_cell(grown, monkeypatch):
    tiny.as_v5e(monkeypatch, harness)
    tiny.recorded_trace(monkeypatch, harness)
    traced = harness.run_cell("kmeans-new.blobs", tiny.SEED, 0.2, True,
                              require_accelerator=False, root=grown)
    assert traced["correct"] is True
    assert traced["metrics"]["calls_per_job"]["unit"] == "calls"
    assert traced["metrics"]["calls_per_job"]["value"] >= 4
    plain = harness.run_cell("kmeans-new.blobs", tiny.SEED, 0.2, False,
                             require_accelerator=False, root=grown)
    assert set(plain["metrics"]) == {"samples_per_s", "time_to_target_s",
                                     "call_ms_p95", "setup_s"}


def test_the_new_metric_is_not_read_in_the_old_cells(grown, monkeypatch):
    tiny.as_v5e(monkeypatch, harness)
    tiny.recorded_trace(monkeypatch, harness)
    old = harness.run_cell(tiny.KMEANS, tiny.SEED, 0.2, True,
                           require_accelerator=False, root=grown)
    assert "calls_per_job" not in old["metrics"]
