"""The harness end to end on the CPU at tiny shapes, every cell (the
four-chip cell on four virtual devices): the result line is to the contract,
and the real entry refuses a machine without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness
from tests.benchmark import tiny

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench")))


def _metrics(tree, group, cell):
    manifest = harness.load_json(os.path.join(tree, "BENCHMARK.json"))
    return {m["name"]: m["unit"] for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_untraced_run_reports_every_end_to_end_metric(tree, cell):
    result = harness.run_cell(cell, tiny.SEED, 0.5, False,
                              require_accelerator=False, root=tree)
    line = json.loads(json.dumps(result))          # what run.py prints
    assert list(line)[:5] == CONTRACT_KEYS and list(line)[-1] == "compared"
    assert "breakdown" not in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    want = _metrics(tree, "end_to_end", cell)
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["window"]["compiles_in_window"] == 0
    assert min(line["window"]["epochs_to_target"]) >= 20
    assert line["device"]["platform"] == "cpu"
    for number in line["compared"].values():
        assert set(number) == {"value", "limit"}


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_traced_run_reports_every_per_layer_metric(tree, cell, monkeypatch):
    tiny.as_v5e(monkeypatch, harness)
    tiny.recorded_trace(monkeypatch, harness)
    result = harness.run_cell(cell, tiny.SEED + 1, 0.5, True,
                              require_accelerator=False, root=tree)
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == CONTRACT_KEYS and list(line)[-1] == "compared"
    want = _metrics(tree, "per_layer", cell)
    if cell != tiny.ML20M_X4:
        assert "collective_exposed_ms" not in want
    else:
        # the recorded one-chip trace holds no collective: the reader finds
        # nothing to read and the harness leaves the metric out, never 0
        assert "collective_exposed_ms" not in line["metrics"]
        want.pop("collective_exposed_ms")
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10


def _entry(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         tiny.KMEANS, "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_real_entry_refuses_a_machine_without_a_tpu():
    done = _entry(tiny.REPO)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "needs 1 TPU chip" in done.stderr


def test_real_entry_refuses_a_directory_without_the_program(tree):
    done = _entry(tree)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "not in this checkout" in done.stderr
