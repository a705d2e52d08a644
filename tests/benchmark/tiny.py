"""A copy of the benchmark at sizes a CPU test can hold: the committed
``BENCHMARK.json``, harness files, configurations (every width as committed)
and readers, with each cell's traffic cut to a few thousand samples."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "kmeans_v5e_1chip.xplane.pb")

KMEANS = "kmeans-d100.overlap-8m"
ML10M = "sgdmf-k100.ml10m"
ML20M_X4 = "sgdmf-k100.ml20m-x4"
CELLS = (KMEANS, ML10M, ML20M_X4)
SEED = 2 ** 31 + 11          # the driver's seeds do not fit 32 signed bits

# tiny traffic: sizes only; generators, epochs_per_call and widths stay
_TRAFFIC = {
    KMEANS: ({"points": 40000}, 0.9597, 40),
    ML10M: ({"rows": 704, "cols": 300, "ratings": 20000,
             "row_offset": 30, "col_offset": 10}, 0.98, 60),
    ML20M_X4: ({"rows": 704, "cols": 300, "ratings": 20000,
                "row_offset": 30, "col_offset": 10}, 0.98, 60),
}
# limits at these sizes on the CPU, where float32 is float32: the program
# reads 1e-7 (K-means) and 7e-4 (SGD-MF, bfloat16 operands); the controls
# read 0.09 and 0.014
_CONFIG = {
    "kmeans-d100": {"limits": {"step1_diff": 0.01, "quality_gap": 1e-5}},
    "sgdmf-k100": {"lr": 2e-3, "limits": {"step1_diff": 0.004,
                                            "quality_gap": 1e-3}},
}


def _rewrite(path: str, change) -> None:
    with open(path) as fh:
        doc = json.load(fh)
    change(doc)
    with open(path, "w") as fh:
        json.dump(doc, fh)


def copy(root: str) -> str:
    """The committed benchmark under ``root``, at its committed sizes."""
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    return root


def build(root: str) -> str:
    """Write the tiny tree under ``root`` and return ``root``."""
    copy(root)
    for cell, (params, target, max_epochs) in _TRAFFIC.items():
        def change(doc, params=params, target=target, max_epochs=max_epochs):
            doc["params"].update(params)
            doc["target"]["at_most"] = target
            doc["max_epochs"] = max_epochs
        _rewrite(os.path.join(root, "benchmark", "workloads", cell + ".json"),
                 change)
    for config, values in _CONFIG.items():
        _rewrite(os.path.join(root, "benchmark", "configs", config + ".json"),
                 lambda doc, values=values: doc.update(values))
    return root


def found_in(monkeypatch, harness, root: str) -> str:
    """``harness.find_cell`` reads the tree at ``root`` where a test names
    none, as the accepted tests do; returns ``root``."""
    real = harness.find_cell
    monkeypatch.setattr(harness, "find_cell",
                        lambda name, at=root: real(name, at))
    return root


def as_v5e(monkeypatch, harness) -> None:
    """Report the CPU devices under the v5e's kind and with a memory
    reading, so that the readers that divide by a peak or read the device's
    memory find one (the numbers mean nothing and are not read)."""
    real = harness.device_info

    def fake():
        return {**real(), "kind": "TPU v5 lite"}

    monkeypatch.setattr(harness, "device_info", fake)
    monkeypatch.setattr(harness, "memory_bytes", lambda chips, key: 2 ** 30)


def recorded_trace(monkeypatch, harness) -> None:
    """Run the window untraced and hand the readers the reduction of the
    recorded v5e trace: the CPU backend has no device plane to read."""
    from benchmark import trace_reduce

    def capture(run, span, name):
        return run(), trace_reduce.reduce(TRACE, spans=harness.SPANS)

    monkeypatch.setattr(harness, "capture_trace", capture)
