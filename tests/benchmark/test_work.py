"""The work functions read the configuration's shapes alone, so the same
operations and bytes are counted whichever layout or kernel runs them."""

import pytest

from benchmark import harness
from tests.benchmark import tiny


@pytest.mark.parametrize("layout", ("auto", "dense", "sparse"))
@pytest.mark.parametrize("cell_name", (tiny.ML10M, tiny.ML20M_X4))
def test_sgdmf_work_does_not_depend_on_the_layout(cell_name, layout):
    cell = harness.find_cell(cell_name)
    base = cell.part("work").work(cell.config, cell.traffic)
    other = cell.part("work").work({**cell.config, "layout": layout},
                                   cell.traffic)
    assert other == base
    p = cell.traffic["params"]
    assert base["flops_per_epoch"] == 6 * 100 * p["ratings"]
    assert base["bytes_per_epoch"] == (
        12 * p["ratings"] + 2 * (p["rows"] + p["cols"]) * 100 * 4)
    assert base["samples_per_epoch"] == p["ratings"]


@pytest.mark.parametrize("lane_pad", (True, False))
@pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
def test_kmeans_work_does_not_depend_on_padding_or_dtype(lane_pad, dtype):
    cell = harness.find_cell(tiny.KMEANS)
    work = cell.part("work").work(
        {**cell.config, "lane_pad": lane_pad, "compute_dtype": dtype},
        cell.traffic)
    assert work == {"flops_per_epoch": 4.0 * 8e6 * 100 * 100,
                    "bytes_per_epoch": 4.0 * 8e6 * 100,
                    "samples_per_epoch": 8000000}


def test_an_unknown_device_has_no_peak():
    cell = harness.find_cell(tiny.KMEANS)
    assert harness.peak_of(cell.bench_dir, "TPU v5 lite")[
        "bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peak_of(cell.bench_dir, "cpu")
