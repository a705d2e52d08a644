"""Algorithmic work of one SGD-MF epoch, from the configuration's shapes
alone: per rating a rank-long prediction and two rank-long updates, whatever
layout (dense slab or sparse buckets) or kernel implements them."""


def work(config: dict, traffic: dict) -> dict:
    p = traffic["params"]
    nnz, rank = int(p["ratings"]), int(config["rank"])
    rows, cols = int(p["rows"]), int(p["cols"])
    return {"flops_per_epoch": 6.0 * rank * nnz,
            # each rating once (row, column, value: 12 B) and both factor
            # tables read and written once in float32
            "bytes_per_epoch": 12.0 * nnz + 2.0 * (rows + cols) * rank * 4.0,
            "samples_per_epoch": nnz}
