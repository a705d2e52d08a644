"""Algorithmic work of one K-means epoch, from the configuration's shapes
alone: the same whatever layout, padding or kernel implements it."""


def work(config: dict, traffic: dict) -> dict:
    n = int(traffic["params"]["points"])
    k, d = int(config["num_centroids"]), int(config["dim"])
    # distances to k centroids (2 n k d) and the sums of the assigned
    # points as a one-hot product (2 n k d); the points are read once
    return {"flops_per_epoch": 4.0 * n * k * d,
            "bytes_per_epoch": 4.0 * n * d,
            "samples_per_epoch": n}
