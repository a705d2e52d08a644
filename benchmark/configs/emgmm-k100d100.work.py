"""Algorithmic work of one full-covariance EM iteration over N points, from
the configuration's shapes alone, whatever implements the step:

* the E-step's whitened product, every component's ``A_k x − b_k`` for every
  point, ``2 N K D^2`` FLOPs, and its squares and sums, ``2 N K D``;
* the M-step's weighted second moments ``Σ_n r_nk x_n x_n'``, ``2 N K D^2``,
  and weighted sums and counts, ``2 N K D``;
* every point read once, float32: ``4 N D`` bytes.

The K factorizations of D x D (``K D^3 / 3`` and the inverses) and the
parameters are under a ten-thousandth of that and left out. The fused E-step
kernel does all of it: its shares are the same numbers."""


def work(config: dict, traffic: dict) -> dict:
    n = int(traffic["params"]["points"])
    k, d = int(config["num_components"]), int(config["dim"])
    flops = 4.0 * n * k * d * d + 4.0 * n * k * d
    nbytes = 4.0 * n * d
    return {"flops_per_epoch": flops, "bytes_per_epoch": nbytes,
            "samples_per_epoch": n,
            "estep_flops_per_epoch": flops, "estep_bytes_per_epoch": nbytes}
