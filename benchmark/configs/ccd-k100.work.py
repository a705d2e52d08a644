"""Algorithmic work of one CCD++ outer iteration with a maintained residual
(Yu et al., ICDM 2012, Algorithm 2), from the configuration's shapes alone,
whatever implements the step. Per feature t, with nnz ratings:

* per inner round and side: each rating's residual times the other side's
  entry into the numerator, that entry's square into the denominator (4
  FLOPs a rating: 8 nnz a round); each reads the residual and its two ids
  (12 bytes a rating: 24 nnz a round);
* the rank-one update of the residual, feature t's new product out and the
  next feature's old one in, in one pass: 4 FLOPs a rating, the residual
  read and written (8 bytes; the ids are those the rounds read).

So ``sweep_flops = rank (8 inner + 4) nnz`` and ``sweep_bytes = rank (24
inner + 8) nnz``, the issue's count (2.0e10 and 5.6e10 at rank 100, inner 2,
1e7 ratings: 68 ms of HBM on a v5e). The monitor squares the residual it
holds: 2 nnz FLOPs, 4 nnz bytes. The factor columns (8 (rows + cols) bytes a
feature and round) are under a thousandth of that and left out."""


def work(config: dict, traffic: dict) -> dict:
    p = traffic["params"]
    nnz, k = int(p["ratings"]), int(config["rank"])
    inner = int(config["inner_iterations"])
    sweep_flops = float(k) * (8 * inner + 4) * nnz
    sweep_bytes = float(k) * (24 * inner + 8) * nnz
    return {"flops_per_epoch": sweep_flops + 2.0 * nnz,
            "bytes_per_epoch": sweep_bytes + 4.0 * nnz,
            "samples_per_epoch": nnz,
            # the same without the monitor: what the sweeps' kernel is
            # measured against
            "sweep_flops_per_epoch": sweep_flops,
            "sweep_bytes_per_epoch": sweep_bytes}
