"""Algorithmic work of one WDA-SMACOF iteration over dense N x N matrices,
from the configuration's shapes and its stated stored types alone, whatever
implements the step. With ``b_w`` the bytes of a stored weight:

* B(X)X and the stress, one pass: every cell's target distance (float32) and
  weight read once, ``(4 + b_w) N^2`` bytes; per cell the three coordinate
  differences, their squares' sum, the root, the annealed target, the ratio,
  three products into the row's sums and the stress's square: 25 FLOPs;
* the Guttman solve, ``cg_iters + 1`` matvecs of the weighted Laplacian (one
  for the warm start's residual): each reads every weight once, ``b_w N^2``
  bytes, and multiplies and adds it into ``target_dim`` columns, 6 FLOPs a
  cell at dimension 3.

The embedding and the CG's vectors (N x 3) are under a ten-thousandth of
that and left out. The weights are an input: a program may not derive them
from the distances to save the read."""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def work(config: dict, traffic: dict) -> dict:
    n = int(traffic["params"]["points"])
    cells = float(n) * n
    b_w = _DTYPE_BYTES[config["weights_dtype"]]
    assert config["distances_dtype"] == "float32"
    matvecs = int(config["cg_iters"]) + 1
    bc_flops, bc_bytes = 25.0 * cells, (4 + b_w) * cells
    matvec_flops = 2.0 * int(config["target_dim"]) * matvecs * cells
    matvec_bytes = float(b_w) * matvecs * cells
    return {"flops_per_epoch": bc_flops + matvec_flops,
            "bytes_per_epoch": bc_bytes + matvec_bytes,
            "samples_per_epoch": n * (n - 1),
            # the two kernels' shares: what each is measured against
            "bc_flops_per_epoch": bc_flops,
            "bc_bytes_per_epoch": bc_bytes,
            "matvec_flops_per_epoch": matvec_flops,
            "matvec_bytes_per_epoch": matvec_bytes}
