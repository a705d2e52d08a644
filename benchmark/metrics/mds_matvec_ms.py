"""Device milliseconds one WDA-SMACOF iteration spends in the weighted
Laplacian's matvecs (``cg_iters + 1`` of them): the self time, in the traced
window, of the device operations whose names the configuration lists under
``device_op_names.matvec``, over the window's iterations. Nothing where no
such operation ran. Layer: models / kernels."""

import os

from benchmark import harness


def read(ctx):
    seconds = harness.load_module(os.path.join(
        ctx.cell.bench_dir, "metrics", "mds_bc_ms.py")).kernel_seconds(
            ctx, "matvec")
    return None if seconds is None else 1e3 * seconds
