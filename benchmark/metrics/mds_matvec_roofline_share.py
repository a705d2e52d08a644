"""Least time the chip could take for one iteration's matvecs of the weighted
Laplacian (the configuration's work function: every stored weight read once
a matvec, ``cg_iters + 1`` matvecs) over the device time an iteration spends
in their operations (``mds_matvec_ms``). Nothing where no such operation
ran. Layer: models / kernels."""

import os

from benchmark import harness


def read(ctx):
    return harness.load_module(os.path.join(
        ctx.cell.bench_dir, "metrics", "mds_bc_ms.py")).roofline_share(
            ctx, "matvec")
