"""Least time the chip could take for one iteration's B(X)X and stress pass
(the larger of its operations over the peak and its bytes over the peak; the
configuration's work function gives both from the shapes and the stored
types alone: every target distance and weight read once) over the device
time an iteration spends in the pass's operations (``mds_bc_ms``). Nothing
where no such operation ran. Layer: models / kernels."""

import os

from benchmark import harness


def read(ctx):
    return harness.load_module(os.path.join(
        ctx.cell.bench_dir, "metrics", "mds_bc_ms.py")).roofline_share(
            ctx, "bc")
