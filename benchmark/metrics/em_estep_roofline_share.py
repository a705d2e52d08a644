"""Least time the chip could take for one iteration's E-step (the larger of
its operations over the peak and its bytes over the peak; the
configuration's work function gives both from the shapes alone: ``4 N K D^2
+ 4 N K D`` FLOPs, every point read once) over the device time an iteration
spends in the kernel's operations (``em_estep_ms``). Compute bound: at float32
precision the MXU runs six bfloat16 passes for each algorithmic product, on
operands padded to 104 rows a component and 128 lanes, so ~12 % is this
share's ceiling. Nothing where no such operation ran. Layer: models /
kernels."""

import os

from benchmark import harness


def read(ctx):
    return harness.load_module(os.path.join(
        ctx.cell.bench_dir, "metrics", "mds_bc_ms.py")).roofline_share(
            ctx, "estep")
