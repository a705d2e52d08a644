"""Device milliseconds one EM iteration spends in the fused E-step: the self
time, in the traced window, of the device operations whose names the
configuration lists under ``device_op_names.estep`` (the kernel carries a
fixed ``name=``), over the window's iterations. Nothing where no such
operation ran (a program without the kernel, a configuration without the
list). Layer: models / kernels."""

import os

from benchmark import harness


def read(ctx):
    seconds = harness.load_module(os.path.join(
        ctx.cell.bench_dir, "metrics", "mds_bc_ms.py")).kernel_seconds(
            ctx, "estep")
    return None if seconds is None else 1e3 * seconds
