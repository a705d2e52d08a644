"""Least time the chip could take for one sweep's sampling (the larger of its
operations over the peak and its bytes over the peak; the configuration's
work function gives both from the shapes alone: ``4 N K`` operations for the
conditional, its running sum and the draw, and the token stream and both
count tables read and written once) over the device time a sweep spends in
the kernel's operations (``lda_sample_ms``). The operations are VPU work (a
vector unit's float32 operation a pair), measured against the MXU's
bfloat16 peak: this share's ceiling is a few per cent, and a low reading is
no slow kernel. Nothing where no such operation ran. Layer: models /
kernels."""

import os

from benchmark import harness


def read(ctx):
    return harness.load_module(os.path.join(
        ctx.cell.bench_dir, "metrics", "mds_bc_ms.py")).roofline_share(
            ctx, "sample")
