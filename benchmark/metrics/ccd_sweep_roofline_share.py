"""Least time the chip could take for one outer iteration's rank-one sweeps
(the larger of their operations over the peak and their bytes over the peak;
the configuration's work function gives both from the shapes alone, for a
maintained residual over the ratings) over the device time an iteration
spends in the sweep's operations (``ccd_sweep_ms``). Nothing where no such
operation ran. Layer: models / kernels."""

import os

from benchmark import harness


def read(ctx):
    seconds = harness.load_module(os.path.join(
        ctx.cell.bench_dir, "metrics", "ccd_sweep_ms.py")).sweep_seconds(ctx)
    if not seconds or "sweep_flops_per_epoch" not in ctx.work:
        return None
    peak, chips = ctx.peak(), ctx.cell.chips
    least = max(ctx.work["sweep_flops_per_epoch"] / peak["bf16_flops_per_s"],
                ctx.work["sweep_bytes_per_epoch"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / chips / seconds
