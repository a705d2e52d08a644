"""Least time the chip could take for one epoch's algorithmic work (the
larger of operations over peak and bytes over peak, per chip) over the device
time of one epoch in the trace. Device time is the whole step program's: no
kernel has a stable name yet. Layer: models / kernels."""


def bound(ctx):
    """``(seconds, "flops" | "hbm")``: the roofline of one epoch per chip."""
    peak, chips = ctx.peak(), ctx.cell.chips
    flops = ctx.work["flops_per_epoch"] / chips / peak["bf16_flops_per_s"]
    hbm = ctx.work["bytes_per_epoch"] / chips / peak["hbm_bytes_per_s"]
    return (flops, "flops") if flops >= hbm else (hbm, "hbm")


def read(ctx):
    if ctx.trace is None or not ctx.trace.step_s:
        return None
    per_call = int(ctx.cell.traffic["epochs_per_call"])
    epoch_s = sum(ctx.trace.step_s) / len(ctx.trace.step_s) / per_call
    return 100.0 * bound(ctx)[0] / epoch_s
