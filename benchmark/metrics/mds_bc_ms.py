"""Device milliseconds one WDA-SMACOF iteration spends in the fused B(X)X and
stress pass: the self time, in the traced window, of the device operations
whose names the configuration lists under ``device_op_names.bc`` (the kernel
carries a fixed ``name=``), over the window's iterations. Nothing where no
such operation ran (a program without the kernel, a configuration without
the list). Layer: models / kernels."""

import re


def kernel_seconds(ctx, key):
    """Seconds per iteration in the operations listed under
    ``device_op_names[key]``, or None where nothing matches."""
    names = set(ctx.cell.config.get("device_op_names", {}).get(key, ()))
    if ctx.trace is None or not names or not ctx.window.epochs:
        return None
    found = [s for name, s in ctx.trace.device_ops
             if re.sub(r"\.\d+$", "", name) in names]
    if not found:
        return None
    return sum(found) / ctx.window.epochs


def roofline_share(ctx, key):
    """The least time the chip could take for the work the configuration's
    work function counts under ``<key>_flops_per_epoch`` and
    ``<key>_bytes_per_epoch``, over :func:`kernel_seconds`, in per cent."""
    seconds = kernel_seconds(ctx, key)
    if not seconds or f"{key}_bytes_per_epoch" not in ctx.work:
        return None
    peak = ctx.peak()
    least = max(ctx.work[f"{key}_flops_per_epoch"] / peak["bf16_flops_per_s"],
                ctx.work[f"{key}_bytes_per_epoch"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / ctx.cell.chips / seconds


def read(ctx):
    seconds = kernel_seconds(ctx, "bc")
    return None if seconds is None else 1e3 * seconds
