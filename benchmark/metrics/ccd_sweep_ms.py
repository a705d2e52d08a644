"""Device milliseconds one CCD++ outer iteration spends in the rank-one
sweeps: the self time, in the traced window, of the device operations whose
names the configuration lists under ``device_op_names.sweep`` (the sweep
kernel carries a fixed ``name=``), over the window's outer iterations.
Nothing where no such operation ran (a program without the kernel, a
configuration without the list). Layer: models / kernels."""

import re


def sweep_seconds(ctx):
    """Seconds per outer iteration, or None where nothing matches."""
    names = set(ctx.cell.config.get("device_op_names", {}).get("sweep", ()))
    if ctx.trace is None or not names or not ctx.window.epochs:
        return None
    found = [s for name, s in ctx.trace.device_ops
             if re.sub(r"\.\d+$", "", name) in names]
    if not found:
        return None
    return sum(found) / ctx.window.epochs


def read(ctx):
    seconds = sweep_seconds(ctx)
    return None if seconds is None else 1e3 * seconds
