"""Per epoch, the time of XLA's collective operations (all-reduce,
collective-permute, all-gather, reduce-scatter) during which no compute
operation ran on that device, mean over the chips. Layer: Table collectives."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.collective_s or not ctx.window.epochs:
        return None
    return 1e3 * ctx.trace.collective_exposed_s / ctx.window.epochs
