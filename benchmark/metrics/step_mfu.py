"""The whole step's share of the chips' peak: algorithmic FLOPs per sample
times the samples per second of the traced window, over chips times the peak
bf16 FLOP/s. Layer: whole step."""


def read(ctx):
    flops_per_sample = (ctx.work["flops_per_epoch"]
                        / ctx.work["samples_per_epoch"])
    peak = ctx.cell.chips * ctx.peak()["bf16_flops_per_s"]
    return 100.0 * flops_per_sample * ctx.counters["samples_per_s"] / peak
