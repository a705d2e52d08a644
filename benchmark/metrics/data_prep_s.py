"""Set-up seconds spent making the data from the seed and placing it through
the program's ``prepare`` (harness clock). Layer: launcher / session."""


def read(ctx):
    return ctx.spans.seconds("data_gen") + ctx.spans.seconds("prepare")
