"""Seconds this process spent in XLA's backend compiler, from jax's
monitoring events: near nothing where the persistent cache hits. Layer:
compile cache / AOT."""


def read(ctx):
    return ctx.counters["backend_compile_s"]
