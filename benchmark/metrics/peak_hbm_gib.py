"""``memory_stats()["peak_bytes_in_use"]`` after the window, on the fullest
device, before the reference runs. Layer: device."""


def read(ctx):
    peak = ctx.counters["peak_bytes"]
    return None if peak is None else peak / 2.0 ** 30
