"""Seconds the models' ``prepare`` spent in the host's own work (casting,
padding, dedupe, bucketing): the self time of the program's ``*.prepare``
phase, outside its ``session.place`` and ``session.run`` children. Read from
the program's phase ring (``harp_tpu/telemetry/host_spans.py``). Layer:
launcher / session."""

from benchmark import program_spans


def read(ctx):
    return program_spans.prepare_self_s(ctx)
