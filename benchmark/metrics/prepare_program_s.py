"""Seconds of ``prepare`` inside ``HarpSession.run``, the one-shot programs
(trace, compile or cache load, enqueue; SGD-MF's ``densify`` is one): the
program's ``session.run`` phases directly under ``*.prepare``. Layer:
launcher / session."""

from benchmark import program_spans


def read(ctx):
    return program_spans.prepare_children_s(ctx, program_spans.RUN)
