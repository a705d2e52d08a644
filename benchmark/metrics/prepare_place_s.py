"""Seconds of ``prepare`` inside ``HarpSession.scatter`` / ``replicate_put``:
the program's ``session.place`` phases directly under ``*.prepare``. A
placement returns when the transfer is enqueued, so a tail it leaves shows in
what waits next. Layer: launcher / session."""

from benchmark import program_spans


def read(ctx):
    return program_spans.prepare_children_s(ctx, program_spans.PLACE)
