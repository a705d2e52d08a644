"""How often jax traced a step or one-shot program of the training path from
the run's start to the end of its window: the ``program.trace`` marks the
traced functions leave in the program's phase ring (one per trace, never on a
cached call). Layer: compile cache / AOT."""

from benchmark import program_spans


def read(ctx):
    records = program_spans.run_phases(ctx)
    if records is None:
        return None
    return sum(r.name == program_spans.TRACE_MARK for r in records)
