"""95th percentile over the window's calls of the program's ``step.dispatch``
phase: the jitted call alone, which returns at the enqueue. Layer: launcher /
session."""

import numpy as np

from benchmark import program_spans


def read(ctx):
    records = program_spans.window_phases(ctx) or []
    spans = [r.end - r.start for r in records
             if r.name == program_spans.DISPATCH]
    if not spans:
        return None
    return 1e3 * float(np.percentile(spans, 95))
