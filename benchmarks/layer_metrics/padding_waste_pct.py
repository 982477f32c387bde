"""Share of the batch rows the engine's programs executed that nobody
asked for: 100 x (1 - delta ``rows_useful`` / delta ``rows_executed``)
from the engines' ``PipelineStats`` across the window (9 tiles in a
chunk of 16 waste 7 rows). Nothing to read from a program that does not
count rows, nor off the chip (the CPU rehearsal carries no trace)."""

from __future__ import annotations


def read(run):
    if not run.trace:
        return None
    executed = run.pipeline_delta("rows_executed")
    useful = run.pipeline_delta("rows_useful")
    if not executed or useful is None:
        return None
    return 100.0 * (1.0 - useful / executed)
