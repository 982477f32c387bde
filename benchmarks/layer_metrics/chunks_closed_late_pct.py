"""Share of the engines' chunks that went partly filled because the
tile stream's issuing thread had a free place and nothing else to put
there (late closing): 100 x delta ``chunks_closed_late`` over delta
``chunks`` across the window. Nothing to read from a program that does
not count why a chunk closed, nor off the chip (the CPU rehearsal
carries no trace)."""

from __future__ import annotations


def read(run):
    if not run.trace:
        return None
    chunks = run.pipeline_delta("chunks")
    late = run.pipeline_delta("chunks_closed_late")
    if not chunks or late is None:
        return None
    return 100.0 * late / chunks
