"""Mean milliseconds from the dispatch of a tiled request's first chunk
to its answer (its last row blended), across the chunks it rode: delta
of the engines' ``stream_run_seconds`` over delta of ``jobs`` across the
window. Nothing to read from a program that does not count them, nor
off the chip (the CPU rehearsal carries no trace)."""

from __future__ import annotations


def read(run):
    if not run.trace:
        return None
    jobs = run.pipeline_delta("jobs")
    seconds = run.pipeline_delta("stream_run_seconds")
    if not jobs or seconds is None:
        return None
    return 1000.0 * seconds / jobs
