"""Mean milliseconds a tiled request's tiles waited in the engine's
tile stream before the device first saw them: delta of the engines'
``stream_wait_seconds`` (from the request's enrolment to the end of the
dispatch of the first chunk that holds its rows) over delta of ``jobs``
across the window. Nothing to read from a program that does not count
them, nor off the chip (the CPU rehearsal carries no trace)."""

from __future__ import annotations


def read(run):
    if not run.trace:
        return None
    jobs = run.pipeline_delta("jobs")
    seconds = run.pipeline_delta("stream_wait_seconds")
    if not jobs or seconds is None:
        return None
    return 1000.0 * seconds / jobs
