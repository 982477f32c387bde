"""Mean milliseconds a task waits for the engine's one dispatch thread:
delta of the engines' ``queue_seconds`` over delta of ``requests``
(``PipelineStats``, fed by the ``engine.queue`` stage) across the
window. Nothing to read from a program that does not count them, nor
off the chip (the CPU rehearsal carries no trace, and its waits are not
the chip's)."""

from __future__ import annotations


def read(run):
    if not run.trace:
        return None
    requests = run.pipeline_delta("requests")
    seconds = run.pipeline_delta("queue_seconds")
    if not requests or seconds is None:
        return None
    return 1000.0 * seconds / requests
