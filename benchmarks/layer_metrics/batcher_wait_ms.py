"""Mean milliseconds a request waits in the runtime's ContinuousBatcher
before its group is flushed: delta of
``batcher_queue_wait_seconds_total`` (a sum since the batcher's birth)
over delta of ``batcher_requests_total`` across the window. Nothing to
read from a program that keeps no such sum, nor off the chip (the CPU
rehearsal carries no trace)."""

from __future__ import annotations


def read(run):
    if not run.trace:
        return None
    seconds = run.counter_delta("batcher_queue_wait_seconds_total")
    requests = run.counter_delta("batcher_requests_total")
    if seconds is None or not requests:
        return None
    return 1000.0 * seconds / requests
