"""Requests per flushed batch of the runtime's ContinuousBatcher:
delta of ``batcher_requests_total`` over delta of
``batcher_batches_total`` across the window."""

from __future__ import annotations


def read(run):
    requests = run.counter_delta("batcher_requests_total")
    batches = run.counter_delta("batcher_batches_total")
    if not requests or not batches:
        return None
    return requests / batches
