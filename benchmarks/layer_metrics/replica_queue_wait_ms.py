"""Mean milliseconds a request waits for one of the runtime replica's
slots (``max_ongoing_requests``): delta of the summed
``replica_park_seconds`` (the replica's semaphore) plus, where the
handle's path goes through a deployment scheduler, of
``scheduler_queue_wait_seconds``, for ``deployment=runtime_deployment``,
over the delta of the requests parked there, across the window. Both are
histograms of the program's registry; a series carries ``sum`` and
``count``. Nothing to read off the chip (the CPU rehearsal carries no
trace, and its waits are not the chip's)."""

from __future__ import annotations

from typing import Optional

DEPLOYMENT = "runtime_deployment"


def histogram_delta(run, name: str, field: str) -> Optional[float]:
    def total(edge: str) -> Optional[float]:
        series = run.counters[edge]["families"].get(name, {}).get("series", [])
        values = [
            s[field] for s in series
            if field in s and s.get("labels", {}).get("deployment") == DEPLOYMENT
        ]
        return sum(values) if values else None

    start, end = total("start"), total("end")
    return None if start is None or end is None else end - start


def read(run):
    if not run.trace:
        return None
    parked = histogram_delta(run, "replica_park_seconds", "count")
    seconds = histogram_delta(run, "replica_park_seconds", "sum")
    if not parked or seconds is None:
        return None
    seconds += histogram_delta(run, "scheduler_queue_wait_seconds", "sum") or 0.0
    return 1000.0 * seconds / parked
