"""The end-to-end metrics of one window, from the requests' records.

``run`` is ``harness.RunData``. A record has ``start``, ``end`` (both on
``time.perf_counter``), ``latency_ms``, ``ok`` and ``work``: what the
request asked for in its path's own unit (input pixels, tokens
generated), as the path counted it when the request was sent.
"""

from __future__ import annotations

import numpy as np


def percentile(values: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def window_latencies(run) -> list[float]:
    """Client latency of every request of the window; a failed request
    counts as the worst a request of this run can be."""
    return [
        r["latency_ms"] if r["ok"] else run.seconds * 1000.0 for r in run.in_window
    ]


def work_served(run, t0: float, t1: float) -> float:
    """Work of the requests answered OK, each counted by the share of
    its time, from send to reply, that lay inside [t0, t1]: a request in
    flight at an edge has part of its work done on either side.
    (Counting whole requests only, a window of a hundred requests reads
    in steps of one percent, and the same loop read 1.1822 or 1.1938
    Mpx/s for 99 or 100 completed: chip runs of PR 25.)"""
    work = 0.0
    for r in run.requests:
        if r["ok"] and r["end"] > r["start"]:
            inside = max(0.0, min(r["end"], t1) - max(r["start"], t0))
            work += r["work"] * inside / (r["end"] - r["start"])
    return work


def end_to_end(run, setup_s: float) -> dict[str, float]:
    """Latencies and set-up for every path; the throughput under the
    name and in the unit the path gives it (``THROUGHPUT``: metric name,
    units of work to one of the metric's), where it names one."""
    out = {}
    if run.cell.path.THROUGHPUT:
        name, per = run.cell.path.THROUGHPUT
        out[name] = work_served(run, *run.window) / per / run.seconds
    out.update(
        latency_p50_ms=percentile(window_latencies(run), 50),
        latency_p95_ms=percentile(window_latencies(run), 95),
        setup_s=setup_s,
    )
    return out
