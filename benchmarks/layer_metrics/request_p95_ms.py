"""The 95th percentile of client latency over every request of the
window, a failed one counted as the window's length: what
``window_metrics`` takes for an end-to-end tail, read here as the tail
of the served request where its runs spread too widely to hold a bound
(the closed loop's rounds and the host's pauses move it by 5-7 % a set
on cpsam-vitl.fov). Host clock; reads off the chip too."""

from __future__ import annotations

from benchmarks import window_metrics


def read(run):
    latencies = window_metrics.window_latencies(run)
    return window_metrics.percentile(latencies, 95) if latencies else None
