"""Tokens generated a second over the window: the path's work served
(each request by the share of its time inside the window) over the
window's seconds."""

from __future__ import annotations

from benchmarks.window_metrics import work_served


def read(run):
    served = work_served(run, *run.window)
    return served / run.seconds if served > 0 else None
