"""Milliseconds from a request's send to the first item of its streamed
reply, median over the window's requests that carry the stamp."""

from __future__ import annotations

import statistics


def read(run):
    waits = [
        (r["first_item"] - r["start"]) * 1000.0
        for r in run.in_window if r.get("ok") and "first_item" in r
    ]
    return statistics.median(waits) if waits else None
