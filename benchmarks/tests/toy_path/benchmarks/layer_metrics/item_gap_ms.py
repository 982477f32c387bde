"""Milliseconds between one item of a streamed reply and the next,
median over every gap of the window's requests. Nothing to read where
every reply came as one item."""

from __future__ import annotations

import statistics


def read(run):
    gaps = [
        (b - a) * 1000.0
        for r in run.in_window if r.get("ok")
        for a, b in zip(r.get("items", []), r.get("items", [])[1:])
    ]
    return statistics.median(gaps) if gaps else None
