"""Milliseconds a request spends above the model-runner runtime: RPC
plane, app proxy, entry deployment, handle, router and replica queue.
Median over the window of (client latency - the reply's
``_meta.duration_ms``, which the runtime replica stamps)."""

from __future__ import annotations

import statistics


def read(run):
    gaps = [
        r["latency_ms"] - r["server_ms"] for r in run.in_window if r.get("ok")
    ]
    return statistics.median(gaps) if gaps else None
