"""Share of the traced window in which no operation ran on the device:
1 - (union of the busy intervals on the TPU plane's "XLA Ops" line,
averaged over the chips) / traced seconds."""

from __future__ import annotations


def read(run):
    trace = run.trace
    if not trace or "reduced" not in trace:
        return None
    lo, hi = trace["span"]
    return 100.0 * (1.0 - trace["reduced"].busy_s(trace["span"]) / ((hi - lo) / 1e9))
