"""The whole step's share of the chip's peak: the model's operations
for the USEFUL work served during the traced window (for the image path
the input pixels users sent: no overlap between pieces of an image, no
bucket or chunk padding) over traced seconds x chips x bf16 peak. Work
is in the path's own unit, operations per unit are the configuration's
work module's (``flops_per_unit``). A request that straddles an edge of
the traced window counts by the share of its time inside it."""

from __future__ import annotations

from benchmarks.harness import work_module
from benchmarks.window_metrics import work_served


def read(run):
    trace = run.trace
    if not trace or not trace.get("peaks"):
        return None
    t0, t1 = trace["host_window"]
    work = work_served(run, t0, t1)
    config = run.cell.config
    per_unit = work_module(config).flops_per_unit(config)
    peak = trace["peaks"]["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * work * per_unit / ((t1 - t0) * peak)
