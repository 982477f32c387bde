"""The whole step's share of the chip's peak: the model's operations
for the USEFUL input pixels served during the traced window (no tile
overlap, no bucket or chunk padding: operations per pixel of one native
tile times the pixels users sent) over traced seconds x chips x bf16
peak. A request that straddles an edge of the traced window counts by
the share of its time inside it."""

from __future__ import annotations

from benchmarks.harness import model_kwargs, pixels_served, work_module


def read(run):
    trace = run.trace
    if not trace or not trace.get("peaks"):
        return None
    t0, t1 = trace["host_window"]
    pixels = pixels_served(run, t0, t1)
    config = run.cell.config
    per_pixel = work_module(config).flops_per_pixel(
        model_kwargs(config), int(config["in_channels"]), int(config["native_tile"])
    )
    peak = trace["peaks"]["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * pixels * per_pixel / ((t1 - t0) * peak)
