"""Share of the tiled pipeline's stage seconds spent on the host: cut,
put, readback and stitch over those plus dispatch and compute, from the
engines' ``PipelineStats`` across the window. Stages overlap, so this is
a share of work, not of wall time. Nothing to read where no request was
tiled."""

from __future__ import annotations

HOST = ("cut_seconds", "put_seconds", "readback_seconds", "stitch_seconds")
DEVICE = ("dispatch_seconds", "compute_seconds")


def read(run):
    if not run.pipeline_delta("chunks"):
        return None
    host = sum(run.pipeline_delta(k) or 0.0 for k in HOST)
    device = sum(run.pipeline_delta(k) or 0.0 for k in DEVICE)
    if host + device <= 0:
        return None
    return 100.0 * host / (host + device)
