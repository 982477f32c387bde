"""Mean chunks a tiled request's tiles rode: delta ``job_chunks`` over
delta ``jobs`` of the engines' ``PipelineStats`` across the window (a
request whose n tiles start at a random row of a 16-row chunk spans
1 + (n - 1)/16 of them; one that always starts a fresh chunk, ceil(n/16)).
Also logs, from the stage timeline's ``engine.predict`` attributes
(``tiles``, ``chunks``, ``stream_wait_s``, ``stream_run_s``), the
window's requests by tile count: how many, and their mean chunks, wait
for the first dispatch and time from there to the answer. Nothing to
read from a program that does not count them, nor off the chip (the CPU
rehearsal carries no trace)."""

from __future__ import annotations

import statistics

from benchmarks.layer_metrics import _stages


def read(run):
    if not run.trace:
        return None
    jobs = run.pipeline_delta("jobs")
    chunks = run.pipeline_delta("job_chunks")
    if not jobs or chunks is None:
        return None
    log_by_tiles(run)
    return chunks / jobs


def by_tiles(stages: list[dict]) -> dict[int, dict]:
    """The ``engine.predict`` stages that carry the stream's attributes,
    by tile count: requests, mean chunks, mean ``stream_wait_s`` and
    ``stream_run_s`` in ms."""
    groups: dict[int, list[dict]] = {}
    for s in stages:
        attrs = s["attrs"]
        if s["name"] == "engine.predict" and "stream_wait_s" in attrs:
            groups.setdefault(attrs["tiles"], []).append(attrs)
    return {
        tiles: {
            "requests": len(group),
            "chunks": statistics.fmean(a["chunks"] for a in group),
            "stream_wait_ms": 1e3 * statistics.fmean(a["stream_wait_s"] for a in group),
            "stream_run_ms": 1e3 * statistics.fmean(a["stream_run_s"] for a in group),
        }
        for tiles, group in sorted(groups.items())
    }


def log_by_tiles(run) -> None:
    """One line a tile count over the window's requests (the rounds of
    a closed loop show in the 16-tile line)."""
    from benchmarks.harness import log

    trace = run.trace
    if "wall_window" not in trace or "host_window" not in trace:
        return
    offset = trace["wall_window"][0] - int(trace["host_window"][0] * 1e9)
    lo, hi = (int(t * 1e9) + offset for t in run.window)
    stages = [
        s for s in _stages.timeline(lo, hi) or []
        if s["start_ns"] >= lo and s["end_ns"] <= hi
    ]
    for tiles, row in by_tiles(stages).items():
        log(f"stream by tiles: {tiles} tiles, {row['requests']} requests, "
            f"chunks {row['chunks']:.3f}, stream_wait_ms {row['stream_wait_ms']:.1f}, "
            f"stream_run_ms {row['stream_run_ms']:.1f}")
