"""What the stage readers share: the program's own stage timeline laid
onto the device trace.

The program records every chunk-grained stage of its engine and runtime
(``engine.request``, ``engine.put``, ``runtime.preprocess``, ...) with
``time.time_ns()`` at both ends in a bounded process-wide timeline
(``bioengine_tpu.utils.tracing.get_stages``). The worker and its
replicas run in the harness's process and the timeline outlives
``worker.stop()``, so a reader asks for the stages of the traced span's
wall-clock ends (``run.trace["wall_window"]``) and lays them onto the
trace's clock with ``Reduced.at``. A program without the timeline (one
older than the stages) gives nothing to read: every function here then
returns ``None``.

Interval arithmetic is in plain functions of made-up intervals, as in
``trace_reduce``.
"""

from __future__ import annotations

import bisect
import statistics
from typing import Iterable, Optional

from benchmarks import trace_reduce
from benchmarks.trace_reduce import Interval

REQUEST = "engine.request"
QUEUE = "engine.queue"
DISPATCH_THREAD = "dispatch-"          # DispatchExecutor's thread_name_prefix
PIPELINE_THREAD = "pipeline-"          # run_pipeline's cut and stitch threads
ENGINE_EMPTY = "(engine empty: no request in hand, engine.queue empty)"
HAND_OVER = "engine.queue (a task submitted, the dispatch thread yet to take it)"
IN_PROGRAM = "(between the operations of a running program)"
# what a request's _meta.duration_ms is made of, one after the other
REQUEST_CHAIN = (
    "runtime.batch_wait", "runtime.assemble", "engine.queue", REQUEST,
    "runtime.split",
)


def timeline(since_ns: int, until_ns: int) -> Optional[list[dict]]:
    from bioengine_tpu.utils import tracing

    get_stages = getattr(tracing, "get_stages", None)
    return None if get_stages is None else get_stages(since_ns, until_ns)


def covered(pieces: Iterable[Interval], cover: Iterable[Interval]) -> int:
    """Length of ``pieces`` (disjoint) that lies inside ``cover``."""
    cover = trace_reduce.merge(cover)
    starts = [s for s, _ in cover]
    total = 0
    for piece in pieces:
        i = max(bisect.bisect_right(starts, piece[0]) - 1, 0)
        while i < len(cover) and cover[i][0] < piece[1]:
            total += trace_reduce.overlap(piece, cover[i])
            i += 1
    return total


def by_stage(
    pieces: Iterable[Interval], stages: list[tuple[int, int, str, str]]
) -> dict[str, int]:
    """Idle ns by the innermost stage on the dispatch thread that covers
    each instant of ``pieces``. ``stages`` are (start, end, name, thread).
    Where the innermost is ``engine.predict`` itself (the dispatch
    thread waits on the pipeline's own threads) the stage of those that
    covers the instant is named beside it. Where no stage of the
    dispatch thread covers it the engine has no request in hand: a task
    is waiting to be taken (``engine.queue``, which is a wait and so
    never the innermost work), or nothing was submitted."""
    on_dispatch = [
        s for s in stages if s[3].startswith(DISPATCH_THREAD) and s[2] != QUEUE
    ]
    others = [s for s in stages if s[3].startswith(PIPELINE_THREAD)]
    queued = [s for s in stages if s[2] == QUEUE]
    out: dict[str, int] = {}
    for lo, hi in pieces:
        cuts = {lo, hi}
        for start, end, _, _ in stages:
            cuts.update(t for t in (start, end) if lo < t < hi)
        edges = sorted(cuts)
        for a, b in zip(edges, edges[1:]):
            over = [s for s in on_dispatch if s[0] <= a and s[1] >= b]
            if not over:
                waiting = any(s[0] <= a and s[1] >= b for s in queued)
                label = HAND_OVER if waiting else ENGINE_EMPTY
            else:
                label = min(over, key=lambda s: s[1] - s[0])[2]
                if label == "engine.predict":
                    helper = [s for s in others if s[0] <= a and s[1] >= b]
                    label += (
                        f" > {min(helper, key=lambda s: s[1] - s[0])[2]} "
                        f"({helper[0][3]})" if helper else " (between stages)"
                    )
                elif label == REQUEST:
                    label += " (between stages)"
            out[label] = out.get(label, 0) + b - a
    return out


def nearest_offsets(ends: list[int], marks: list[int]) -> list[int]:
    """For each of ``ends``, its distance (signed, ns) after the nearest
    of ``marks``."""
    marks = sorted(marks)
    out = []
    for end in ends:
        i = bisect.bisect_left(marks, end)
        near = marks[max(i - 1, 0) : i + 1]
        if near:
            out.append(end - min(near, key=lambda m: abs(end - m)))
    return out


def idle_split(run) -> Optional[dict]:
    """The traced span's idle time of device 0, split by whether the
    engine had a request in hand. Computed once per run, logged as a
    table by stage, kept in ``run.trace``."""
    from benchmarks.harness import log

    trace = run.trace
    if not trace or "reduced" not in trace or "wall_window" not in trace:
        return None
    if "stage_idle" in trace:
        return trace["stage_idle"]
    trace["stage_idle"] = None
    stages = timeline(*trace["wall_window"])
    if not stages:
        return None
    reduced = trace["reduced"]
    lo, hi = trace["span"]
    laid = [
        (reduced.at(s["start_ns"]), reduced.at(s["end_ns"]), s["name"], s["thread"])
        for s in stages
    ]
    requests = [(s, e) for s, e, name, _ in laid if name == REQUEST]
    if not requests:
        return None
    device = reduced.devices[0]
    ops = [(s, s + d) for _, s, d in device.ops]
    programs = [(s, s + d) for _, s, d in device.modules]
    idle = trace_reduce.gaps(ops, lo, hi)
    between = trace_reduce.gaps(ops + programs, lo, hi)
    idle_ns = sum(e - s for s, e in idle)
    in_request = covered(idle, requests)
    trace["stage_idle"] = split = {
        "in_request_pct": 100.0 * in_request / (hi - lo),
        "engine_empty_pct": 100.0 * (idle_ns - in_request) / (hi - lo),
    }
    table = by_stage(between, laid)
    table[IN_PROGRAM] = idle_ns - sum(e - s for s, e in between)
    log(f"idle by stage: {idle_ns / 1e9:.4f}s of {(hi - lo) / 1e9:.3f}s traced, "
        f"{in_request / 1e9:.4f}s with a request in the engine's hand")
    for label, ns in sorted(table.items(), key=lambda kv: -kv[1]):
        log(f"idle by stage: {ns / 1e9:8.4f}s  {label}")
    offsets = nearest_offsets(
        [e for _, e, name, _ in laid if name == "engine.device_wait" and lo < e <= hi],
        [s + d for _, s, d in device.modules],
    )
    if offsets:
        log(f"clocks: engine.device_wait ends {statistics.median(offsets) / 1e6:.3f} ms "
            f"(median of {len(offsets)}, {min(offsets) / 1e6:.3f} to "
            f"{max(offsets) / 1e6:.3f}) after the end of its XLA Modules event")
    log_request_chain(run, log)
    return split


def log_request_chain(run, log) -> None:
    """The means of the stages a request passes one after the other,
    beside the mean ``_meta.duration_ms`` of the window's requests."""
    trace = run.trace
    offset = trace["wall_window"][0] - int(trace["host_window"][0] * 1e9)
    lo, hi = (int(t * 1e9) + offset for t in run.window)
    stages = [
        s for s in timeline(lo, hi) or []
        if s["start_ns"] >= lo and s["end_ns"] <= hi
    ]
    means = {}
    for name in REQUEST_CHAIN:
        seconds = [s["duration_s"] for s in stages if s["name"] == name]
        if seconds:
            means[name] = 1e3 * statistics.fmean(seconds)
    served = [
        r["server_ms"] for r in run.requests
        if r.get("ok") and run.window[0] <= r["start"] and r["end"] <= run.window[1]
    ]
    if means and served:
        log("request chain, mean ms: "
            + ", ".join(f"{k} {v:.1f}" for k, v in means.items())
            + f"; sum {sum(means.values()):.1f} of _meta.duration_ms "
            f"{statistics.fmean(served):.1f} ({len(served)} requests)")
