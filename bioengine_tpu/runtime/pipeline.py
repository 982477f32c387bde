"""Overlapped inference pipeline — bounded-depth async dispatch.

The engine's original tiled hot path was strictly serial: cut tiles on
the host, block on ``jax.device_put``, compute, force a ``np.asarray``
readback, stitch, repeat — the device idled through every host phase
and the host idled through every device phase. XLA dispatch is
asynchronous (a jitted call returns a future-like Array immediately),
so the fix is structural, not a kernel change:

    staging thread   cut/pad chunk k+1 into a reusable staging buffer
    caller thread    device_put + dispatch chunk k (returns instantly),
                     force the readback of chunk k-depth+1
    stitch thread    ramp-blend chunk k-depth into the accumulator

``run_pipeline`` orchestrates those three roles around any
(fill, dispatch, force, stitch) stage functions, keeps at most
``depth`` chunks in flight on the device (bounding HBM), at most
``prefetch`` staged chunks on the host (bounding RAM), and accounts
every stage in a ``PipelineStats``. Each stage is measured once, by
``tracing.stage`` (utils/tracing.py): the interval the sums receive is
the one the process-wide stage timeline, a sampled request's span tree
and a profiler trace show.

``StagingPool`` recycles the host-side staging buffers per
(shape, dtype) so steady-state tiled inference stops paying a fresh
``pad_to`` + ``np.concatenate`` allocation per chunk, and
``DispatchExecutor`` is the async front door: one long-lived dispatch
thread per engine that coroutines await through ``asyncio.wrap_future``
instead of spawning a thread per prediction via ``asyncio.to_thread``.
"""

from __future__ import annotations

import contextvars
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Iterable, Optional

import numpy as np

from bioengine_tpu.utils import metrics, tracing


def _collect_pipelines(instances: list) -> list:
    """Fold every live PipelineStats into process totals for the
    metrics plane — the same objects Replica.describe reads per
    replica, summed to the device-busy/overlap signal a scheduler
    wants per worker."""
    totals = dict.fromkeys(PipelineStats._FIELDS, 0.0)
    for st in instances:
        with st._lock:
            for f in totals:
                totals[f] += getattr(st, f)
    return [
        metrics.Sample(
            f"pipeline_{name}",
            round(value, 4),
            kind="counter",
            help=f"overlapped-pipeline cumulative {name.replace('_', ' ')}",
        )
        for name, value in totals.items()
    ]


_PIPELINE_STATS = metrics.InstanceSet("pipeline_stats", _collect_pipelines)


class PipelineStats:
    """Cumulative per-stage accounting for one engine's pipeline.

    ``compute_seconds`` is the estimated device-busy time: chunks
    execute serially on one device, so chunk *i* occupies it from
    max(its dispatch, the previous force completing) until its own
    force completes. ``overlap_efficiency`` = device-busy / wall — 1.0
    means the device never waited on the host. On CPU backends XLA
    dispatch is near-synchronous, so the numbers are informational.

    Every ``*_seconds`` sum but ``compute`` and ``wall`` is fed by one
    ``tracing.stage`` of the same name (``cut_seconds`` by
    ``engine.cut``, ``queue_seconds`` by ``engine.queue``,
    ``preprocess_seconds`` by ``runtime.preprocess``, ...);
    ``readback_seconds`` is ``device_wait_seconds`` + ``d2h_seconds``
    (mostly the wait for the device, not host work). The direct and
    serial paths feed the same fields as the pipelined one, one chunk
    per program call; ``runs`` counts ``run_pipeline`` runs alone.
    ``rows_executed`` are the batch rows of every program call, padding
    rows included; ``rows_useful`` the tiles or items asked for.
    """

    _FIELDS = (
        "runs",
        "chunks",
        "items",
        "requests",
        "queue_seconds",
        "preprocess_seconds",
        "postprocess_seconds",
        "cut_seconds",
        "put_seconds",
        "dispatch_seconds",
        "compute_seconds",
        "device_wait_seconds",
        "d2h_seconds",
        "readback_seconds",
        "stitch_seconds",
        "wall_seconds",
        "h2d_bytes",
        "d2h_bytes",
        "rows_executed",
        "rows_useful",
    )

    def __init__(self, depth: int = 0):
        self._lock = threading.Lock()
        self.depth = depth
        self.max_in_flight = 0
        for name in self._FIELDS:
            setattr(self, name, 0)
        _PIPELINE_STATS.add(self)

    def add(self, **deltas: float) -> None:
        with self._lock:
            for name, delta in deltas.items():
                setattr(self, name, getattr(self, name) + delta)

    def observe_in_flight(self, n: int) -> None:
        with self._lock:
            if n > self.max_in_flight:
                self.max_in_flight = n

    @property
    def overlap_efficiency(self) -> float:
        with self._lock:
            wall = self.wall_seconds
            busy = self.compute_seconds
        return busy / wall if wall > 0 else 0.0

    def as_dict(self) -> dict:
        with self._lock:
            d = {name: getattr(self, name) for name in self._FIELDS}
            d["depth"] = self.depth
            d["max_in_flight"] = self.max_in_flight
        for key in list(d):
            if key.endswith("_seconds"):
                d[key] = round(d[key], 4)
        d["overlap_efficiency"] = round(self.overlap_efficiency, 4)
        return d


class StagingPool:
    """Free-list of reusable host staging buffers keyed by
    (shape, dtype).

    ``acquire`` hands back a previously released buffer when one is
    available (its contents are STALE — the caller overwrites the rows
    it uses and zeroes the rest) and allocates otherwise. The pool
    never holds more buffers than the pipeline had concurrently
    outstanding, so memory stays bounded by depth + prefetch."""

    def __init__(self):
        self._free: dict[tuple, list[np.ndarray]] = {}
        self._lock = threading.Lock()
        self.allocated = 0  # lifetime allocations (reuse effectiveness)

    def acquire(self, shape: tuple, dtype) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            stack = self._free.get(key)
            if stack:
                return stack.pop()
            self.allocated += 1
        return np.zeros(shape, dtype)

    def release(self, buf: np.ndarray) -> None:
        key = (buf.shape, buf.dtype.str)
        with self._lock:
            self._free.setdefault(key, []).append(buf)


class DispatchExecutor:
    """One long-lived dispatch thread per engine — the async front
    door. Coroutines submit whole predictions here and await the
    future; the event loop never blocks and no per-call thread is
    spawned (``asyncio.to_thread`` churns a pool slot per request and
    gives every caller its own thread racing for the same device)."""

    def __init__(self, name: str = "engine-dispatch"):
        self._name = name
        self._lock = threading.Lock()
        self._pool: Optional[ThreadPoolExecutor] = None
        self._closed = False

    def submit(self, fn: Callable, *args: Any, **kwargs: Any) -> Future:
        with self._lock:
            if self._closed:
                # terminal: a submit after close must not resurrect the
                # executor (the new thread would leak — nothing closes
                # this dispatcher twice). Callers racing an eviction get
                # a clear, retryable error instead.
                raise RuntimeError(f"dispatcher '{self._name}' is closed")
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix=self._name
                )
            return self._pool.submit(fn, *args, **kwargs)

    def close(self) -> None:
        """Terminal and idempotent; already-submitted work still runs."""
        with self._lock:
            self._closed = True
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)


_DONE = object()


def run_pipeline(
    descs: Iterable[Any],
    *,
    fill: Callable[[Any], Any],
    dispatch: Callable[[Any, Any], Any],
    force: Callable[[Any], Any],
    stitch: Callable[[Any, Any], None],
    depth: int,
    stats: PipelineStats,
    prefetch: Optional[int] = None,
) -> None:
    """Stream ``descs`` through fill -> dispatch -> force -> stitch.

    - ``fill(desc)`` (staging thread): host prep, returns the staged
      payload.
    - ``dispatch(desc, staged)`` (caller thread): hand the chunk to the
      device, return a future-like handle WITHOUT blocking.
    - ``force(handle)`` (caller thread): block until the device result
      is on the host, return it (and account its own wait and copy).
    - ``stitch(desc, host)`` (stitch thread): fold the result into the
      caller's accumulator.

    At most ``depth`` dispatched-but-unforced chunks exist at any time
    (the HBM bound) and at most ``prefetch`` staged chunks wait on the
    host. Exceptions from any stage abort the pipeline and re-raise in
    the caller. Returns when every desc has been stitched."""
    depth = max(int(depth), 1)
    prefetch = depth if prefetch is None else max(int(prefetch), 1)
    cut_q: queue.Queue = queue.Queue(maxsize=prefetch)
    stitch_q: queue.Queue = queue.Queue(maxsize=depth + 1)
    stop = threading.Event()
    errors: list[BaseException] = []

    def _put(q: queue.Queue, item: Any) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def cut_worker() -> None:
        try:
            for desc in descs:
                if stop.is_set():
                    return
                with tracing.stage("engine.cut") as cut:
                    staged = fill(desc)
                stats.add(cut_seconds=cut.seconds)
                if not _put(cut_q, (desc, staged)):
                    return
            _put(cut_q, _DONE)
        except BaseException as exc:  # noqa: BLE001 — re-raised in caller
            errors.append(exc)
            stop.set()

    def stitch_worker() -> None:
        try:
            while not stop.is_set():
                try:
                    item = stitch_q.get(timeout=0.05)
                except queue.Empty:
                    continue
                if item is _DONE:
                    return
                desc, host = item
                with tracing.stage("engine.stitch") as blend:
                    stitch(desc, host)
                stats.add(stitch_seconds=blend.seconds)
        except BaseException as exc:  # noqa: BLE001 — re-raised in caller
            errors.append(exc)
            stop.set()

    # each worker runs in a copy of the caller's context: its stages
    # carry the caller's request number and land in a sampled request's
    # tree (a Context can be entered by one thread at a time)
    cut_t = threading.Thread(
        target=contextvars.copy_context().run, args=(cut_worker,),
        name="pipeline-cut", daemon=True,
    )
    stitch_t = threading.Thread(
        target=contextvars.copy_context().run, args=(stitch_worker,),
        name="pipeline-stitch", daemon=True,
    )
    cut_t.start()
    stitch_t.start()

    window: deque = deque()  # (desc, handle, dispatch_done_at)
    last_force_done: Optional[float] = None
    t_wall = time.perf_counter()

    def force_oldest() -> None:
        nonlocal last_force_done
        desc, handle, dispatched_at = window.popleft()
        host = force(handle)
        done = time.perf_counter()
        busy_from = dispatched_at
        if last_force_done is not None and last_force_done > busy_from:
            busy_from = last_force_done
        stats.add(compute_seconds=max(done - busy_from, 0.0))
        last_force_done = done
        _put(stitch_q, (desc, host))

    try:
        while not stop.is_set():
            try:
                item = cut_q.get(timeout=0.05)
            except queue.Empty:
                continue
            if item is _DONE:
                break
            desc, staged = item
            handle = dispatch(desc, staged)
            window.append((desc, handle, time.perf_counter()))
            stats.add(chunks=1)
            stats.observe_in_flight(len(window))
            if len(window) >= depth:
                force_oldest()
        while window and not stop.is_set():
            force_oldest()
        _put(stitch_q, _DONE)
    except BaseException:
        stop.set()
        raise
    finally:
        # unbounded joins: both workers exit promptly once the stream
        # ends or ``stop`` is set (their queue waits poll it), and the
        # caller reads the stitch accumulator right after this returns —
        # a timed-out join would hand back a result the stitch thread is
        # still mutating
        cut_t.join()
        stitch_t.join()
        stats.add(wall_seconds=time.perf_counter() - t_wall, runs=1)
    if errors:
        raise errors[0]
