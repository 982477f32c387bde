"""The engine's tile stream — one long-lived overlapped pipeline.

A tiled prediction is many tiles through one compiled program, a chunk
of ``tile_batch`` rows at a time. XLA dispatch is asynchronous (a jitted
call returns a future-like Array immediately), so host and device work
overlap when three roles run side by side:

    cut thread       cuts the tiles of the requests in hand, in arrival
                     order, into reusable staging buffers, up to
                     ``PREFETCH`` chunks ahead
    issuing thread   device_put + dispatch of chunk k (returns at once),
                     readback of chunk k-depth+1; the ONLY thread that
                     issues device work, and it does nothing else
    stitch thread    ramp-blends chunk k-depth into its requests'
                     accumulators

``TileStream`` owns those threads for as long as its engine lives, and
every prediction joins it: **the stream does not end between requests**.
A request has a thread of its own (``DispatchExecutor``) for its host
work: pre-processing, the tile plan, then it enrols its tiles and waits
for its last row to be blended, divides, post-processes and answers,
while the device already runs the next chunk. A chunk is filled across
the requests in hand (the head request's tiles first, then the next
one's of the same chunk shape), so the padding rows of one request
carry another's tiles. At most ``depth`` chunks are in flight (the HBM
bound). An open, partly filled chunk is closed late: it keeps taking
rows of newly enrolled requests until the issuing thread has a free
place in its window and no full chunk to put there, and then, while a
chunk is still in flight, until that chunk's device deadline; then it
goes, padded, as a lone request's tail always did.

**The device-deadline hold.** The stream times each chunk it reads
back: its device time, from when it had the device (its dispatch, or
the previous chunk's end) to its end, and its lead, the put + dispatch
it took on the issuing thread; it keeps the last ``TIMED_CHUNKS`` of
each program (key and row count). The newest in-flight chunk's
predicted end is ``max(its dispatch, the previous chunk's end)`` plus
the smallest device time its program has kept; the deadline is that end
less the smallest lead kept and the host-to-device copy that outlasts
the put call (``H2D_NS_PER_BYTE`` times the open chunk's bytes). A
reading errs long far more often than short (a compile in the dispatch,
a pause of the host in the wait, a chunk that found the device idle and
waited for its rows), and a long one would hold past the device's end:
so the smallest of the last few is taken, and one long reading is never
used. A partly filled open chunk goes at the first of: no chunk in
flight (the device is dry), the deadline, or a deadline that is unknown
(a program not timed yet) or already past. Until then it closes
``full``, ``key`` or ``direct`` as before, and the rows of a request
that enrols meanwhile join it. While it holds, the issuing thread reads
nothing back (that would block until the in-flight chunk ends); it
waits for the deadline on the stream's condition. So **a held chunk
starts on the device no later than the late rule would have started
it, give or take the lead's error**: its head request loses nothing,
and the rows that ride with it gain a chunk. A read-back that finds its
chunk already done has seen no end and teaches no device time: the
program's readings are forgotten, so a hold that overshot the end does
not repeat, and the next chunk behind it goes at once and is timed
afresh.

Every stage is measured once, by ``tracing.stage`` (utils/tracing.py):
the interval the ``PipelineStats`` sums receive is the one the
process-wide stage timeline, a sampled request's span tree and a
profiler trace show.

``StagingPool`` recycles the host-side staging buffers per
(shape, dtype) so steady-state tiled inference pays no fresh
``pad_to`` + ``np.concatenate`` allocation per chunk.
"""

from __future__ import annotations

import contextvars
import dataclasses
import logging
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Optional

import numpy as np

from bioengine_tpu.utils import metrics, tracing

# host chunks cut ahead of the issuing thread, the open one included:
# with the in-flight window that is every buffer a stream holds
PREFETCH = 2
# the readings of each program that the hold takes its smallest from
TIMED_CHUNKS = 4
# a held chunk goes this much earlier for each byte it puts: the
# host-to-device copy goes on after the put call returns (a 12.6 MB
# chunk of 16 x 256 x 256 x 3 f32 lands 4.0-5.3 ms after its put call
# began on a TPU v5e, of which the put + dispatch cover about 1.4; 4.0 ms
# over its bytes is 0.32 ns a byte)
H2D_NS_PER_BYTE = 0.32
# a read-back that waited less than this found its chunk already done:
# it saw no end, so it teaches no device time
FOUND_DONE_NS = 500_000


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
            help=f"engine pipeline cumulative {name.replace('_', ' ')}",
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

    Every ``*_seconds`` sum but ``compute``, ``wall``, ``hold`` and the
    two ``stream_*`` is fed by one ``tracing.stage`` of the same name
    (``cut_seconds`` by ``engine.cut``, ``queue_seconds`` by
    ``engine.queue``, ``preprocess_seconds`` by ``runtime.preprocess``,
    ...). The direct and serial paths feed the same fields as the
    stream, one chunk per program call. ``wall_seconds`` is, for the
    stream, the time it had a request in hand. ``rows_executed`` are the
    batch rows of every program call, padding rows included;
    ``rows_useful`` the tiles or items asked for; ``chunks_shared`` the
    stream's chunks that held rows of more than one request.

    The stream counts each of its chunks by why it closed
    (``chunks_closed_<reason>``, the four reasons of
    ``TileStream._close_chunk``; they sum to the stream's chunks, all
    of ``chunks`` where no prediction ran direct or serial) and, for
    each tiled request it answered (``jobs``), the chunks its rows rode
    (``job_chunks``), its wait from enrolment to the dispatch of its
    first chunk (``stream_wait_seconds``) and the time from there to
    its answer (``stream_run_seconds``). ``chunks_held`` are the chunks
    the late rule would have sent at once and the device-deadline hold
    kept (module docstring), ``chunks_held_filled`` those of them that
    took rows of a request that enrolled during the hold, and
    ``hold_seconds`` the time they were held (the sum of their
    ``engine.dispatch`` stages' ``held_ms``).
    """

    _FIELDS = (
        "chunks",
        "chunks_shared",
        "chunks_closed_full",
        "chunks_closed_key",
        "chunks_closed_direct",
        "chunks_closed_late",
        "chunks_held",
        "chunks_held_filled",
        "items",
        "requests",
        "jobs",
        "job_chunks",
        "queue_seconds",
        "preprocess_seconds",
        "postprocess_seconds",
        "cut_seconds",
        "put_seconds",
        "dispatch_seconds",
        "compute_seconds",
        "device_wait_seconds",
        "d2h_seconds",
        "stitch_seconds",
        "wall_seconds",
        "stream_wait_seconds",
        "stream_run_seconds",
        "hold_seconds",
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
    never holds more buffers than the stream had concurrently
    outstanding, so memory stays bounded by depth + ``PREFETCH``."""

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


# requests an engine has in hand at once (each has a thread of its own
# while it is); what arrives beyond that waits in ``engine.queue``
REQUEST_THREADS = 16


class DispatchExecutor:
    """The async front door: long-lived request threads per engine.
    Coroutines submit whole predictions here and await the future; the
    event loop never blocks and no thread is spawned per call. A
    request's thread does its host work (pre- and post-processing, the
    tile plan, the final divide) and otherwise waits for the engine's
    tile stream, which alone talks to the device: requests in hand
    overlap."""

    def __init__(self, name: str = "engine-dispatch"):
        self._name = name
        self._lock = threading.Lock()
        self._pool: Optional[ThreadPoolExecutor] = None
        self._closed = False

    def submit(self, fn: Callable, *args: Any, **kwargs: Any) -> Future:
        with self._lock:
            if self._closed:
                # terminal: a submit after close must not resurrect the
                # executor (the new threads would leak — nothing closes
                # this dispatcher twice). Callers racing an eviction get
                # a clear, retryable error instead.
                raise RuntimeError(f"dispatcher '{self._name}' is closed")
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=REQUEST_THREADS, thread_name_prefix=self._name
                )
            return self._pool.submit(fn, *args, **kwargs)

    def close(self) -> None:
        """Terminal and idempotent; already-submitted work still runs
        (and fails where the stream it waits for has closed)."""
        with self._lock:
            self._closed = True
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)


@dataclasses.dataclass
class InFlight:
    """One chunk handed to the device: its output (a future until
    forced), its useful rows, when the dispatch ended, how long its put
    + dispatch took, when the wait for it ended (``time.time_ns()``) and
    how long that wait took, and the stages it passed on the issuing
    thread (put, dispatch, device_wait, d2h)."""

    out: Any
    n: int
    dispatched_ns: int
    issue_ns: int = 0
    ready_ns: int = 0
    waited_ns: int = 0
    stages: list = dataclasses.field(default_factory=list)


class TileJob:
    """One tiled prediction in the stream's hands, created on the
    request's own thread (inside its ``engine.predict`` stage: the
    stream's threads record this job's stages in copies of that
    context, so they chain under it). The engine's subclass gives

    - ``key``: tiles of equal key (bucket, channels, dtype) share chunks;
    - ``row_shape``, ``dtype``: one row of a chunk;
    - ``tiles``: how many rows the job has;
    - ``sizes``: the row counts the prediction would run alone;
    - ``cut(first, rows)``: write tiles ``first...`` into the rows given;
    - ``blend(first, rows)``: fold their outputs into the accumulator,
      called in tile order.

    ``future`` resolves (to None) when the last row is blended;
    ``compute_seconds`` is then the job's share of the device time of
    every chunk it rode: a chunk's ``compute_seconds`` x its rows / the
    chunk's useful rows, so padding is split the same way and the shares
    of all jobs sum to the device's busy time. ``chunks`` is how many
    chunks it rode; ``enrolled_ns``, ``first_dispatch_ns`` and
    ``answered_ns`` (``time.time_ns()``) when it joined the stream, when
    the dispatch of its first chunk ended and when it was answered;
    ``request_seq`` the number of the request it belongs to (the
    ``engine.dispatch`` stage of every chunk lists those of its jobs)."""

    key: tuple
    row_shape: tuple
    dtype: Any
    tiles: int
    sizes: frozenset

    def __init__(self):
        self.future: Future = Future()
        self.next_tile = 0
        self.blended = 0
        self.compute_seconds = 0.0
        self.chunks = 0
        self.enrolled_ns = self.first_dispatch_ns = self.answered_ns = 0
        self.request_seq = tracing.current_request_seq()
        self.cut_context = contextvars.copy_context()
        self.issue_context = contextvars.copy_context()
        self.stitch_context = contextvars.copy_context()
        self.trace, self.parent_span = tracing.current_trace_and_span()

    @property
    def stream_wait_seconds(self) -> float:
        return (self.first_dispatch_ns - self.enrolled_ns) / 1e9

    @property
    def stream_run_seconds(self) -> float:
        return (self.answered_ns - self.first_dispatch_ns) / 1e9

    def cut(self, first: int, rows: np.ndarray) -> None:
        raise NotImplementedError

    def blend(self, first: int, rows: np.ndarray) -> None:
        raise NotImplementedError


class _Chunk:
    """Rows of one or more jobs on their way through the device:
    ``segments`` are (job, first tile, rows) in row order, one a job,
    ``sizes`` the row counts its jobs would run alone, ``size`` the one
    it runs at, ``closed`` why it closed (``TileStream._close_chunk``),
    ``held_ns`` when the device-deadline hold began to keep it (0: never)
    and ``held_rows`` its rows then."""

    __slots__ = (
        "key", "buf", "rows", "segments", "sizes", "size", "flight", "closed",
        "held_ns", "held_rows",
    )

    def __init__(self, key: tuple, buf: np.ndarray):
        self.key = key
        self.buf = buf
        self.rows = 0
        self.segments: list[tuple[TileJob, int, int]] = []
        self.sizes: set[int] = set()
        self.size = 0
        self.flight: Optional[InFlight] = None
        self.closed = ""
        self.held_ns = self.held_rows = 0


class _Direct:
    """A prediction that needs no tiling, waiting for its turn on the
    issuing thread."""

    __slots__ = ("run", "context", "future")

    def __init__(self, run: Callable[[], Any]):
        self.run = run
        self.context = contextvars.copy_context()
        self.future: Future = Future()


class TileStream:
    """The engine's one tile stream and its three threads (module
    docstring). ``dispatch(buf, n, **attrs)`` hands a staged chunk with
    ``n`` useful rows to the device without blocking and returns its
    ``InFlight``, ``issue_ns`` set; ``attrs`` go on its ``engine.dispatch`` stage:
    ``rows``, ``capacity``, ``closed`` (why it closed), ``requests``
    (the ``request_seq`` of each job with rows in it, the first job's
    first) and ``held_ms`` (how long the device-deadline hold kept it).
    ``force(flight)`` blocks until its rows are on the host, sets its
    ``ready_ns`` and ``waited_ns``, and returns them all.

    **Row counts.** A chunk holds at most ``tile_batch`` useful rows and
    runs at the smallest row count that one of its jobs would have run
    alone and that holds its rows. So the stream compiles no program
    that lone requests would not, and a lone request on an empty engine
    runs exactly the chunks it always did.
    """

    def __init__(
        self,
        name: str,
        *,
        depth: int,
        tile_batch: int,
        chunk_rows: int,
        stats: PipelineStats,
        pool: StagingPool,
        dispatch: Callable[..., InFlight],
        force: Callable[[InFlight], np.ndarray],
    ):
        self._name = name
        self._depth = max(int(depth), 1)
        self._tile_batch = max(int(tile_batch), 1)
        self._chunk_rows = chunk_rows  # rows of a staging buffer
        self._stats = stats
        self._pool = pool
        self._dispatch = dispatch
        self._force = force
        # one condition guards everything below; every change notifies
        self._cond = threading.Condition()
        self._closed = False
        self._threads: list[threading.Thread] = []
        self._pending: set[TileJob] = set()       # enrolled, not yet blended
        self._cutting: deque[TileJob] = deque()   # tiles left to cut
        self._open: Optional[_Chunk] = None       # the partly filled chunk
        # tiles in hand may still join the open chunk: a job has just
        # enrolled, or the cut thread is at work
        self._cutter_busy = False
        self._staged: deque = deque()  # closed chunks and direct runs, in order
        self._blends: deque = deque()  # read-back chunks for the stitch thread
        self._busy_since_ns = 0     # when the stream last got a job in hand
        self._last_ready_ns = 0
        # the last (device time, put + dispatch) readings of each program
        # (key, row count), as the issuing thread saw its chunks end: the
        # hold's clock
        self._timed: dict[tuple, deque] = {}

    # ---- the requests' side -------------------------------------------------

    def enrol(self, job: TileJob) -> Future:
        """Join the stream: the job's tiles are cut in arrival order,
        into the open chunk first. Returns ``job.future``."""
        with self._cond:
            self._ensure_running()
            job.enrolled_ns = time.time_ns()
            if not self._pending:
                self._busy_since_ns = job.enrolled_ns
            self._pending.add(job)
            self._cutting.append(job)
            # the open chunk waits for these tiles, not only for the cut
            # thread to wake up and see them
            self._cutter_busy = True
            self._cond.notify_all()
        return job.future

    def run(self, fn: Callable[[], Any]) -> Future:
        """Run ``fn`` (a prediction that needs no tiling) on the issuing
        thread, in its turn, in a copy of the caller's context, with
        nothing else in flight: as such predictions always ran."""
        direct = _Direct(fn)
        with self._cond:
            self._ensure_running()
            if self._open is not None and not self._cutter_busy:
                # in arrival order behind the rows that wait (a chunk
                # the cut thread is filling right now it overtakes)
                self._close_chunk("direct")
            self._staged.append(direct)
            self._cond.notify_all()
        return direct.future

    def _ensure_running(self) -> None:
        """(Lock held.)"""
        if self._closed:
            raise RuntimeError(f"dispatcher '{self._name}' is closed")
        if not self._threads:
            for name, loop in (
                ("pipeline-cut", self._cut_loop),
                (f"{self._name}-device", self._issue_loop),
                ("pipeline-stitch", self._stitch_loop),
            ):
                thread = threading.Thread(
                    target=self._guarded, args=(loop,), name=name, daemon=True
                )
                thread.start()
                self._threads.append(thread)

    def close(self) -> None:
        """Terminal and idempotent: the threads end, and whatever the
        stream still had in hand fails with the error ``enrol`` raises
        from now on."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            threads, self._threads = self._threads, []
            self._cond.notify_all()
        for thread in threads:
            if thread is not threading.current_thread():
                thread.join(timeout=5.0)
        error = RuntimeError(f"dispatcher '{self._name}' is closed")
        with self._cond:
            waiting = [e for e in self._staged if isinstance(e, _Direct)]
            self._staged.clear()
        for job in list(self._pending):
            self._answer(job, error)
        for direct in waiting:
            direct.future.set_exception(error)

    def _guarded(self, loop: Callable[[], None]) -> None:
        try:
            loop()
        except Exception:  # a fault of the stream's own: nobody would answer
            logging.getLogger(__name__).exception(
                "tile stream '%s': %s died; closing the stream",
                self._name, threading.current_thread().name,
            )
            self.close()

    def _answer(self, job: TileJob, error: Optional[BaseException] = None) -> None:
        """Resolve a job's future, once: its last row is blended (the
        job's time in the stream is then counted), or it failed (its
        remaining rows are then dropped)."""
        with self._cond:
            if job not in self._pending:
                return
            self._pending.remove(job)
            now = time.time_ns()
            deltas = {}
            if error is None:
                job.answered_ns = now
                deltas.update(
                    jobs=1,
                    job_chunks=job.chunks,
                    stream_wait_seconds=job.stream_wait_seconds,
                    stream_run_seconds=job.stream_run_seconds,
                )
            if not self._pending:
                deltas["wall_seconds"] = (now - self._busy_since_ns) / 1e9
            if deltas:
                self._stats.add(**deltas)
        if error is None:
            job.future.set_result(None)
        else:
            job.future.set_exception(error)

    # ---- cut thread ---------------------------------------------------------

    def _cut_loop(self) -> None:
        cond = self._cond
        while True:
            with cond:
                while True:
                    if self._closed:
                        return
                    self._cutter_busy = True
                    work = self._reserve()
                    if work is not None:
                        break
                    # nothing to cut, or no room to cut into: the issuing
                    # thread may have the open chunk as it stands
                    self._cutter_busy = False
                    cond.notify_all()
                    cond.wait()
            self._cut(*work)

    def _reserve(self) -> Optional[tuple]:
        """(Lock held.) The next rows to cut: the head job's next tiles
        into the open chunk, a new one where there is none and room for
        one. None when there is nothing to cut or no room."""
        cutting = self._cutting
        while cutting and cutting[0] not in self._pending:  # failed since
            cutting.popleft()
        if not cutting:
            return None
        job = cutting[0]
        chunk = self._open
        if chunk is not None and chunk.key != job.key:
            self._close_chunk("key")  # tiles of another shape share no chunk
            chunk = None
        if chunk is None:
            if len(self._staged) >= PREFETCH:
                return None
            chunk = self._open = _Chunk(
                job.key,
                self._pool.acquire(
                    (self._chunk_rows, *job.row_shape), job.dtype
                ),
            )
        chunk.sizes |= job.sizes
        n = min(job.tiles - job.next_tile, self._capacity(chunk) - chunk.rows)
        first = job.next_tile
        job.next_tile += n
        if job.next_tile == job.tiles:
            cutting.popleft()
        return job, chunk, first, n

    def _capacity(self, chunk: _Chunk) -> int:
        return min(self._tile_batch, max(chunk.sizes))

    def _close_chunk(self, reason: str) -> None:
        """(Lock held.) The open chunk goes to the issuing thread:
        ``full`` (its rows reached its capacity), ``key`` (the next
        job's tiles are of another key), ``direct`` (a prediction that
        needs no tiling overtakes it). The fourth reason, ``late``, is
        the issuing thread's own (``_issue_loop``)."""
        self._open.closed = reason
        self._staged.append(self._open)
        self._open = None

    def _cut(self, job: TileJob, chunk: _Chunk, first: int, n: int) -> None:
        error = None
        try:
            job.cut_context.run(
                self._cut_rows, job, first, chunk.buf[chunk.rows : chunk.rows + n]
            )
        except Exception as exc:
            error = exc
        with self._cond:
            # the rows stand in the chunk either way; a failed job's are
            # run and dropped
            chunk.segments.append((job, first, n))
            chunk.rows += n
            if chunk.rows == self._capacity(chunk):
                self._close_chunk("full")
            self._cond.notify_all()
        if error is not None:
            self._answer(job, error)

    def _cut_rows(self, job: TileJob, first: int, rows: np.ndarray) -> None:
        with tracing.stage("engine.cut") as cut:
            job.cut(first, rows)
        self._stats.add(cut_seconds=cut.seconds)

    # ---- issuing thread: the only one that talks to the device --------------

    def _issue_loop(self) -> None:
        cond = self._cond
        window: deque[_Chunk] = deque()  # dispatched, not yet read back
        while True:
            entry = None
            waited_from = 0
            with cond:
                while True:
                    if self._closed:
                        return
                    place = len(window) < self._depth
                    if place and self._staged:
                        entry = self._staged.popleft()
                        break
                    chunk = self._open
                    if (
                        place and not self._cutter_busy
                        and chunk is not None and chunk.rows
                    ):
                        # a place is free, no full chunk stands ready, and
                        # no tile in hand could still join this one: it
                        # goes late now unless the chunk in flight keeps
                        # the device busy past the lead (module docstring)
                        now = time.time_ns()
                        deadline = self._deadline(window, chunk)
                        if deadline is None or now >= deadline:
                            self._open = None
                            chunk.closed = "late"
                            entry = chunk
                            break
                        if not chunk.held_ns:
                            chunk.held_ns, chunk.held_rows = now, chunk.rows
                        # held: no read-back (it would block past the
                        # deadline); rows that enrol meanwhile join it
                        cond.wait((deadline - now) / 1e9)
                        continue
                    # read back when the window is full or nothing more
                    # is coming; while the cut thread is at work the next
                    # chunk goes first, so the device never waits for it
                    if window and not (place and self._cutter_busy):
                        break
                    waited_from = waited_from or time.time_ns()
                    cond.wait()
                cond.notify_all()
            if waited_from and isinstance(entry, _Chunk):
                # idle with a job in hand: the cut thread's turn
                tracing.record_stage(
                    "engine.chunk_wait",
                    max(waited_from, self._busy_since_ns), time.time_ns(),
                    span=False,
                )
            if entry is None:
                self._read_back(window.popleft())
            elif isinstance(entry, _Chunk):
                self._issue(entry, window)
            else:
                while window:
                    self._read_back(window.popleft())
                try:
                    entry.future.set_result(entry.context.run(entry.run))
                except Exception as exc:
                    entry.future.set_exception(exc)

    def _deadline(self, window: deque, chunk: _Chunk) -> Optional[int]:
        """When the open ``chunk`` must go for the device not to run dry
        behind the chunks in ``window`` (``time.time_ns()``): their
        predicted end less the lead, the smallest put + dispatch kept
        for the newest one's program and the copy of ``chunk``'s bytes
        (module docstring). None where nothing is in flight or a program
        in flight has not been timed yet."""
        end = self._last_ready_ns
        timed = None
        for flying in window:
            timed = self._timed.get((flying.key, flying.size))
            if not timed:
                return None
            run = min(device for device, _ in timed)
            end = max(flying.flight.dispatched_ns, end) + run
        if timed is None:
            return None
        lead = min(issue for _, issue in timed)
        return end - lead - int(chunk.buf.nbytes * H2D_NS_PER_BYTE)

    def _issue(self, chunk: _Chunk, window: deque) -> None:
        rows = chunk.rows
        chunk.size = size = min(s for s in chunk.sizes if s >= rows)
        chunk.buf[rows:size] = 0  # stale rows of an earlier, fuller chunk
        # a chunk's stages are recorded once, under its first job; its
        # dispatch names every job's request
        head = chunk.segments[0][0]
        start = time.time_ns()
        held = (start - chunk.held_ns) / 1e9 if chunk.held_ns else 0.0
        try:
            chunk.flight = flight = head.issue_context.run(
                self._dispatch, chunk.buf[:size], rows,
                rows=rows, capacity=self._capacity(chunk), closed=chunk.closed,
                requests=[job.request_seq for job, _, _ in chunk.segments],
                held_ms=held * 1e3,
            )
        except Exception as exc:
            self._pool.release(chunk.buf)
            self._to_stitch(chunk, None, exc)
            return
        window.append(chunk)
        for job, _, _ in chunk.segments:
            job.chunks += 1
            job.first_dispatch_ns = job.first_dispatch_ns or flight.dispatched_ns
        self._stats.add(
            chunks=1,
            chunks_shared=int(len(chunk.segments) > 1),
            chunks_held=int(bool(chunk.held_ns)),
            chunks_held_filled=int(bool(chunk.held_ns) and rows > chunk.held_rows),
            hold_seconds=held,
            **{f"chunks_closed_{chunk.closed}": 1},
        )
        self._stats.observe_in_flight(len(window))

    def _read_back(self, chunk: _Chunk) -> None:
        flight = chunk.flight
        head = chunk.segments[0][0]
        host = error = None
        try:
            host = head.issue_context.run(self._force, flight)
        except Exception as exc:
            error = exc
        self._pool.release(chunk.buf)
        chunk.flight = None  # the device's copy goes now, not after the blend
        if error is None:
            # chunks execute one after another: this one had the device
            # from its dispatch, or from when the one before it was
            # ready, until it was ready itself
            busy_from = max(flight.dispatched_ns, self._last_ready_ns)
            compute = max(flight.ready_ns - busy_from, 0) / 1e9
            self._last_ready_ns = flight.ready_ns
            program = (chunk.key, chunk.size)
            if flight.waited_ns >= FOUND_DONE_NS:
                self._timed.setdefault(
                    program, deque(maxlen=TIMED_CHUNKS)
                ).append((flight.ready_ns - busy_from, flight.issue_ns))
            else:
                # done before the wait began: an end not seen, and maybe
                # a hold that overshot it
                self._timed.pop(program, None)
            self._stats.add(compute_seconds=compute)
            for job, _, n in chunk.segments:
                job.compute_seconds += compute * n / chunk.rows
                if job is not head and job.trace is not None and job.trace.sampled:
                    # the shared chunk's device stages, as spans of a
                    # sampled job that rode it without being its first
                    for st in flight.stages:
                        tracing.record_span(
                            st.name, st.seconds, started_at=st.start_ns / 1e9,
                            parent_id=job.parent_span, ctx=job.trace, **st.attrs,
                        )
        self._to_stitch(chunk, host, error)

    def _to_stitch(self, chunk: _Chunk, host, error) -> None:
        """Queue a chunk for the stitch thread; blocks while it is more
        than a window behind (results on the host are bounded too)."""
        with self._cond:
            while len(self._blends) > self._depth and not self._closed:
                self._cond.wait()
            self._blends.append((chunk, host, error))
            self._cond.notify_all()

    # ---- stitch thread ------------------------------------------------------

    def _stitch_loop(self) -> None:
        cond = self._cond
        while True:
            with cond:
                while True:
                    if self._closed:
                        return
                    if self._blends:
                        break
                    cond.wait()
                chunk, host, error = self._blends.popleft()
                cond.notify_all()
            at = 0
            for job, first, n in chunk.segments:
                rows = None if host is None else host[at : at + n]
                at += n
                if job not in self._pending:
                    continue  # failed already: its rows are dropped
                # only the jobs that rode the chunk fail with it
                failure = error
                if failure is None:
                    try:
                        job.stitch_context.run(self._blend, job, first, rows)
                    except Exception as exc:
                        failure = exc
                if failure is not None:
                    self._answer(job, failure)
                elif job.blended == job.tiles:
                    self._answer(job)

    def _blend(self, job: TileJob, first: int, rows: np.ndarray) -> None:
        with tracing.stage("engine.stitch") as blend:
            job.blend(first, rows)
        job.blended += len(rows)
        self._stats.add(stitch_seconds=blend.seconds)
