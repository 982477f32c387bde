"""From a profiler trace (``.xplane.pb``) to numbers. The one reduction.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane
(``/device:TPU:<n>``) carries a line of executed programs ("XLA
Modules") and a line of the operations inside them ("XLA Ops"); host
planes carry one line per thread. All share one clock, in nanoseconds
since the trace started; the "Task Environment" plane says when that
was on the wall clock (``profile_start_time``, ns since the epoch), so
that a ``time.time_ns()`` of the traced process can be laid onto it.

  busy      union of the intervals in which an operation ran on a device
  modules   every program execution: name, start, duration
  top ops   operations summed by kind, clipped to a window like busy
  gaps      the idle intervals between operations, longest first, each
            labelled by the host event that overlapped it most

Interval arithmetic is in plain functions so that it can be tested on
made-up intervals with a known answer.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
Interval = tuple[int, int]  # [start, end) in ns


def merge(intervals: Iterable[Interval]) -> list[Interval]:
    """Overlapping or touching intervals joined, sorted."""
    out: list[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def union_length(intervals: Iterable[Interval]) -> int:
    return sum(end - start for start, end in merge(intervals))


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> list[Interval]:
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)
    ]


def gaps(intervals: Iterable[Interval], lo: int, hi: int) -> list[Interval]:
    """What ``merge(intervals)`` leaves uncovered inside [lo, hi)."""
    out = []
    cursor = lo
    for start, end in merge(clip(intervals, lo, hi)):
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


_HLO = re.compile(r"^%[\w\-.]+ = (.*?) ([\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_kind(name: str) -> str:
    """``%fusion.12 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion bf16[8,128]``: the opcode and what it produces, without the
    instruction's number and layouts, so that the same operation of
    every layer sums under one name. Anything else is its own kind."""
    match = _HLO.match(name)
    if not match:
        return name
    return f"{match.group(2)} {_LAYOUT.sub('', match.group(1))}"


@dataclasses.dataclass
class DeviceTrace:
    device: int
    ops: list[tuple[str, int, int]]       # name, start, duration
    modules: list[tuple[str, int, int]]


@dataclasses.dataclass
class Reduced:
    devices: list[DeviceTrace]
    host: list[tuple[str, int, int]]      # name, start, duration (all threads)
    lo: int                               # first device event start
    hi: int                               # last device event end
    started_wall_ns: int = 0              # the trace's start on the wall clock

    def at(self, wall_ns: int) -> int:
        """A ``time.time_ns()`` of the traced process on the trace's clock."""
        return int(wall_ns) - self.started_wall_ns

    def busy_s(self, window: Optional[Interval] = None) -> float:
        """Seconds an operation ran, averaged over the devices."""
        lo, hi = window or (self.lo, self.hi)
        per_device = [
            union_length(clip(((s, s + d) for _, s, d in dev.ops), lo, hi))
            for dev in self.devices
        ]
        return sum(per_device) / len(per_device) / 1e9

    def span_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def module_seconds(
        self, window: Optional[Interval] = None
    ) -> dict[str, list[float]]:
        """Program name -> the duration of each of its executions (of
        those that ended inside ``window``)."""
        out: dict[str, list[float]] = {}
        for dev in self.devices:
            for name, start, dur in dev.modules:
                if window is None or window[0] < start + dur <= window[1]:
                    out.setdefault(name, []).append(dur / 1e9)
        return out

    def top_ops(self, n: int = 10, window: Optional[Interval] = None) -> list[list]:
        """Device seconds summed by kind of operation (``op_kind``), of
        the part of each operation that lies inside ``window``: the same
        clip as ``busy_s``, so that the two can be divided."""
        lo, hi = window or (self.lo, self.hi)
        total: dict[str, int] = {}
        for dev in self.devices:
            for name, start, dur in dev.ops:
                inside = min(start + dur, hi) - max(start, lo)
                if inside > 0:
                    kind = op_kind(name)
                    total[kind] = total.get(kind, 0) + inside
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, dur / 1e9] for name, dur in ranked]

    def host_seconds(self, n: int = 10) -> list[list]:
        """Host events summed by name over all threads, largest first."""
        total: dict[str, int] = {}
        for name, _, dur in self.host:
            total[name] = total.get(name, 0) + dur
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in ranked]

    def idle_gaps(self, n: int = 10, window: Optional[Interval] = None) -> list[list]:
        """Idle seconds of device 0 summed by the host event that
        overlapped each gap most; the ``n`` largest sums."""
        dev = self.devices[0]
        lo, hi = window or (self.lo, self.hi)
        idle = gaps(((s, s + d) for _, s, d in dev.ops), lo, hi)
        idle.sort(key=lambda g: g[0] - g[1])
        host = sorted((s, s + d, name) for name, s, d in self.host)
        by_label: dict[str, int] = {}
        # label the 200 longest gaps one by one, lump the rest
        for gap in idle[:200]:
            best, best_ns, best_len = "unattributed", 0, 0
            for s, e, name in host:
                if s >= gap[1]:
                    break
                ns = overlap(gap, (s, e))
                # the innermost (shortest) event that covers most of it
                if ns > best_ns or (ns == best_ns and ns > 0 and e - s < best_len):
                    best, best_ns, best_len = name, ns, e - s
            by_label[best] = by_label.get(best, 0) + gap[1] - gap[0]
        rest = sum(g[1] - g[0] for g in idle[200:])
        if rest:
            by_label["(shorter gaps, unlabelled)"] = rest
        ranked = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in ranked]


def find_xplane(trace_dir: str | Path) -> Path:
    (path,) = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1:]
    return path


def reduce(xplane: str | Path) -> Reduced:
    """Raises ``ValueError`` when no device plane holds an operation: a
    trace in which nothing ran on the device measures nothing."""
    import jax

    profile = jax.profiler.ProfileData.from_file(str(xplane))
    devices: list[DeviceTrace] = []
    host: list[tuple[str, int, int]] = []
    started = 0
    for plane in profile.planes:
        if plane.name == "Task Environment":
            started = int(dict(plane.stats).get("profile_start_time", 0))
        match = DEVICE_PLANE.match(plane.name)
        if match:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events
                    ]
                elif line.name == MODULES_LINE:
                    modules = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events
                    ]
            if ops:
                devices.append(DeviceTrace(int(match.group(1)), ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events
                    if e.duration_ns > 0
                )
    if not devices:
        names = [p.name for p in profile.planes]
        raise ValueError(f"no TPU plane with operations in {xplane}: {names}")
    if not started:
        raise ValueError(f"no profile_start_time in {xplane}")
    lo = min(s for dev in devices for _, s, _ in dev.ops)
    hi = max(s + d for dev in devices for _, s, d in dev.ops)
    return Reduced(devices, host, lo, hi, started)
