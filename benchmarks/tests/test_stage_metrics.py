"""The readers of the program's stage timeline and of its new counters
(PR 26), checked on made-up intervals and counters with known answers.
None of them reads anything off the chip.
"""

from __future__ import annotations

import importlib
from types import SimpleNamespace

import pytest

from benchmarks import harness, trace_reduce
from benchmarks.layer_metrics import _stages

NEW = (
    "idle_in_request_pct", "idle_engine_empty_pct", "engine_queue_wait_ms",
    "replica_queue_wait_ms", "batcher_wait_ms", "padding_waste_pct", "d2h_ms",
)
WALL0 = 1_700_000_000_000_000_000  # the trace's start on the wall clock


def reader(name: str):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def stage(name, start, end, thread="dispatch-m_0", seq=1):
    return {
        "name": name, "start_ns": WALL0 + start, "end_ns": WALL0 + end,
        "started_at": (WALL0 + start) / 1e9, "duration_s": (end - start) / 1e9,
        "thread": thread, "request_seq": seq, "attrs": {},
    }


def traced_run(counters=None):
    """A run whose device ran two programs, 100..400 and 500..800 ns of
    a span of 0..1000, each of two operations with 20 ns between them."""
    reduced = trace_reduce.Reduced(
        devices=[
            trace_reduce.DeviceTrace(
                0,
                ops=[("a", 100, 140), ("b", 260, 140), ("a", 500, 140), ("b", 660, 140)],
                modules=[("jit_engine_m_16x8x8x1(1)", 100, 300),
                         ("jit_engine_m_16x8x8x1(1)", 500, 300)],
            )
        ],
        host=[], lo=100, hi=800, started_wall_ns=WALL0,
    )
    cell = SimpleNamespace(name="toy", chips=1)
    run = harness.RunData(
        cell=cell, seconds=1e-6, window=(0.0, 1e-6), requests=[],
        counters=counters or {"start": {"families": {}, "pipeline": {}},
                              "end": {"families": {}, "pipeline": {}}},
        compiles_in_window=0,
        trace={
            "reduced": reduced, "span": (0, 1000),
            "wall_window": (WALL0, WALL0 + 1000), "host_window": (0.0, 1e-6),
        },
    )
    return run


@pytest.fixture
def timeline(monkeypatch):
    """Stands in for the program's ``tracing.get_stages``."""
    held = {}

    def fake(since_ns, until_ns):
        stages = held.get("stages")
        if stages is None:
            return None
        return [s for s in stages if s["end_ns"] >= since_ns and s["start_ns"] <= until_ns]

    monkeypatch.setattr(_stages, "timeline", fake)
    return held


def test_interval_helpers_on_a_known_case():
    idle = [(0, 100), (240, 260), (400, 500), (640, 660), (800, 1000)]
    assert _stages.covered(idle, [(50, 450), (420, 470), (900, 2000)]) == 50 + 20 + 70 + 100
    assert _stages.covered(idle, []) == 0
    assert _stages.nearest_offsets([405, 798, 2000], [400, 800]) == [5, -2, 1200]
    stages = [
        (50, 450, "engine.request", "dispatch-m_0"),
        (60, 90, "runtime.preprocess", "dispatch-m_0"),
        (90, 440, "engine.predict", "dispatch-m_0"),
        (92, 99, "engine.cut", "pipeline-cut"),
        (405, 430, "engine.d2h", "dispatch-m_0"),
        # waits and the loop's stages are nobody's innermost work
        (20, 50, "engine.queue", "dispatch-m_0"),
        (60, 95, "engine.queue", "dispatch-m_0"),
        (91, 99, "runtime.batch_wait", "MainThread"),
    ]
    assert _stages.by_stage([(0, 100), (400, 500)], stages) == {
        _stages.ENGINE_EMPTY: 20 + 50,
        _stages.HAND_OVER: 30,
        "engine.request (between stages)": 10 + 10,
        "runtime.preprocess": 30,
        "engine.predict > engine.cut (pipeline-cut)": 7,
        "engine.predict (between stages)": 2 + 1 + 5 + 10,
        "engine.d2h": 25,
    }


def test_the_two_idle_shares_sum_to_the_idle_share(timeline):
    # one request holds the engine from 50 to 450 ns, a second from 480 to 900
    timeline["stages"] = [
        stage("engine.request", 50, 450),
        stage("engine.device_wait", 120, 403),
        stage("engine.request", 480, 900, seq=2),
        stage("engine.device_wait", 520, 801, seq=2),
        stage("runtime.assemble", 455, 470, thread="MainThread", seq=0),
    ]
    run = traced_run()
    in_request = reader("idle_in_request_pct").read(run)
    empty = reader("idle_engine_empty_pct").read(run)
    # idle: 0-100, 240-260, 400-500, 640-660, 800-1000 = 440 of 1000 ns;
    # inside a request: 50 + 20 + (50 + 20) + 20 + 100 = 260
    assert in_request == pytest.approx(26.0)
    assert empty == pytest.approx(18.0)
    idle = reader("device_idle_pct").read(run)
    assert in_request + empty == pytest.approx(idle) == pytest.approx(44.0)
    # computed once per run
    assert run.trace["stage_idle"] == {
        "in_request_pct": in_request, "engine_empty_pct": empty
    }


def test_idle_readers_are_silent_without_a_timeline_or_a_request(timeline):
    timeline["stages"] = None          # a program older than the stages
    run = traced_run()
    assert reader("idle_in_request_pct").read(run) is None
    assert reader("idle_engine_empty_pct").read(run) is None
    timeline["stages"] = [stage("runtime.assemble", 10, 20, thread="MainThread")]
    run = traced_run()  # nothing reached the engine
    assert reader("idle_in_request_pct").read(run) is None
    assert reader("idle_engine_empty_pct").read(run) is None


def test_the_real_timeline_is_read_where_the_program_has_one():
    from bioengine_tpu.utils import tracing

    tracing.record_stage("engine.request", WALL0 + 50, WALL0 + 450)
    got = _stages.timeline(WALL0, WALL0 + 1000)
    assert [s["name"] for s in got] == ["engine.request"]
    assert got[0]["start_ns"] == WALL0 + 50 and got[0]["thread"]
    tracing.clear_stages()


def counters(start_pipeline, end_pipeline, start_families=None, end_families=None):
    return {
        "start": {"families": start_families or {}, "pipeline": start_pipeline},
        "end": {"families": end_families or {}, "pipeline": end_pipeline},
    }


def family(value):
    return {"series": [{"labels": {}, "value": value}]}


def histogram(deployment, total, count):
    return {"labels": {"deployment": deployment}, "sum": total, "count": count}


def test_counter_readers_on_known_counters(timeline):
    timeline["stages"] = None
    run = traced_run(counters(
        {"requests": 10, "queue_seconds": 1.0, "rows_executed": 160,
         "rows_useful": 90, "chunks": 10, "d2h_seconds": 0.5},
        {"requests": 30, "queue_seconds": 29.0, "rows_executed": 608,
         "rows_useful": 408, "chunks": 38, "d2h_seconds": 0.92},
        {"batcher_queue_wait_seconds_total": family(0.5),
         "batcher_requests_total": family(10),
         "replica_park_seconds": {"series": [
             histogram("runtime_deployment", 10.0, 10),
             histogram("entry_deployment", 99.0, 10)]}},
        {"batcher_queue_wait_seconds_total": family(0.7),
         "batcher_requests_total": family(30),
         "replica_park_seconds": {"series": [
             histogram("runtime_deployment", 48.0, 30),
             histogram("entry_deployment", 500.0, 30)]},
         "scheduler_queue_wait_seconds": {"series": [
             histogram("runtime_deployment", 2.0, 20)]}},
    ))
    assert reader("engine_queue_wait_ms").read(run) == pytest.approx(1400.0)
    assert reader("padding_waste_pct").read(run) == pytest.approx(100 * (1 - 318 / 448))
    assert reader("d2h_ms").read(run) == pytest.approx(15.0)
    assert reader("batcher_wait_ms").read(run) == pytest.approx(10.0)
    # the scheduler's histogram is new at the window's end: no delta, park alone
    assert reader("replica_queue_wait_ms").read(run) == pytest.approx(1900.0)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_off_the_chip(name, timeline):
    """The CPU rehearsal carries no trace: nothing under a new name."""
    timeline["stages"] = None
    run = traced_run(counters(
        {"requests": 1, "queue_seconds": 1.0, "rows_executed": 16,
         "rows_useful": 9, "chunks": 1, "d2h_seconds": 0.1},
        {"requests": 2, "queue_seconds": 2.0, "rows_executed": 32,
         "rows_useful": 18, "chunks": 2, "d2h_seconds": 0.2},
    ))
    run.trace = None
    assert reader(name).read(run) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_on_a_program_without_them(name, timeline):
    """The parent commit: a trace, but no timeline and none of the new
    counters (its park histogram aside, which it has)."""
    timeline["stages"] = None
    run = traced_run()
    assert reader(name).read(run) is None


def test_manifest_lists_the_new_metrics_last_and_for_the_one_cell():
    per_layer = harness.load_manifest()["per_layer"]
    assert tuple(p["name"] for p in per_layer[-len(NEW):]) == NEW
    for p in per_layer[-len(NEW):]:
        assert p["workloads"] == ["cpsam-vitl.fov"]
        assert p["source"] in ("device_trace", "program_counter")
    assert set(harness.load_cell("cpsam-vitl.fov").per_layer) >= set(NEW)
