"""The benchmark's own tests of its stage readers (``benchmarks/tests``),
a file of their own so that ``--dist loadfile`` gives them a worker."""

import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_stage_metrics")

from benchmarks.tests.test_stage_metrics import *  # noqa: E402,F401,F403


# ---- the stream: requests in hand overlap (PR 33) -----------------------------
# The readers are the benchmark's, unchanged; these cases hold the
# program to what they read once one tile stream serves every request.

from types import SimpleNamespace  # noqa: E402

from benchmarks import harness, trace_reduce  # noqa: E402
from benchmarks.layer_metrics import _stages  # noqa: E402


def test_overlapping_requests_are_merged_by_the_idle_readers(timeline):
    # two requests in hand at once, each on a request thread of its
    # own; the device's stages are on the issuing thread
    timeline["stages"] = [
        stage("engine.queue", 40, 50),
        stage("engine.request", 50, 700),
        stage("engine.queue", 55, 60, thread="dispatch-m_1", seq=2),
        stage("engine.request", 60, 900, thread="dispatch-m_1", seq=2),
        stage("engine.device_wait", 120, 403, thread="dispatch-m-device"),
        stage("engine.device_wait", 520, 801, thread="dispatch-m-device", seq=2),
    ]
    run = traced_run()
    in_request = reader("idle_in_request_pct").read(run)
    empty = reader("idle_engine_empty_pct").read(run)
    # idle: 0-100, 240-260, 400-500, 640-660, 800-1000 = 440 of 1000 ns;
    # a request in hand from 50 to 900: 50 + 20 + 100 + 20 + 100 = 290
    assert in_request == pytest.approx(29.0)
    assert empty == pytest.approx(15.0)
    assert in_request + empty == pytest.approx(reader("device_idle_pct").read(run))


def test_a_real_stream_gives_the_readers_what_they_read():
    """Two requests through one engine's stream on the CPU: every stage
    name, thread prefix and counter the readers use is there, and the
    idle split still sums over overlapping ``engine.request`` stages."""
    import threading
    import time

    import numpy as np

    from bioengine_tpu.runtime import (
        CompiledProgramCache, EngineConfig, InferenceEngine,
    )
    from bioengine_tpu.utils import tracing

    eng = InferenceEngine(
        "m", lambda p, x: x * 2.0, {},
        config=EngineConfig(max_tile=64, tile=48, tile_overlap=16, tile_batch=16),
        cache=CompiledProgramCache(),
    )
    images = [np.random.rand(1, 112, 112, 1).astype(np.float32) for _ in "ab"]
    plug = np.random.rand(1, 176, 176, 1).astype(np.float32)
    gate = threading.Event()
    gate.set()
    sound = eng._stream._force

    def held(flight):  # what is dispatched stays in flight while it is shut
        gate.wait(30)
        return sound(flight)

    eng._stream._force = held
    try:
        eng.predict(images[0])  # compile
        before = eng.pipeline_stats.as_dict()
        t0 = time.time_ns()
        gate.clear()
        # the plug's two chunks fill the window; the two requests enrol
        # behind them and share a chunk for certain
        futures = [eng.submit(eng.predict, plug)]
        deadline = time.monotonic() + 30
        while eng.pipeline_stats.chunks - before["chunks"] < 2:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        futures += [eng.submit(eng.predict, x) for x in images]
        while len(eng._stream._pending) < 3 or eng._stream._cutter_busy:
            assert time.monotonic() < deadline
            time.sleep(0.002)
        gate.set()
        for f in futures:
            f.result(timeout=60)
        t1 = time.time_ns()
        after = eng.pipeline_stats.as_dict()
    finally:
        gate.set()
        eng.close()

    stages = _stages.timeline(t0, t1)
    requests = sorted(
        (s for s in stages if s["name"] == _stages.REQUEST),
        key=lambda s: s["start_ns"],
    )
    assert len(requests) == 3
    assert requests[2]["start_ns"] < requests[1]["end_ns"]  # they overlap
    assert len([s for s in stages if s["name"] == _stages.QUEUE]) == 3
    # the requests' own stages and the device's are on dispatch- threads,
    # the cut and the blend on pipeline- threads: what by_stage tells apart
    on_dispatch = {
        s["name"] for s in stages if s["thread"].startswith(_stages.DISPATCH_THREAD)
    }
    assert {_stages.REQUEST, _stages.QUEUE, "engine.predict", "engine.put",
            "engine.dispatch", "engine.device_wait", "engine.d2h"} <= on_dispatch
    helpers = {
        s["name"] for s in stages if s["thread"].startswith(_stages.PIPELINE_THREAD)
    }
    assert helpers == {"engine.cut", "engine.stitch"}
    # the counters the readers take deltas of
    for key in ("requests", "queue_seconds", "rows_executed", "rows_useful",
                "chunks", "d2h_seconds"):
        assert after[key] >= before[key], key
    assert after["requests"] - before["requests"] == 3
    # 16 | 9 -> 16 | 9 + 7 | 2 -> 16
    assert after["rows_executed"] - before["rows_executed"] == 64
    assert after["rows_useful"] - before["rows_useful"] == 43
    assert after["chunks_shared"] - before["chunks_shared"] == 1

    # a device that was busy exactly while the issuing thread waited for it
    busy = [
        (s["start_ns"] - t0, s["duration_s"] * 1e9)
        for s in stages if s["name"] == "engine.device_wait"
    ]
    reduced = trace_reduce.Reduced(
        devices=[trace_reduce.DeviceTrace(
            0,
            ops=[("op", int(s), int(d)) for s, d in busy],
            modules=[("jit_engine_m_16x64x64x1(1)", int(s), int(d)) for s, d in busy],
        )],
        host=[], lo=0, hi=t1 - t0, started_wall_ns=t0,
    )
    run = harness.RunData(
        cell=SimpleNamespace(name="toy", chips=1), seconds=(t1 - t0) / 1e9,
        window=(0.0, (t1 - t0) / 1e9), requests=[],
        counters=counters(before, after), compiles_in_window=0,
        trace={"reduced": reduced, "span": (0, t1 - t0),
               "wall_window": (t0, t1), "host_window": (0.0, (t1 - t0) / 1e9)},
    )
    in_request = reader("idle_in_request_pct").read(run)
    empty = reader("idle_engine_empty_pct").read(run)
    assert in_request + empty == pytest.approx(reader("device_idle_pct").read(run))
    assert in_request > empty >= 0.0
    assert reader("padding_waste_pct").read(run) == pytest.approx(100 * (1 - 43 / 64))
    table = _stages.by_stage(
        [(0, t1 - t0)],
        [(reduced.at(s["start_ns"]), reduced.at(s["end_ns"]), s["name"], s["thread"])
         for s in stages],
    )
    # every instant of the span has a label the reader knows how to make
    assert sum(table.values()) == t1 - t0
    assert "engine.device_wait" in table
    assert reader("engine_queue_wait_ms").read(run) >= 0.0
    assert reader("d2h_ms").read(run) >= 0.0
    tracing.clear_stages()
