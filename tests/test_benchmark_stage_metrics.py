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


# ---- the stream's own account of its chunks and requests ---------------------
# New readers of new counters. The benchmark's tests above are unchanged, but
# for its manifest test, whose name is redefined below: the stream's four
# entries are appended after the seven it pins as the list's last.

STREAM = ("chunks_per_request", "stream_wait_ms", "stream_run_ms",
          "chunks_closed_late_pct")


def stream_counters():
    return counters(
        {"jobs": 10, "job_chunks": 19, "stream_wait_seconds": 1.0,
         "stream_run_seconds": 3.0, "chunks": 12, "chunks_closed_late": 2},
        {"jobs": 30, "job_chunks": 57, "stream_wait_seconds": 3.4,
         "stream_run_seconds": 9.6, "chunks": 36, "chunks_closed_late": 8},
    )


def predicted(start, end, tiles, chunks, wait, run, seq=1):
    s = stage("engine.predict", start, end, seq=seq)
    s["attrs"] = {"model": "m", "tiles": tiles, "chunks": chunks,
                  "stream_wait_s": wait, "stream_run_s": run}
    return s


@pytest.mark.parametrize("name, want", [
    ("chunks_per_request", 38 / 20),
    ("stream_wait_ms", 1000 * 2.4 / 20),
    ("stream_run_ms", 1000 * 6.6 / 20),
    ("chunks_closed_late_pct", 100 * 6 / 24),
])
def test_stream_readers_on_known_counters(name, want, timeline):
    timeline["stages"] = None
    assert reader(name).read(traced_run(stream_counters())) == pytest.approx(want)


@pytest.mark.parametrize("name", STREAM)
def test_stream_readers_return_none_off_the_chip(name, timeline):
    timeline["stages"] = None
    run = traced_run(stream_counters())
    run.trace = None
    assert reader(name).read(run) is None


@pytest.mark.parametrize("name", STREAM)
def test_stream_readers_return_none_on_a_program_without_the_counters(name, timeline):
    """The parent commit: a trace and its older counters, none of these."""
    timeline["stages"] = None
    run = traced_run(counters(
        {"requests": 1, "chunks": 1, "rows_executed": 16, "rows_useful": 9},
        {"requests": 2, "chunks": 2, "rows_executed": 32, "rows_useful": 18},
    ))
    assert reader(name).read(run) is None


def test_chunks_per_request_logs_the_window_s_requests_by_tile_count(timeline, capsys):
    timeline["stages"] = [
        predicted(10, 300, 9, 1, 0.010, 0.200),
        predicted(20, 400, 16, 2, 0.020, 0.300, seq=2),
        predicted(30, 500, 16, 1, 0.040, 0.250, seq=3),
        predicted(40, 600, 25, 3, 0.030, 0.350, seq=4),
        predicted(50, 2000, 16, 2, 0.5, 0.5, seq=5),   # ends after the window
        stage("engine.predict", 60, 700, seq=6),        # a direct prediction
        stage("engine.request", 5, 650),
    ]
    chunks = reader("chunks_per_request")
    got = chunks.by_tiles(timeline["stages"][:4] + timeline["stages"][5:])
    assert got == {
        9: {"requests": 1, "chunks": 1.0, "stream_wait_ms": pytest.approx(10.0),
            "stream_run_ms": pytest.approx(200.0)},
        16: {"requests": 2, "chunks": 1.5, "stream_wait_ms": pytest.approx(30.0),
             "stream_run_ms": pytest.approx(275.0)},
        25: {"requests": 1, "chunks": 3.0, "stream_wait_ms": pytest.approx(30.0),
             "stream_run_ms": pytest.approx(350.0)},
    }
    assert chunks.read(traced_run(stream_counters())) == pytest.approx(1.9)
    lines = [
        line for line in capsys.readouterr().err.splitlines()
        if "stream by tiles" in line
    ]
    assert lines == [
        "[bench] stream by tiles: 9 tiles, 1 requests, chunks 1.000, "
        "stream_wait_ms 10.0, stream_run_ms 200.0",
        "[bench] stream by tiles: 16 tiles, 2 requests, chunks 1.500, "
        "stream_wait_ms 30.0, stream_run_ms 275.0",
        "[bench] stream by tiles: 25 tiles, 1 requests, chunks 3.000, "
        "stream_wait_ms 30.0, stream_run_ms 350.0",
    ]


def test_the_stream_s_manifest_entries_stand_last():
    per_layer = harness.load_manifest()["per_layer"]
    assert tuple(p["name"] for p in per_layer[-len(STREAM):]) == STREAM
    moves = ("latency_p50_ms",) * 3 + ("throughput_mpx_s",)
    for p, m in zip(per_layer[-len(STREAM):], moves):
        assert p["source"] == "program_counter"
        assert p["layer"] == "engine tiled pipeline"
        assert p["better"] == "lower"
        assert p["moves"] == m
        assert p["workloads"] == ["cpsam-vitl.fov"]
    assert set(harness.load_cell("cpsam-vitl.fov").per_layer) >= set(STREAM)


def test_manifest_lists_the_new_metrics_last_and_for_the_one_cell():
    """The benchmark's test of the same name, brought up to date: a later
    entry is appended, so the stage readers' seven (``NEW``) now stand
    directly before the stream's four, which end the list."""
    per_layer = harness.load_manifest()["per_layer"]
    tail = NEW + STREAM
    assert tuple(p["name"] for p in per_layer[-len(tail):]) == tail
    for p in per_layer[-len(tail):]:
        assert p["workloads"] == ["cpsam-vitl.fov"]
        assert p["source"] in ("device_trace", "program_counter")
    assert set(harness.load_cell("cpsam-vitl.fov").per_layer) >= set(tail)


def test_a_real_stream_gives_the_stream_readers_what_they_read():
    """Three requests through one engine's stream on the CPU: the new
    counters agree with each other and with the timeline."""
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
    images = [np.random.rand(1, s, s, 1).astype(np.float32) for s in (112, 144, 176)]
    try:
        eng.predict(images[0])  # compile
        before, t0 = eng.pipeline_stats.as_dict(), time.time_ns()
        futures = [eng.submit(eng.predict, x) for x in images]
        for f in futures:
            f.result(timeout=60)
        t1 = time.time_ns()
        after = eng.pipeline_stats.as_dict()
    finally:
        eng.close()
    closed = sum(
        after[f"chunks_closed_{r}"] - before[f"chunks_closed_{r}"]
        for r in ("full", "key", "direct", "late")
    )
    assert closed == after["chunks"] - before["chunks"] > 0
    assert after["jobs"] - before["jobs"] == 3
    stages = [s for s in _stages.timeline(t0, t1) if s["start_ns"] >= t0]
    dispatched = [s["attrs"] for s in stages if s["name"] == "engine.dispatch"]
    assert after["job_chunks"] - before["job_chunks"] == sum(
        len(d["requests"]) for d in dispatched
    )
    rows = reader("chunks_per_request").by_tiles(stages)
    assert {tiles: row["requests"] for tiles, row in rows.items()} == {
        9: 1, 16: 1, 25: 1
    }
    run = harness.RunData(
        cell=SimpleNamespace(name="toy", chips=1), seconds=(t1 - t0) / 1e9,
        window=(0.0, (t1 - t0) / 1e9), requests=[],
        counters=counters(before, after), compiles_in_window=0,
        trace={"wall_window": (t0, t1), "host_window": (0.0, (t1 - t0) / 1e9)},
    )
    per_request = reader("chunks_per_request").read(run)
    assert per_request == pytest.approx(
        (after["job_chunks"] - before["job_chunks"]) / 3
    )
    assert per_request >= 1.0
    assert reader("stream_wait_ms").read(run) >= 0.0
    assert reader("stream_run_ms").read(run) > 0.0
    assert 0.0 <= reader("chunks_closed_late_pct").read(run) <= 100.0
    # the mean time in the stream lies inside the mean engine.request
    # (give or take as_dict's rounding of the sums to 0.1 ms)
    requests = [s["duration_s"] for s in stages if s["name"] == "engine.request"]
    assert len(requests) == 3
    assert reader("stream_wait_ms").read(run) + reader("stream_run_ms").read(run) <= (
        1000 * sum(requests) / 3 + 0.1
    )
    tracing.clear_stages()
