"""The bench artifact contract, suite-guarded.

These tests pin the artifact guarantees by running ``bench.py`` as a
real subprocess the way a driver does:

- a normal run prints exactly ONE final JSON line and exits 0;
- a worker hung mid-stage (simulated via a tiny BENCH_STALL against a
  sleeping stage) is killed, diagnosed, and the artifact still prints —
  with rc 1, because a stage failed: never rc 0, never rc 124;
- the SIGTERM path (the driver's own axe) emits the artifact before
  dying, again with rc 1 for the unfinished stage.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

pytestmark = pytest.mark.integration

BENCH = Path(__file__).resolve().parent.parent / "bench.py"


def _run(env_extra: dict, timeout: float = 240.0):
    env = dict(os.environ, BENCH_PLATFORM="cpu", **env_extra)
    proc = subprocess.run(
        [sys.executable, str(BENCH)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=str(BENCH.parent),
    )
    lines = [
        ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")
    ]
    return proc, lines


def test_normal_run_prints_one_parsed_line():
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "search,pipeline_overlap",
            "BENCH_DEADLINE": "180",
        }
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert len(lines) == 1, proc.stdout
    d = json.loads(lines[0])
    assert d["metric"] == "dinov2_vitb14_embed_images_per_sec_per_chip"
    assert d["extra"]["probe"]["ok"]
    assert d["extra"]["search_latency"]["ok"]
    # the overlapped-pipeline stage must run and emit its schema on CPU
    # (numbers are informational there; the schema is the contract)
    po = d["extra"]["pipeline_overlap"]
    assert po["ok"], po
    for key in (
        "serial_s",
        "pipelined_s",
        "speedup",
        "serial_tiles_per_sec",
        "pipelined_tiles_per_sec",
        "overlap_efficiency",
        "pipeline_stats",
        "depth",
    ):
        assert key in po, key
    assert po["pipeline_stats"]["max_in_flight"] <= po["depth"]
    assert po["pipeline_stats"]["chunks"] > 0


def test_sharded_serving_stage_schema():
    """Pin the sharded_serving artifact schema: 1-chip vs dp-K engine
    throughput on the same bucketed batch workload, the dp scaling
    efficiency, and the parity check. On CPU the stage spawns its own
    --sharded-worker subprocess with 4 forced virtual host devices (the
    flag stays out of the worker every other stage is measured in), so
    the dp leg always runs; throughput numbers there are core-bound and
    informational — the schema plus parity are the contract (the TPU
    round supplies the scaling number)."""
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "sharded_serving",
            "BENCH_DEADLINE": "170",
        },
        timeout=200.0,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(lines[-1])["extra"]["sharded_serving"]
    assert st["ok"], st
    for key in (
        "batch",
        "image_hw",
        "n_devices",
        "images_per_sec_1chip",
        "images_per_sec_dp",
        "speedup",
        "dp_scaling_efficiency",
        "mesh",
        "parity_max_abs_err",
        "parity_ok",
    ):
        assert key in st, key
    assert st["n_devices"] == 4
    assert st["mesh"] == {"dp": 4}
    assert st["images_per_sec_1chip"] > 0
    assert st["images_per_sec_dp"] > 0
    # the two engines ran the same inputs: outputs must agree
    assert st["parity_ok"], st["parity_max_abs_err"]


def test_multihost_mesh_stage_schema():
    """Pin the multihost_mesh artifact schema: the SAME 2-stage
    pipeline-mesh deployment spec measured on a 1-host mesh vs
    spanning 2 simulated hosts (each leg its own --multihost-worker
    subprocess under a forced 4-device CPU layout). CPU throughput is
    core-bound and informational; the contract is the schema, output
    parity on both legs, and the RpcStats pin that cross-shard
    activation payloads rode the zero-copy OOB path."""
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "multihost_mesh",
            "BENCH_DEADLINE": "170",
        },
        timeout=200.0,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(lines[-1])["extra"]["multihost_mesh"]
    assert st["ok"], st
    for key in (
        "batch",
        "image_hw",
        "stages",
        "images_per_sec_1host",
        "images_per_sec_2host",
        "scaling_efficiency",
        "cross_host_overhead_ms_per_request",
        "transfer_bytes_per_request",
        "transfer_seconds_per_request",
        "cross_host_1host",
        "cross_host_2host",
        "parity_ok",
        "parity_max_abs_err",
        "oob_payloads_out",
        "legacy_msgs_out",
    ):
        assert key in st, key
    assert st["stages"] == 2
    assert st["images_per_sec_1host"] > 0
    assert st["images_per_sec_2host"] > 0
    assert st["scaling_efficiency"] > 0
    # one leg colocates (1 host joined), the other spans hosts —
    # the same spec, two topologies
    assert st["cross_host_1host"] is False
    assert st["cross_host_2host"] is True
    # both legs ran the same inputs: outputs must agree with the model
    assert st["parity_ok"], st["parity_max_abs_err"]
    # cross-shard activations moved per request…
    assert st["transfer_bytes_per_request"] > 0
    # …and demonstrably as extracted OOB payloads, never legacy packs
    assert st["oob_payloads_out"] > 0
    assert st["legacy_msgs_out"] == 0


def test_cold_start_stage_schema():
    """Pin the cold_start artifact schema: replica TTFR on the
    model-runner path across three legs — cold (fresh process, empty
    compile cache), warm-cache (fresh process against the cache the
    cold leg populated — the shared-tier experience), warm-pool
    (standby promotion) — each with its compile/load/first-request
    breakdown. The acceptance gate is the warm-pool path: promotion
    must beat the cold path by ≥10x even on a loaded CI core (it's a
    list move vs an XLA compile)."""
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "cold_start",
            "BENCH_DEADLINE": "170",
        },
        timeout=200.0,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(lines[-1])["extra"]["cold_start"]
    assert st["ok"], st
    for key in (
        "cold",
        "warm_cache",
        "warm_pool",
        "speedup_warm_cache",
        "speedup_warm_pool",
        "warm_cache_hit_observed",
    ):
        assert key in st, key
    for leg in ("cold", "warm_cache"):
        for key in (
            "ttfr_s",
            "build_s",
            "first_request_s",
            "weights_s",
            "compile_s",
            "streamed",
            "persistent_cache_hits",
            "real_compiles",
        ):
            assert key in st[leg], (leg, key)
    assert st["cold"]["streamed"] is True        # manifest package streams
    assert st["cold"]["real_compiles"] >= 1       # the cold leg compiled
    assert st["warm_cache_hit_observed"] is True  # the warm leg did not
    wp = st["warm_pool"]
    assert wp["promoted_from_warm_pool"] is True
    assert wp["promotions"] == 1
    assert wp["ttfr_s"] > 0
    # the acceptance ratio: warm-pool TTFR ≥10x faster than cold
    assert st["speedup_warm_pool"] >= 10.0, st["speedup_warm_pool"]


def test_rpc_transport_stage_schema():
    """Pin the rpc_transport artifact schema: three paths (legacy /
    zero-copy oob / shm), per-size e2e + codec round-trip numbers, the
    headline speedups, and the >frame-limit chunked round trip. Sizes
    are shrunk via env so the test exercises the full stage shape —
    including chunking — in seconds."""
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "rpc_transport",
            "BENCH_DEADLINE": "170",
            "BENCH_RPC_SIZES_MB": "1,8",
            "BENCH_RPC_BIG_MB": "24",
            "BIOENGINE_RPC_FRAME_LIMIT_MB": "8",
        },
        timeout=200.0,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(lines[-1])["extra"]["rpc_transport"]
    assert st["ok"], st
    for key in (
        "sizes_mb",
        "paths",
        "speedup_oob_vs_legacy",
        "codec_roundtrip_speedup_oob_vs_legacy",
        "speedup_shm_vs_legacy",
        "big_roundtrip",
    ):
        assert key in st, key
    for path in ("legacy", "oob", "shm"):
        per_size = st["paths"][path]["mb8"]
        for key in ("p50_ms", "p95_ms", "mb_per_sec", "codec_ms_per_roundtrip"):
            assert key in per_size, (path, key)
    # the leg above the frame limit must have round-tripped chunked
    assert st["big_roundtrip"]["ok"]
    assert st["big_roundtrip"]["chunked"]


def test_observability_overhead_stage_schema():
    """Pin the observability_overhead artifact schema: five interleaved
    legs (disabled / unsampled / flight / telem / sampled) over the
    same live serve path, per-leg p50, the relative + absolute
    overheads, the flight-recorder-vs-unsampled delta, and the
    push-telemetry-vs-flight delta. The <2% (and flight/telem <1%)
    acceptance numbers come from the full-size driver run — a loaded CI
    core would flake a hard threshold here, so the schema and sanity
    ordering are the contract."""
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "observability_overhead",
            "BENCH_DEADLINE": "170",
            "BENCH_OBS_ROUNDS": "2",
            "BENCH_OBS_REQUESTS": "25",
        },
        timeout=200.0,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(lines[-1])["extra"]["observability_overhead"]
    assert st["ok"], st
    for key in (
        "requests_per_leg",
        "legs",
        "overhead_unsampled_pct",
        "overhead_unsampled_abs_us",
        "overhead_flight_pct",
        "overhead_flight_abs_us",
        "overhead_flight_vs_unsampled_pct",
        "overhead_telem_pct",
        "overhead_telem_abs_us",
        "overhead_telem_vs_flight_pct",
        "telem_interval_s",
        "overhead_sampled_pct",
        "overhead_sampled_abs_us",
    ):
        assert key in st, key
    assert st["requests_per_leg"] == 50
    for leg in ("disabled", "unsampled", "flight", "telem", "sampled"):
        assert st["legs"][leg]["p50_us"] > 0, leg
    # full span recording can't be cheaper than the unsampled path's
    # contextvar reads (sanity on the leg wiring, not a perf threshold)
    assert (
        st["overhead_sampled_abs_us"] >= st["overhead_unsampled_abs_us"] - 50
    )


def test_request_overhead_stage_schema():
    """Pin the request_overhead artifact schema: three interleaved legs
    (baseline = pre-fast1 stack on TCP, fast_tcp = BEFS + inline
    dispatch on the identical wire, fast = same over the unix socket),
    per-leg uncontended/concurrent throughput, the live-stats codec
    bucket, the per-request decomposition, and the paired speedups.
    The >=2x uncontended acceptance number comes from the full-size
    driver run — a loaded CI core would flake a hard threshold here,
    so the schema and fast-frame wiring are the contract."""
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "request_overhead",
            "BENCH_DEADLINE": "170",
            "BENCH_REQ_ROUNDS": "3",
            "BENCH_REQ_N": "40",
            "BENCH_REQ_CALLERS": "4",
            "BENCH_REQ_PER_CALLER": "5",
        },
        timeout=200.0,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(lines[-1])["extra"]["request_overhead"]
    assert st["ok"], st
    for key in (
        "legs",
        "decomposition_us",
        "uncontended_speedup",
        "concurrent_speedup",
        "threshold_bytes",
    ):
        assert key in st, key
    for leg in ("baseline", "fast_tcp", "fast"):
        lg = st["legs"][leg]
        for key in (
            "transport",
            "uncontended",
            "concurrent",
            "codec_us_per_req",
            "fast_frames",
            "small_frames_out",
            "fast_frame_hit_rate",
        ):
            assert key in lg, (leg, key)
        for key in ("req_per_sec", "p50_us", "p95_us", "median_req_per_sec"):
            assert lg["uncontended"][key] > 0, (leg, key)
        assert lg["concurrent"]["req_per_sec"] > 0, leg
    for key in (
        "codec_us",
        "tracing_ctx_us",
        "scheduler_us",
        "scoring_us",
        "asyncio_hop_us",
        "wire_residual_us",
    ):
        assert key in st["decomposition_us"], key
    # the fast-frame wiring is the contract: the baseline leg must
    # have negotiated NO fast frames and the fast legs must have run
    # entirely on them
    assert st["legs"]["baseline"]["fast_frames"] is False
    assert st["legs"]["baseline"]["small_frames_out"] == 0
    for leg in ("fast_tcp", "fast"):
        assert st["legs"][leg]["fast_frames"] is True
        assert st["legs"][leg]["small_frames_out"] > 0, leg
        assert st["legs"][leg]["fast_frame_hit_rate"] == 1.0, leg
    assert st["legs"]["fast"]["transport"] == "uds"
    assert st["legs"]["fast_tcp"]["transport"] == "tcp"


def test_scheduler_goodput_stage_schema():
    """Pin the scheduler_goodput artifact schema: per-request router vs
    global scheduler on the same mixed-priority workload (goodput, per
    class p50/p99, SLO attainment, batch occupancy) plus the
    interleaved uncontended leg (the <2% scheduler-overhead acceptance
    gate reads overhead_scheduler_pct from the full-size driver run — a
    loaded CI core would flake a hard threshold here, so schema and
    sanity ordering are the contract)."""
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "scheduler_goodput",
            "BENCH_DEADLINE": "170",
            "BENCH_SCHED_ROUNDS": "1",
            "BENCH_SCHED_WAVES": "6",
            "BENCH_SCHED_SOLO": "12",
        },
        timeout=200.0,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(lines[-1])["extra"]["scheduler_goodput"]
    assert st["ok"], st
    for key in (
        "workload",
        "legs",
        "goodput_speedup",
        "occupancy_gain",
        "uncontended",
    ):
        assert key in st, key
    for leg in ("router", "scheduler"):
        d = st["legs"][leg]
        for key in (
            "goodput_rps",
            "interactive_p50_ms",
            "interactive_p99_ms",
            "interactive_slo_met_pct",
            "bulk_p50_ms",
            "bulk_p99_ms",
            "batch_occupancy",
            "failed",
        ):
            assert key in d, (leg, key)
        assert d["goodput_rps"] > 0, leg
        assert d["failed"] == 0, (leg, d)
    # the same workload ran both ways; coalescing must raise occupancy
    # (the mechanism — the goodput consequence is a hardware number)
    assert (
        st["legs"]["scheduler"]["batch_occupancy"]
        >= st["legs"]["router"]["batch_occupancy"]
    ), st["legs"]
    unc = st["uncontended"]
    for key in (
        "router_p50_us",
        "scheduler_p50_us",
        "overhead_scheduler_pct",
        "overhead_scheduler_abs_us",
    ):
        assert key in unc, key
    assert unc["router_p50_us"] > 0 and unc["scheduler_p50_us"] > 0


def test_gray_failure_stage_schema():
    """Pin the gray_failure artifact schema: the slow_replica scenario
    (seeded slow-ramp on one replica, health checks still passing) run
    without and with probation + hedging. The acceptance gates ride the
    stage's own ok flag: the defended leg recovers tail p99 to within
    2x the healthy baseline with zero failed idempotent requests, and
    the undefended leg shows the degradation (proving the scenario
    still exercises what the machinery fixes)."""
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "gray_failure",
            "BENCH_DEADLINE": "280",
        },
        timeout=320.0,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(lines[-1])["extra"]["gray_failure"]
    assert st["ok"], st
    for key in (
        "scenario",
        "seed",
        "legs",
        "tail_p99_improvement",
        "goodput_delta_pct",
        "p99_recovered",
        "degradation_shown",
    ):
        assert key in st, key
    assert st["scenario"] == "slow_replica"
    for leg in ("undefended", "defended"):
        d = st["legs"][leg]
        for key in (
            "requests",
            "failed",
            "goodput_rps",
            "p50_ms",
            "p99_ms",
            "baseline_p99_ms",
            "tail_p99_ms",
            "probations",
            "hedges",
            "invariants_ok",
        ):
            assert key in d, (leg, key)
        # zero failed IDEMPOTENT requests in BOTH legs: failover alone
        # keeps traffic alive; the defenses fix the tail, not liveness
        assert d["failed"] == 0, (leg, d)
        assert d["goodput_rps"] > 0, leg
    assert st["p99_recovered"] is True
    assert st["degradation_shown"] is True
    # the machinery actually engaged in the defended leg only
    assert st["legs"]["defended"]["probations"] >= 1
    assert st["legs"]["defended"]["hedges"] > 0
    assert st["legs"]["undefended"]["probations"] == 0
    assert st["legs"]["undefended"]["hedges"] == 0
    # the headline: the defended tail sits well under the undefended
    assert st["tail_p99_improvement"] > 1.0, st


def test_router_scaling_stage_schema():
    """Pin the router_scaling artifact schema: the fleet_scale scenario
    run per router count, goodput capacity-bound per router so the
    4-router leg must reach >= 3x the 1-router goodput; the router_loss
    leg (one of three routers SIGKILL'd mid-traffic) must lose zero
    idempotent requests; and the seam probe reports serial per-request
    overhead through a table-synced standalone router vs the in-process
    controller path. Legs pinned to 1,4 to keep the gate fast — the
    default 1,2,4,8 sweep is the bench-artifact run."""
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "router_scaling",
            "BENCH_ROUTER_LEGS": "1,4",
            "BENCH_DEADLINE": "280",
        },
        timeout=320.0,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(lines[-1])["extra"]["router_scaling"]
    assert st["ok"], st
    for key in (
        "scenario",
        "seed",
        "legs",
        "goodput_scaling_4x_vs_1",
        "router_loss",
        "per_request_overhead_us",
    ):
        assert key in st, key
    assert st["scenario"] == "fleet_scale"
    for name in ("1", "4"):
        leg = st["legs"][name]
        for key in (
            "routers",
            "offered",
            "served",
            "wall_s",
            "goodput_rps",
            "table_staleness_max_s",
            "invariants_ok",
        ):
            assert key in leg, (name, key)
        assert leg["invariants_ok"] is True, leg
        assert leg["goodput_rps"] > 0, leg
        # bounded staleness is measured, not just asserted green
        assert leg["table_staleness_max_s"] is not None, leg
    # the acceptance gate: aggregate goodput scales near-linearly
    assert st["goodput_scaling_4x_vs_1"] >= 3.0, st
    loss = st["router_loss"]
    for key in (
        "requests",
        "failed_idempotent",
        "client_failovers",
        "killed",
        "table_staleness_max_s",
        "invariants_ok",
    ):
        assert key in loss, key
    # zero idempotent loss across the router kill, and the clients
    # actually hopped to a sibling (the kill engaged)
    assert loss["failed_idempotent"] == 0, loss
    assert loss["client_failovers"] > 0, loss
    assert loss["killed"] == ["r1"], loss
    assert loss["invariants_ok"] is True, loss
    probe = st["per_request_overhead_us"]
    for key in ("controller", "router", "router_delta_us_p50"):
        assert key in probe, key
    for leg in ("controller", "router"):
        assert probe[leg]["p50_us"] > 0, probe


def test_token_streaming_stage_schema():
    """Pin the token_streaming artifact schema: the co-batched
    throughput leg must show real step-level batching (mean occupancy
    above 1, far fewer steps than serial token count), the inter-token
    leg reports the first-class latency SLO numbers, and the
    join-mid-batch leg proves no head-of-line blocking — the short
    interactive stream joined a RUNNING batch and finished while the
    long bulk generation was still going."""
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "token_streaming",
            "BENCH_DEADLINE": "160",
        },
        timeout=200.0,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    st = json.loads(lines[-1])["extra"]["token_streaming"]
    assert st["ok"], st
    tp = st["throughput"]
    for key in (
        "streams",
        "new_tokens_each",
        "tokens_per_sec",
        "tokens_per_sec_per_chip",
        "batch_occupancy",
        "steps",
        "wall_s",
    ):
        assert key in tp, key
    assert tp["tokens_per_sec"] > 0
    assert tp["tokens_per_sec_per_chip"] > 0
    # continuous batching engaged: sequences shared steps
    assert tp["batch_occupancy"] > 1.0, tp
    assert tp["steps"] < tp["streams"] * tp["new_tokens_each"], tp
    it = st["inter_token"]
    for key in ("ttft_ms", "inter_token_p50_ms", "inter_token_p99_ms"):
        assert key in it, key
        assert it[key] > 0, it
    assert it["inter_token_p99_ms"] >= it["inter_token_p50_ms"]
    jm = st["join_mid_batch"]
    for key in (
        "joined_mid_batch",
        "mid_batch_ttft_ms",
        "short_wall_ms",
        "long_still_running",
        "long_tokens",
    ):
        assert key in jm, key
    # the no-HOL-blocking proof rides the artifact, not just a test
    assert jm["joined_mid_batch"] == 1, jm
    assert jm["long_still_running"] == 1, jm
    assert jm["mid_batch_ttft_ms"] > 0, jm
    eng = st["engine"]
    assert eng["n_devices"] >= 1
    assert eng["kv_block_size"] >= 1


def _artifact(vit=1000.0, pipelined=2.0, p50_us=100.0) -> dict:
    """A minimal bench artifact in the real schema, tunable per metric."""
    return {
        "metric": "dinov2_vitb14_embed_images_per_sec_per_chip",
        "value": vit,
        "unit": "images/sec",
        "vs_baseline": round(vit / 500.0, 3),
        "extra": {
            "pipeline_overlap": {
                "ok": True,
                "serial_s": 4.0,
                "pipelined_s": pipelined,
                "speedup": round(4.0 / pipelined, 2),
            },
            "observability_overhead": {
                "ok": True,
                "legs": {"disabled": {"p50_us": p50_us}},
                "overhead_flight_vs_unsampled_pct": 0.5,
            },
            "skipped": {"unet3d": "budget"},
            "attempts": 1,
        },
    }


def test_compare_mode_schema_and_exit_codes(tmp_path):
    """Pin the --compare contract: one JSON line with per-stage deltas
    and direction-aware regression flags; exit 0 when the candidate
    holds, non-zero past the tolerance."""
    a = tmp_path / "a.json"
    b_ok = tmp_path / "b_ok.json"
    b_bad = tmp_path / "b_bad.json"
    a.write_text(json.dumps(_artifact()))
    # candidate within tolerance (slightly slower, under 10%)
    b_ok.write_text(json.dumps(_artifact(vit=950.0, pipelined=2.1)))
    # candidate regressed: headline -30%, pipeline 2x slower
    b_bad.write_text(json.dumps(_artifact(vit=700.0, pipelined=4.0)))

    def run_compare(b_path):
        proc = subprocess.run(
            [sys.executable, str(BENCH), "--compare", str(a), str(b_path)],
            capture_output=True,
            text=True,
            timeout=60,
            cwd=str(BENCH.parent),
        )
        lines = [
            ln
            for ln in proc.stdout.strip().splitlines()
            if ln.startswith("{")
        ]
        assert len(lines) == 1, proc.stdout
        return proc.returncode, json.loads(lines[0])

    rc, ok_report = run_compare(b_ok)
    assert rc == 0
    assert ok_report["ok"] is True
    for key in (
        "mode",
        "tolerance_pct",
        "stages_compared",
        "stages_only_a",
        "stages_only_b",
        "regressions",
        "improvements",
        "stages",
    ):
        assert key in ok_report, key
    assert "pipeline_overlap" in ok_report["stages_compared"]
    assert "headline" in ok_report["stages_compared"]
    entry = ok_report["stages"]["pipeline_overlap"]["pipelined_s"]
    assert entry["direction"] == "lower"
    assert entry["regression"] is False

    rc, bad_report = run_compare(b_bad)
    assert rc == 1
    assert bad_report["ok"] is False
    regressed = {r["metric"] for r in bad_report["regressions"]}
    assert "headline.images_per_sec_per_chip" in regressed
    assert "pipeline_overlap.pipelined_s" in regressed
    # direction inference: the slower pipelined_s also halves speedup —
    # a higher-is-better metric moving DOWN is a regression too
    assert "pipeline_overlap.speedup" in regressed


def test_compare_token_streaming_directions(tmp_path):
    """Direction inference on the streaming metrics: tokens_per_sec /
    batch_occupancy are higher-is-better (a drop regresses), the
    inter-token percentiles are lower-is-better (a rise regresses) —
    so a compare gate catches a co-batching break from either side."""

    def art(tps, occ, p99):
        a = _artifact()
        a["extra"]["token_streaming"] = {
            "ok": True,
            "throughput": {
                "tokens_per_sec": tps,
                "batch_occupancy": occ,
            },
            "inter_token": {"inter_token_p99_ms": p99},
        }
        return a

    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(art(2000.0, 8.0, 3.0)))
    # throughput/occupancy DOWN, tail latency UP: all three must flag
    b.write_text(json.dumps(art(1200.0, 4.0, 9.0)))
    proc = subprocess.run(
        [sys.executable, str(BENCH), "--compare", str(a), str(b)],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=str(BENCH.parent),
    )
    assert proc.returncode == 1
    report = json.loads(
        [ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")][-1]
    )
    stage = report["stages"]["token_streaming"]
    assert stage["throughput.tokens_per_sec"]["direction"] == "higher"
    assert stage["throughput.batch_occupancy"]["direction"] == "higher"
    assert stage["inter_token.inter_token_p99_ms"]["direction"] == "lower"
    regressed = {r["metric"] for r in report["regressions"]}
    assert {
        "token_streaming.throughput.tokens_per_sec",
        "token_streaming.throughput.batch_occupancy",
        "token_streaming.inter_token.inter_token_p99_ms",
    } <= regressed


def test_compare_usage_error_is_json_not_traceback(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(BENCH), "--compare", "only-one.json"],
        capture_output=True,
        text=True,
        timeout=60,
        cwd=str(BENCH.parent),
    )
    assert proc.returncode == 2
    d = json.loads(proc.stdout.strip().splitlines()[-1])
    assert d["ok"] is False and "usage" in d["error"]


def test_no_tpu_without_cpu_asked_for_exits_nonzero():
    # the suite's JAX_PLATFORMS=cpu makes the worker's probe come up on
    # the CPU; without BENCH_PLATFORM=cpu that is "no TPU", not a run
    env = {k: v for k, v in os.environ.items() if k != "BENCH_PLATFORM"}
    proc = subprocess.run(
        [sys.executable, str(BENCH)],
        capture_output=True,
        text=True,
        timeout=120,
        env=dict(env, BENCH_CONFIGS="search", BENCH_ATTEMPTS="1"),
        cwd=str(BENCH.parent),
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    d = json.loads(proc.stdout.strip().splitlines()[-1])
    assert d["extra"]["probe"]["ok"] is False
    assert "no TPU" in d["extra"]["probe"]["error"]
    assert d["extra"]["search_latency"] is None  # no stage ran on the CPU


def test_failed_stage_prints_artifact_and_exits_nonzero():
    # the env-gated 'sleep' stage hangs mid-stage DETERMINISTICALLY (no
    # dependence on compile latency or a warm compilation cache), so a
    # tiny BENCH_STALL always triggers the hang detector. The artifact
    # still prints, and the exit status says a stage failed.
    proc, lines = _run(
        {
            "BENCH_CONFIGS": "sleep",
            "BENCH_SLEEP_S": "90",
            "BENCH_DEADLINE": "120",
            "BENCH_STALL": "6",
            "BENCH_ATTEMPTS": "1",
        }
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    d = json.loads(lines[-1])
    assert d["value"] == 0.0
    diags = d["extra"]["diagnostics"]
    assert any("hung mid-stage" in (x.get("killed") or "") for x in diags)


def test_sigterm_emits_artifact_before_dying():
    env = dict(
        os.environ,
        BENCH_PLATFORM="cpu",
        BENCH_CONFIGS="sleep",
        BENCH_SLEEP_S="240",
        BENCH_DEADLINE="300",
    )
    proc = subprocess.Popen(
        [sys.executable, str(BENCH)],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=env,
        cwd=str(BENCH.parent),
    )
    try:
        time.sleep(8)  # worker is deterministically mid-sleep-stage
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()  # never leak a detached bench past the test
    assert proc.returncode == 1  # the sleep stage never completed
    lines = [ln for ln in out.strip().splitlines() if ln.startswith("{")]
    d = json.loads(lines[-1])
    assert d["extra"].get("deadline_hit") is True
