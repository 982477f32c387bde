"""End-to-end request tracing + the unified metrics plane.

Rides the in-process multi-host chaos harness (real websockets, one
event loop): a sampled request minted in DeploymentHandle.call crosses
the RPC plane to a worker host, through the replica semaphore, the
continuous batcher, and the engine's overlapped pipeline — and comes
back as ONE reconstructable span tree whose stage durations account
for the observed end-to-end latency. Plus: legacy-peer negotiation
(no trace bytes on the wire without ``trace1``), failover under one
trace_id, and the Prometheus scrape surface.
"""

import asyncio
import gc
import re
import time
from pathlib import Path

import aiohttp
import pytest

from bioengine_tpu.apps.builder import AppBuilder
from bioengine_tpu.cluster.state import ClusterState
from bioengine_tpu.cluster.topology import TpuTopology
from bioengine_tpu.rpc.client import connect_to_server
from bioengine_tpu.rpc.server import RpcServer
from bioengine_tpu.serving import (
    DeploymentSpec,
    RequestOptions,
    ServeController,
)
from bioengine_tpu.testing import faults
from bioengine_tpu.utils import metrics, tracing
from bioengine_tpu.worker_host import WorkerHost

pytestmark = [pytest.mark.integration, pytest.mark.anyio]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(autouse=True)
def _sample_everything(monkeypatch):
    """Deterministic head sampling for these tests; production default
    stays ~1%."""
    monkeypatch.setenv("BIOENGINE_TRACE_SAMPLE", "1.0")
    tracing.reset_env_cache()
    tracing.clear_spans()
    yield
    tracing.reset_env_cache()


# ---------------------------------------------------------------------------
# the observability app: batcher + tiled engine pipeline behind a verb
# ---------------------------------------------------------------------------

OBS_MANIFEST = """\
name: Obs App
id: obs-app
id_emoji: "\U0001F50E"
description: batcher + engine pipeline for trace tests
type: tpu-serve
version: 1.0.0
deployments:
  - obs_dep:ObsDep
authorized_users: ["*"]
deployment_config:
  obs_dep:
    num_replicas: {num_replicas}
    min_replicas: {num_replicas}
    max_replicas: {num_replicas}
    chips: 2
    autoscale: false
"""

OBS_SOURCE = '''\
import asyncio

import numpy as np

from bioengine_tpu.rpc import schema_method
from bioengine_tpu.runtime.engine import EngineConfig, InferenceEngine
from bioengine_tpu.serving import ContinuousBatcher


class ObsDep:
    async def async_init(self):
        # tiny tiles force the overlapped tiled pipeline on a 40x40 input
        config = EngineConfig(
            max_tile=16, tile=8, tile_overlap=2, pipeline_depth=2
        )
        self.engine = InferenceEngine(
            model_id="obs-toy",
            apply_fn=lambda params, x: x * params,
            params=np.float32(2.0),
            config=config,
        )
        self.batcher = ContinuousBatcher(
            self._run_batch, max_batch=4, max_wait_ms=5.0
        )

    async def _run_batch(self, signature, payloads):
        merged = np.concatenate(payloads, axis=0)
        out = await self.engine.predict_async(merged)
        res, start = [], 0
        for p in payloads:
            res.append(out[start : start + len(p)])
            start += len(p)
        return res

    @schema_method
    async def infer(self, n: int = 1, size: int = 40, context=None):
        """One request through batcher + tiled engine pipeline."""
        x = np.ones((n, size, size, 1), np.float32)
        y = await self.batcher.submit(("obs", x.shape[1:]), x)
        # a deliberate, dominant stage so the tree's duration math is
        # assertable without depending on CPU compile noise
        await asyncio.sleep(0.15)
        return {"sum": float(np.asarray(y).sum())}

    async def close(self):
        await self.batcher.close()
        self.engine.close()
'''


def _write_obs_app(tmp_path: Path, num_replicas: int = 1) -> Path:
    app_dir = tmp_path / "obs-src"
    app_dir.mkdir(exist_ok=True)
    (app_dir / "manifest.yaml").write_text(
        OBS_MANIFEST.format(num_replicas=num_replicas)
    )
    (app_dir / "obs_dep.py").write_text(OBS_SOURCE)
    return app_dir


def _no_local_chips() -> ClusterState:
    return ClusterState(TpuTopology(chips=(), n_hosts=1, platform="cpu"))


@pytest.fixture()
async def obs_plane(tmp_path):
    server = RpcServer(host="127.0.0.1", admin_users=["admin"])
    await server.start()
    token = server.issue_token("admin", is_admin=True)
    controller = ServeController(_no_local_chips(), health_check_period=3600)
    controller.attach_rpc(server, admin_users=["admin"])
    hosts = []

    async def spawn_host(host_id: str) -> WorkerHost:
        host = WorkerHost(
            server_url=server.url,
            token=token,
            host_id=host_id,
            workspace_dir=tmp_path / f"ws-{host_id}",
        )
        await host.start()
        hosts.append(host)
        return host

    try:
        yield server, controller, spawn_host, tmp_path
    finally:
        for host in hosts:
            try:
                await host.stop()
            except Exception:
                pass
        await controller.stop()
        await server.stop()


async def _deploy_obs_app(controller, tmp_path, num_replicas: int = 1):
    builder = AppBuilder(workdir_root=tmp_path / "apps")
    built = builder.build(
        app_id="obs-app",
        local_path=_write_obs_app(tmp_path, num_replicas),
    )
    await controller.deploy("obs-app", built.specs)
    return controller.apps["obs-app"].replicas["obs_dep"]


def _flatten(tree_nodes):
    out = []
    stack = list(tree_nodes)
    while stack:
        node = stack.pop()
        out.append(node)
        stack.extend(node["children"])
    return out


class TestFullPathTrace:
    async def test_span_tree_accounts_for_e2e_latency(self, obs_plane):
        """Acceptance: one sampled request client -> controller ->
        remote replica -> batcher -> engine pipeline yields ONE span
        tree under one trace_id whose stage durations sum to ~= the
        observed end-to-end latency."""
        server, controller, spawn_host, tmp_path = obs_plane
        await spawn_host("h1")
        await _deploy_obs_app(controller, tmp_path)
        handle = controller.get_handle("obs-app")

        # warmup: compile the engine programs outside the timed request
        await handle.call("infer", n=1)
        tracing.clear_spans()

        t0 = time.monotonic()
        result = await handle.call("infer", n=1)
        e2e = time.monotonic() - t0
        # 40x40 input, every pixel doubled, ramp-blend stitching is
        # weight-normalized
        assert result["sum"] == pytest.approx(2.0 * 40 * 40, rel=1e-3)

        (root_span,) = tracing.get_spans(name="request")
        trace_id = root_span["trace_id"]
        tree = tracing.build_trace_tree(trace_id)
        assert tree["trace_id"] == trace_id
        (root,) = tree["tree"]
        assert root["name"] == "request"

        nodes = _flatten(tree["tree"])
        names = {n["name"] for n in nodes}
        # the full stage ladder is present in ONE tree: routing,
        # attempt, the RPC hop, host-side handling, semaphore park,
        # execution, batch queue wait, and the engine pipeline
        assert {
            "request",
            "route",
            "attempt",
            "remote.call",
            "rpc.call",
            "rpc.handle",
            "replica.park",
            "replica.execute",
            "batch.queue",
            "engine.predict",
        } <= names
        # every span belongs to this one trace
        assert all(n.get("trace_id") == trace_id for n in nodes)

        # duration accounting: the root span tracks the observed e2e,
        # and its direct children (route + attempt) cover it without
        # exceeding it
        assert root["duration_s"] == pytest.approx(e2e, rel=0.35)
        child_sum = sum(c["duration_s"] for c in root["children"])
        assert child_sum <= root["duration_s"] * 1.05
        assert child_sum >= root["duration_s"] * 0.6
        # the deliberate 150 ms stage dominates replica.execute
        execute = next(n for n in nodes if n["name"] == "replica.execute")
        assert execute["duration_s"] >= 0.14
        # the engine pipeline span carries the per-stage breakdown
        engine_span = next(n for n in nodes if n["name"] == "engine.predict")
        stage_seconds = engine_span["attrs"]["stage_seconds"]
        assert {
            "cut", "put", "dispatch", "compute", "readback", "stitch"
        } <= set(stage_seconds)
        # get_traces(trace_id=...) rollup matches the tree
        assert tree["stage_seconds"]["request"] == root["duration_s"]

    async def test_local_path_batch_queue_stays_in_one_tree(self, tmp_path):
        """A single-process deployment (no RPC hop) using the batcher:
        the retroactive batch.queue span must parent under the
        submitter's replica.execute span, not orphan a second root —
        ctx.span_id is None for locally-minted contexts."""
        import numpy as np

        from bioengine_tpu.serving import ContinuousBatcher

        class LocalApp:
            async def async_init(self):
                self.batcher = ContinuousBatcher(
                    self._run, max_batch=4, max_wait_ms=5.0
                )

            async def _run(self, sig, payloads):
                return [p * 2 for p in payloads]

            async def infer(self):
                out = await self.batcher.submit("k", np.ones(4))
                return float(out.sum())

            async def close(self):
                await self.batcher.close()

        controller = ServeController(_no_local_chips(), health_check_period=3600)
        try:
            await controller.deploy(
                "local-app",
                [DeploymentSpec(name="entry", instance_factory=LocalApp)],
            )
            handle = controller.get_handle("local-app")
            await handle.call("infer")
            tracing.clear_spans()
            assert await handle.call("infer") == 8.0
            (root_span,) = tracing.get_spans(name="request")
            tree = tracing.build_trace_tree(root_span["trace_id"])
            assert len(tree["tree"]) == 1, tree["tree"]
            (bq,) = tracing.get_spans(
                name="batch.queue", trace_id=root_span["trace_id"]
            )
            (execute,) = tracing.get_spans(
                name="replica.execute", trace_id=root_span["trace_id"]
            )
            assert bq["parent_id"] == execute["span_id"]
            # started_at is back-dated to the enqueue, so the span
            # sorts where the wait happened
            assert bq["started_at"] <= execute["started_at"] + execute[
                "duration_s"
            ]
        finally:
            await controller.stop()

    async def test_unsampled_request_leaves_no_spans(
        self, obs_plane, monkeypatch
    ):
        server, controller, spawn_host, tmp_path = obs_plane
        await spawn_host("h1")
        await _deploy_obs_app(controller, tmp_path)
        handle = controller.get_handle("obs-app")
        await handle.call("infer", n=1)  # warm (sampled — autouse env)
        monkeypatch.setenv("BIOENGINE_TRACE_SAMPLE", "0.0")
        tracing.reset_env_cache()
        tracing.clear_spans()
        await handle.call("infer", n=1)
        assert tracing.get_spans(include_open=True) == []


class TestFailoverTrace:
    async def test_failed_attempt_and_failover_share_one_trace(
        self, obs_plane
    ):
        """Satellite: kill the first routed replica call mid-request —
        the trace shows the failed attempt AND the successful failover
        attempt under one trace_id."""
        server, controller, spawn_host, tmp_path = obs_plane
        await spawn_host("h1")
        await spawn_host("h2")
        replicas = await _deploy_obs_app(controller, tmp_path, num_replicas=2)
        assert sorted(r.host_id for r in replicas) == ["h1", "h2"]
        handle = controller.get_handle("obs-app")
        await handle.call("infer", n=1)  # warm both engines? (one is enough)

        tracing.clear_spans()
        faults.configure("host.replica_call", "raise", nth=1, count=1)
        result = await handle.call(
            "infer", n=1, options=RequestOptions(idempotent=True)
        )
        assert result["sum"] == pytest.approx(2.0 * 40 * 40, rel=1e-3)

        (root_span,) = tracing.get_spans(name="request")
        attempts = tracing.get_spans(
            name="attempt", trace_id=root_span["trace_id"]
        )
        assert len(attempts) == 2
        first, second = attempts
        assert "error" in first and "error" not in second
        assert first["attrs"]["replica"] != second["attrs"]["replica"]
        assert first["attrs"]["attempt"] == 1
        assert second["attrs"]["attempt"] == 2


class TestLegacyNegotiation:
    async def test_no_trace_fields_without_trace1(self, obs_plane):
        """Satellite: a peer that does not advertise ``trace1`` never
        sees trace fields on the wire; a trace1 peer sees them exactly
        when the request is sampled."""
        server, controller, spawn_host, tmp_path = obs_plane

        async def make_echo_client(name, protocols):
            conn = await connect_to_server(
                {"server_url": server.url, "protocols": protocols}
            )
            seen = []
            orig = conn._handle_incoming_call

            async def spy(msg):
                seen.append(msg)
                await orig(msg)

            conn._handle_incoming_call = spy
            conn._seen = seen
            trace_state = []

            def echo(x):
                trace_state.append(tracing.current_trace())
                return x

            conn._trace_state = trace_state
            # forwarded CALLs carry the caller's service id verbatim, so
            # address each peer by the FULL id REGISTER handed back
            reg = await conn.register_service({"id": name, "echo": echo})
            return conn, reg["id"]

        legacy, legacy_id = await make_echo_client("legacy-svc", ["oob1"])
        modern, modern_id = await make_echo_client("modern-svc", None)
        try:
            ctx = tracing.maybe_start_trace(sample=True)
            token = tracing.activate(ctx)
            try:
                await server.call_service_method(legacy_id, "echo", (1,))
                await server.call_service_method(modern_id, "echo", (1,))
            finally:
                tracing.deactivate(token)

            (legacy_msg,) = legacy._seen
            (modern_msg,) = modern._seen
            assert "trace" not in legacy_msg  # legacy wire: byte-identical
            assert modern_msg["trace"]["tid"] == ctx.trace_id
            assert legacy._trace_state == [None]
            (remote_ctx,) = modern._trace_state
            assert remote_ctx is not None
            assert remote_ctx.trace_id == ctx.trace_id

            # unsampled requests put nothing on the wire even for
            # trace1 peers (near-zero unsampled cost)
            modern._seen.clear()
            ctx2 = tracing.maybe_start_trace(sample=False)
            token = tracing.activate(ctx2)
            try:
                await server.call_service_method(modern_id, "echo", (1,))
            finally:
                tracing.deactivate(token)
            (msg2,) = modern._seen
            assert "trace" not in msg2
        finally:
            await legacy.disconnect()
            await modern.disconnect()


_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) .+|[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? "
    r"(-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|-Inf|NaN))$"
)


class TestMetricsSurface:
    async def test_prometheus_endpoint_serves_request_histograms(
        self, obs_plane
    ):
        """Acceptance: GET /metrics on the worker serves valid
        Prometheus text including request-latency histograms labeled
        by deployment and replica."""
        server, controller, spawn_host, tmp_path = obs_plane
        await spawn_host("h1")
        await _deploy_obs_app(controller, tmp_path)
        handle = controller.get_handle("obs-app")
        await handle.call("infer", n=1)

        async with aiohttp.ClientSession() as session:
            async with session.get(server.http_url + "/metrics") as resp:
                assert resp.status == 200
                assert "version=0.0.4" in resp.headers["Content-Type"]
                body = await resp.text()

        for line in body.splitlines():
            assert _PROM_LINE.match(line), f"invalid line: {line!r}"
        # request-latency histogram labeled by deployment (+ method/app)
        assert re.search(
            r'bioengine_request_e2e_seconds_bucket\{app="obs-app",'
            r'deployment="obs_dep",le="\+Inf",method="infer"\} \d+',
            body,
        ), body[:2000]
        # per-replica execution histogram (host runs in this process)
        assert re.search(
            r'bioengine_replica_request_seconds_bucket\{app="obs-app",'
            r'deployment="obs_dep",le="\+Inf",replica="obs_dep-[0-9a-f]+"\}',
            body,
        )
        # absorbed islands: transport counters + serving gauges
        assert "bioengine_rpc_bytes_out" in body
        assert "bioengine_serve_replicas" in body
        assert "bioengine_chips_free" in body
        assert "bioengine_batcher_requests_total" in body

    async def test_get_metrics_verb_and_describe_agree(self, obs_plane):
        """Satellite: describe() keeps its schema but is backed by the
        registry — the same number shows up in both surfaces."""
        server, controller, spawn_host, tmp_path = obs_plane
        host = await spawn_host("h1")
        await _deploy_obs_app(controller, tmp_path)
        handle = controller.get_handle("obs-app")
        for _ in range(3):
            await handle.call("infer", n=1)

        replica = host.replicas[next(iter(host.replicas))]
        desc = replica.describe()
        assert desc["total_requests"] == 3
        assert desc["uptime_seconds"] > 0

        # the host's get_metrics verb (over RPC) sees the same counter
        snap = await controller._call_host(host.service_id, "get_metrics")
        series = snap["replica_requests_total"]["series"]
        mine = [
            s
            for s in series
            if s["labels"]["replica"] == replica.replica_id
        ]
        assert mine and mine[0]["value"] == 3

        # worker status["rpc"] shape is fed by the same RpcStats the
        # registry scrapes
        rpc_desc = server.describe()
        assert rpc_desc["transport"]["msgs_in"] > 0
        prom = await controller._call_host(
            host.service_id, "get_metrics", prometheus=True
        )
        assert isinstance(prom, str) and "bioengine_rpc_msgs_in" in prom


class TestTracingDisabled:
    async def test_metrics_and_slow_log_survive_tracing_off(
        self, monkeypatch, caplog
    ):
        """BIOENGINE_TRACING=0 is the *tracing* kill-switch — metrics
        (own knob: BIOENGINE_METRICS) and slow-request logging (own
        knob: BIOENGINE_SLOW_REQUEST_MS) keep working, with
        trace_id=- in the log line."""
        import logging

        monkeypatch.setenv("BIOENGINE_TRACING", "0")
        monkeypatch.setenv("BIOENGINE_SLOW_REQUEST_MS", "10")
        tracing.reset_env_cache()

        class App:
            async def infer(self):
                await asyncio.sleep(0.05)
                return 1

        controller = ServeController(_no_local_chips(), health_check_period=3600)
        serving_logger = logging.getLogger("bioengine.serving")
        serving_logger.addHandler(caplog.handler)
        try:
            await controller.deploy(
                "off-app",
                [DeploymentSpec(name="entry", instance_factory=App)],
            )
            handle = controller.get_handle("off-app")
            tracing.clear_spans()
            for _ in range(3):
                await handle.call("infer")
        finally:
            serving_logger.removeHandler(caplog.handler)
            await controller.stop()
            tracing.reset_env_cache()

        # no request-path spans minted at all
        assert tracing.get_spans(name="request", include_open=True) == []
        # but the e2e histogram and outcome counter still counted
        snap = metrics.collect()
        mine = [
            s
            for s in snap["request_e2e_seconds"]["series"]
            if s["labels"]["app"] == "off-app"
        ]
        assert mine and mine[0]["count"] == 3
        outcomes = [
            s
            for s in snap["requests_total"]["series"]
            if s["labels"]["app"] == "off-app"
        ]
        assert outcomes and outcomes[0]["value"] == 3
        # and the slow log fired, un-correlatable but present
        slow = [r for r in caplog.records if "slow_request" in r.message]
        assert slow and "trace_id=-" in slow[-1].message


class TestTraceBufferHardening:
    async def test_span_ring_stays_bounded_under_sustained_sampled_load(
        self,
    ):
        """Satellite: 100%-sampled load three times the ring size never
        grows the buffer past MAX_SPANS — the ring is the memory
        ceiling, not the request rate."""
        ctx = tracing.maybe_start_trace(sample=True)
        token = tracing.activate(ctx)
        try:
            for i in range(tracing.MAX_SPANS * 3):
                with tracing.trace_span("load.span", i=i):
                    pass
        finally:
            tracing.deactivate(token)
        spans = tracing.get_spans(
            max_spans=tracing.MAX_SPANS * 10, include_open=True
        )
        assert len(spans) <= tracing.MAX_SPANS
        # newest survived, oldest rolled off
        assert spans[-1]["attrs"]["i"] == tracing.MAX_SPANS * 3 - 1

    async def test_get_spans_since_and_limit_paginate(self):
        ctx = tracing.maybe_start_trace(sample=True)
        token = tracing.activate(ctx)
        try:
            for i in range(10):
                with tracing.trace_span("page.span", i=i):
                    time.sleep(0.002)  # distinct wall started_at stamps
        finally:
            tracing.deactivate(token)
        all_spans = tracing.get_spans(name="page.span", max_spans=100)
        assert len(all_spans) == 10
        # limit: newest N
        assert [
            s["attrs"]["i"] for s in tracing.get_spans(
                name="page.span", max_spans=3
            )
        ] == [7, 8, 9]
        # since: wall-clock cursor (inclusive)
        cut = all_spans[6]["started_at"]
        assert [
            s["attrs"]["i"]
            for s in tracing.get_spans(
                name="page.span", max_spans=100, since=cut
            )
        ] == [6, 7, 8, 9]


class TestSlowRequestLog:
    async def test_slow_request_logged_with_trace_id(
        self, obs_plane, monkeypatch, caplog
    ):
        server, controller, spawn_host, tmp_path = obs_plane
        await spawn_host("h1")
        await _deploy_obs_app(controller, tmp_path)
        monkeypatch.setenv("BIOENGINE_SLOW_REQUEST_MS", "50")
        tracing.reset_env_cache()
        handle = controller.get_handle("obs-app")
        import logging

        # bioengine loggers set propagate=False, so caplog's root
        # handler never sees them — attach its handler directly
        serving_logger = logging.getLogger("bioengine.serving")
        serving_logger.addHandler(caplog.handler)
        try:
            await handle.call("infer", n=1)  # sleeps 150 ms > 50 ms
        finally:
            serving_logger.removeHandler(caplog.handler)
        slow = [r for r in caplog.records if "slow_request" in r.message]
        assert slow, caplog.records
        msg = slow[-1].message
        assert re.search(r"trace_id=[0-9a-f]{32}", msg)
        assert "app=obs-app" in msg
        assert "deployment=obs_dep" in msg
        assert re.search(r"duration_ms=\d+", msg)


# ---------------------------------------------------------------------------
# stages: the engine's timeline, one measurement for every sink
# ---------------------------------------------------------------------------


def _stage_engine():
    import numpy as np

    from bioengine_tpu.runtime.engine import EngineConfig, InferenceEngine
    from bioengine_tpu.runtime.program_cache import CompiledProgramCache

    return InferenceEngine(
        "stage-toy",
        lambda params, x: x * params,
        np.float32(2.0),
        config=EngineConfig(max_tile=16, tile=8, tile_overlap=2, tile_batch=4),
        cache=CompiledProgramCache(),
    )


class TestStages:
    async def test_sampled_tree_holds_the_stages_under_engine_predict(self):
        """A sampled request's tree: engine.queue and engine.request
        under the caller's span, engine.predict inside the request,
        the stages of the chunks it rode (recorded on the stream's
        threads) under engine.predict, whose stage_seconds is their
        sums and whose chip_seconds is its share of the device time."""
        import numpy as np

        eng = _stage_engine()
        x = np.ones((1, 40, 40, 1), np.float32)
        try:
            await eng.predict_async(x)  # compile
            ctx = tracing.maybe_start_trace(sample=True)
            token = tracing.activate(ctx)
            try:
                with tracing.span("caller") as caller:
                    out = await eng.predict_async(x)
            finally:
                tracing.deactivate(token)
        finally:
            eng.close()
        assert float(out.sum()) == pytest.approx(2.0 * 40 * 40, rel=1e-3)
        tree = tracing.build_trace_tree(ctx.trace_id)
        (root,) = tree["tree"]
        assert root["span_id"] == caller["span_id"]
        assert [c["name"] for c in root["children"]] == [
            "engine.queue", "engine.request",
        ]
        (predict,) = root["children"][1]["children"]
        self._check_predict_span(predict, ctx)
        # alone, the request is billed every chunk whole
        assert predict["attrs"]["stage_seconds"]["compute"] == pytest.approx(
            eng.pipeline_stats.compute_seconds / 2, rel=0.9
        )
        # the same intervals stand on the timeline, sampled or not
        (on_timeline,) = tracing.get_stages(
            int(predict["started_at"] * 1e9) - 1000, name="engine.predict"
        )
        assert on_timeline["duration_s"] == pytest.approx(
            predict["duration_s"], abs=1e-6
        )

    @staticmethod
    def _check_predict_span(predict, ctx):
        assert predict["name"] == "engine.predict"
        own = {}
        for child in predict["children"]:
            assert child["trace_id"] == ctx.trace_id
            own[child["name"]] = own.get(child["name"], 0.0) + child["duration_s"]
        assert set(own) == {
            "engine.cut", "engine.put", "engine.dispatch",
            "engine.device_wait", "engine.d2h", "engine.stitch",
        }
        stage_seconds = predict["attrs"]["stage_seconds"]
        for name, seconds in own.items():
            assert stage_seconds[name.removeprefix("engine.")] == pytest.approx(
                seconds, abs=1e-5
            )
        assert stage_seconds["readback"] == pytest.approx(
            stage_seconds["device_wait"] + stage_seconds["d2h"], abs=2e-6
        )
        assert stage_seconds["compute"] > 0
        # billed the device time of its rows, not the time it was in hand
        assert predict["attrs"]["chip_seconds"] == pytest.approx(
            stage_seconds["compute"] * predict["attrs"]["devices"], abs=2e-6
        )
        assert predict["attrs"]["chip_seconds"] <= predict["duration_s"] + 1e-5

    async def test_two_sampled_requests_in_one_stream(self):
        """Two requests in hand at once, sharing a chunk: their
        engine.request spans overlap, each tree holds every stage of the
        chunks its request rode (the shared chunk's device stages in
        both), and the two bills sum to the device time."""
        import asyncio
        import threading

        import numpy as np

        eng = _stage_engine()
        # 9 tiles each in chunks of 4: 4 | 4 | 1 + 3 | 4 | 2
        images = [np.full((1, 16, 18, 1), v, np.float32) for v in (1.0, 3.0)]
        gate = threading.Event()
        sound = eng._stream._force

        def held(flight):  # the first read-back waits for both to enrol
            gate.wait(30)
            return sound(flight)

        eng._stream._force = held

        async def ask(x):
            ctx = tracing.maybe_start_trace(sample=True)
            token = tracing.activate(ctx)
            try:
                with tracing.span("caller"):
                    return ctx, await eng.predict_async(x)
            finally:
                tracing.deactivate(token)

        try:
            await eng.predict_async(images[0])  # compile
            before = eng.pipeline_stats.as_dict()
            busy = eng.pipeline_stats.compute_seconds
            since = time.time_ns()
            gate.clear()
            asks = [asyncio.ensure_future(ask(x)) for x in images]
            while len(eng._stream._pending) < 2 or eng._stream._cutter_busy:
                await asyncio.sleep(0.002)
            gate.set()
            (ctx_a, out_a), (ctx_b, out_b) = await asyncio.gather(*asks)
            after = eng.pipeline_stats.as_dict()
            busy = eng.pipeline_stats.compute_seconds - busy
        finally:
            gate.set()
            eng.close()
        assert float(out_a.mean()) == pytest.approx(2.0, rel=1e-3)
        assert float(out_b.mean()) == pytest.approx(6.0, rel=1e-3)
        assert after["chunks_shared"] - before["chunks_shared"] == 1
        bills, requests = [], []
        for ctx in (ctx_a, ctx_b):
            (root,) = tracing.build_trace_tree(ctx.trace_id)["tree"]
            assert [c["name"] for c in root["children"]] == [
                "engine.queue", "engine.request",
            ]
            request = root["children"][1]
            requests.append(request)
            (predict,) = request["children"]
            self._check_predict_span(predict, ctx)
            # three chunks each: the shared one is in both trees
            puts = [c for c in predict["children"] if c["name"] == "engine.put"]
            assert len(puts) == 3
            bills.append(predict["attrs"]["chip_seconds"])
        assert sum(bills) == pytest.approx(busy, abs=1e-5)
        a, b = requests
        assert b["started_at"] < a["started_at"] + a["duration_s"]
        # every name, thread prefix and counter the benchmark's readers
        # use is still there, with the requests overlapping
        threads = {}
        for s in tracing.get_stages(since):
            threads.setdefault(s["name"], set()).add(s["thread"].split("-")[0])
        for name in ("engine.queue", "engine.request", "engine.predict",
                     "engine.put", "engine.dispatch", "engine.device_wait",
                     "engine.d2h"):
            assert threads[name] == {"dispatch"}, name
        assert threads["engine.cut"] == {"pipeline"}
        assert threads["engine.stitch"] == {"pipeline", "dispatch"}
        for key in ("requests", "queue_seconds", "chunks", "chunks_shared",
                    "rows_executed", "rows_useful", "d2h_seconds"):
            assert key in after

    async def test_get_traces_returns_stages(self):
        from types import SimpleNamespace

        from bioengine_tpu.utils.permissions import create_context
        from bioengine_tpu.worker.worker import BioEngineWorker

        worker = SimpleNamespace(admin_users=["admin"])
        admin = create_context("admin", workspace="bioengine")
        since = time.time()
        with tracing.stage("verb.stage", bytes=7):
            pass
        with tracing.stage("verb.other"):
            pass
        with pytest.raises(PermissionError):
            BioEngineWorker.get_traces(
                worker, stages=True,
                context=create_context("anon", workspace="public"),
            )
        got = BioEngineWorker.get_traces(
            worker, stages=True, since=since, context=admin
        )
        assert [s["name"] for s in got][-2:] == ["verb.stage", "verb.other"]
        (stage,) = BioEngineWorker.get_traces(
            worker, stages=True, name="verb.stage", since=since, context=admin
        )
        # shaped like a span, with the thread and the request beside it
        assert {
            "name", "started_at", "duration_s", "attrs", "thread", "request_seq",
        } <= set(stage)
        assert stage["attrs"] == {"bytes": 7} and stage["started_at"] >= since
        assert len(
            BioEngineWorker.get_traces(worker, stages=True, limit=1, context=admin)
        ) == 1

    @pytest.mark.parametrize(
        "given, want", [({}, (1, 0)), ({"host_tracer_level": 2,
                                        "python_tracer_level": 1}, (2, 1))],
    )
    async def test_profiling_passes_profile_options(
        self, given, want, tmp_path, monkeypatch
    ):
        """An operator's trace holds the stage annotations (host level
        1), not a million Python events (Python tracer off)."""
        import jax

        from bioengine_tpu.utils import profiling

        seen = {}
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda d, profiler_options=None: seen.update(
                dir=d, options=profiler_options
            ),
        )
        trace_dir = profiling.start_trace(tmp_path, None, None, **given)
        assert seen["dir"] == trace_dir and Path(trace_dir).is_dir()
        options = seen["options"]
        assert (options.host_tracer_level, options.python_tracer_level) == want

    async def test_batcher_counts_its_waits_as_a_sum(self):
        from bioengine_tpu.serving import ContinuousBatcher

        def total():
            (series,) = metrics.collect()["batcher_queue_wait_seconds_total"][
                "series"
            ]
            return series["value"]

        async def double(signature, payloads):
            return [p * 2 for p in payloads]

        batcher = ContinuousBatcher(double, max_batch=4, max_wait_ms=20.0)
        # the counter sums the batchers alive at the scrape (ROADMAP
        # D16): an earlier test's batcher must not leave, and take its
        # seconds with it, between the two reads
        gc.collect()
        before, since = total(), time.time_ns()
        assert await asyncio.gather(
            batcher.submit("k", 1), batcher.submit("k", 2)
        ) == [2, 4]
        await batcher.close()
        waits = [
            s["duration_s"]
            for s in tracing.get_stages(since, name="runtime.batch_wait")
        ]
        assert len(waits) == 2 and min(waits) >= 0.015
        assert batcher.stats["queue_wait_seconds"] == pytest.approx(sum(waits))
        assert total() - before == pytest.approx(sum(waits), abs=2e-6)

    async def test_model_runner_stages_cover_the_host_work(self, tmp_path):
        """runtime.assemble / runtime.split on the loop, runtime.preprocess
        / runtime.postprocess on the request's own thread inside
        engine.request (not the one that talks to the device), their
        seconds in the engine's PipelineStats."""
        import importlib.util

        import jax
        import jax.numpy as jnp
        import numpy as np
        import yaml

        from bioengine_tpu.models.unet import UNet2D
        from bioengine_tpu.runtime.convert import save_params_npz

        package = tmp_path / "stage-unet"
        package.mkdir()
        x = np.random.default_rng(0).normal(size=(1, 64, 64, 1)).astype(np.float32)
        model = UNet2D(features=(4, 8), out_channels=1)
        save_params_npz(
            str(package / "weights.npz"),
            model.init(jax.random.key(0), jnp.asarray(x))["params"],
        )
        (package / "rdf.yaml").write_text(yaml.safe_dump({
            "type": "model", "name": "Stage UNet", "description": "stages",
            "inputs": [{"name": "input0", "axes": "byxc"}],
            "outputs": [{"name": "output0", "axes": "byxc"}],
            "weights": {"jax_params": {
                "source": "weights.npz",
                "architecture": {
                    "name": "unet2d",
                    "kwargs": {"features": [4, 8], "out_channels": 1},
                },
            }},
        }))
        spec = importlib.util.spec_from_file_location(
            "stage_mr_rt",
            Path(__file__).resolve().parent.parent
            / "apps" / "model-runner" / "runtime_deployment.py",
        )
        rt = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rt)
        dep = rt.RuntimeDeployment(batch_max=8, batch_wait_ms=5.0)
        await dep.async_init()
        try:
            await dep.predict(str(package), x)  # load and compile
            since = time.time_ns()
            reply = await dep.predict(str(package), x)
            (stats,) = dep.pipeline_stats().values()
        finally:
            await dep.close()
        assert reply["output0"].shape == x.shape
        stages = {s["name"]: s for s in tracing.get_stages(since)}
        assert {
            "runtime.batch_wait", "runtime.assemble", "engine.queue",
            "engine.request", "runtime.preprocess", "engine.predict",
            "runtime.postprocess", "runtime.split",
        } <= set(stages)
        request = stages["engine.request"]
        for name in ("runtime.preprocess", "engine.predict", "runtime.postprocess"):
            assert stages[name]["thread"] == request["thread"]
            assert request["start_ns"] <= stages[name]["start_ns"]
            assert stages[name]["end_ns"] <= request["end_ns"]
        assert request["thread"].startswith("dispatch-")
        # the host work on either side of the engine is off the one
        # thread that talks to the device
        assert stages["engine.dispatch"]["thread"].endswith("-device")
        assert stages["engine.dispatch"]["thread"] != request["thread"]
        loop_thread = stages["runtime.assemble"]["thread"]
        assert loop_thread == stages["runtime.split"]["thread"] != request["thread"]
        # in the order a request passes them
        order = [
            "runtime.batch_wait", "runtime.assemble", "engine.queue",
            "engine.request", "runtime.split",
        ]
        ends = [stages[n]["end_ns"] for n in order]
        assert ends == sorted(ends)
        assert stats["requests"] == 2 and stats["preprocess_seconds"] >= 0
        assert stats["rows_executed"] == stats["rows_useful"] == 2
