"""Flight recorder, incident bundles, chip-seconds accounting, and
on-demand profiling.

The integration layer rides the PR-4 in-process multi-host chaos
harness (real websockets, one event loop): kill a host mid-traffic,
then ``debug_bundle`` must hand back ONE time-ordered artifact holding
the breaker-trip and re-placement evidence from both hosts, the failed
request's trace tree, and a metrics snapshot — and a normal request's
trace root must carry a non-zero ``chip_seconds`` that agrees with the
engine span's device seconds x mesh width.
"""

import asyncio
import json
import time
from pathlib import Path

import pytest

from bioengine_tpu.apps.builder import AppBuilder
from bioengine_tpu.cluster.state import ClusterState
from bioengine_tpu.cluster.topology import TpuTopology
from bioengine_tpu.rpc.server import RpcServer
from bioengine_tpu.serving import (
    DeploymentSpec,
    ReplicaState,
    RequestOptions,
    ServeController,
)
from bioengine_tpu.serving.replica import CHIP_SECONDS
from bioengine_tpu.testing import faults
from bioengine_tpu.utils import flight, tracing
from bioengine_tpu.worker_host import WorkerHost

pytestmark = [pytest.mark.integration, pytest.mark.anyio]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(autouse=True)
def _clean_flight():
    flight.clear()
    flight.reset_env_cache()
    yield
    flight.clear()
    flight.reset_env_cache()


@pytest.fixture(autouse=True)
def _sample_everything(monkeypatch):
    monkeypatch.setenv("BIOENGINE_TRACE_SAMPLE", "1.0")
    tracing.reset_env_cache()
    tracing.clear_spans()
    yield
    tracing.reset_env_cache()


# ---------------------------------------------------------------------------
# recorder unit behavior
# ---------------------------------------------------------------------------


class TestRecorder:
    def test_ring_stays_bounded(self):
        cap = flight._events.maxlen
        for i in range(cap + 300):
            flight.record("test.event", i=i)
        events = flight.get_events(limit=None)
        assert len(events) == cap
        # oldest events rolled off, newest survived
        assert events[-1]["attrs"]["i"] == cap + 299
        assert events[0]["attrs"]["i"] == 300

    def test_seq_is_monotonic_and_recorder_stamped(self):
        a = flight.record("test.a")
        b = flight.record("test.b")
        assert b["seq"] == a["seq"] + 1
        assert a["recorder"] == flight.recorder_id()

    def test_disabled_via_env(self, monkeypatch):
        monkeypatch.setenv("BIOENGINE_FLIGHT", "0")
        flight.reset_env_cache()
        assert flight.record("test.event") is None
        assert flight.dump("nope") is None
        assert flight.get_events() == []

    def test_dump_snapshots_and_rate_limits(self, monkeypatch):
        monkeypatch.setenv("BIOENGINE_FLIGHT_DUMP_INTERVAL_S", "3600")
        flight.record("test.before", k=1)
        snap = flight.dump("unit_reason", extra="x")
        assert snap is not None
        assert snap["reason"] == "unit_reason"
        assert any(e["type"] == "test.before" for e in snap["events"])
        # same reason inside the interval: suppressed
        assert flight.dump("unit_reason") is None
        # a different reason is its own budget
        assert flight.dump("other_reason") is not None
        reasons = [d["reason"] for d in flight.get_dumps()]
        assert reasons == ["unit_reason", "other_reason"]
        # dump metadata (not full events) rides get_record
        record = flight.get_record()
        assert [d["reason"] for d in record["dumps"]] == reasons

    def test_dump_persists_to_dir(self, monkeypatch, tmp_path):
        monkeypatch.setenv("BIOENGINE_FLIGHT_DIR", str(tmp_path / "dumps"))
        flight.record("test.evidence")
        flight.dump("disk_reason")
        files = list(
            (tmp_path / "dumps").glob(
                f"flight-*disk_reason-{flight.recorder_id()}.json"
            )
        )
        assert len(files) == 1
        data = json.loads(files[0].read_text())
        assert data["reason"] == "disk_reason"
        assert any(e["type"] == "test.evidence" for e in data["events"])

    def test_get_record_limit_and_since(self):
        for i in range(10):
            flight.record("test.page", i=i)
        events = flight.get_events(limit=None)
        cut = events[6]["ts"]
        rec = flight.get_record(limit=3)
        assert [e["attrs"]["i"] for e in rec["events"]] == [7, 8, 9]
        rec = flight.get_record(limit=None, since=cut)
        assert [e["attrs"]["i"] for e in rec["events"]] == [6, 7, 8, 9]

    def test_merge_dedupes_and_time_orders(self):
        def evt(recorder, seq, ts):
            return {"recorder": recorder, "seq": seq, "ts": ts, "type": "t"}

        rec_a = {"events": [evt("aaa", 1, 10.0), evt("aaa", 2, 30.0)]}
        rec_b = {"events": [evt("bbb", 1, 20.0), evt("aaa", 2, 30.0)]}
        merged = flight.merge_records([rec_a, rec_b, rec_a])
        assert [(e["recorder"], e["seq"]) for e in merged] == [
            ("aaa", 1),
            ("bbb", 1),
            ("aaa", 2),
        ]


# ---------------------------------------------------------------------------
# chip-seconds accounting (local serving path, no RPC)
# ---------------------------------------------------------------------------


def _no_local_chips() -> ClusterState:
    return ClusterState(TpuTopology(chips=(), n_hosts=1, platform="cpu"))


def _engine_app_factory():
    import numpy as np

    from bioengine_tpu.runtime.engine import EngineConfig, InferenceEngine

    class EngineApp:
        async def async_init(self):
            # tiny tiles force the overlapped tiled pipeline on 40x40
            config = EngineConfig(
                max_tile=16, tile=8, tile_overlap=2, pipeline_depth=2
            )
            self.engine = InferenceEngine(
                model_id="flight-toy",
                apply_fn=lambda params, x: x * params,
                params=np.float32(3.0),
                config=config,
            )

        async def infer(self, size: int = 40):
            x = np.ones((1, size, size, 1), np.float32)
            y = await self.engine.predict_async(x)
            return float(np.asarray(y).sum())

        async def close(self):
            self.engine.close()

    return EngineApp


def _chip_counter_value(app_id: str) -> float:
    return sum(
        child.value
        for key, child in CHIP_SECONDS.items()
        if key[0] == app_id
    )


class TestChipSeconds:
    async def test_root_span_carries_chip_seconds_that_agree(self):
        controller = ServeController(_no_local_chips(), health_check_period=3600)
        try:
            await controller.deploy(
                "cost-app",
                [
                    DeploymentSpec(
                        name="entry", instance_factory=_engine_app_factory()
                    )
                ],
            )
            handle = controller.get_handle("cost-app")
            await handle.call("infer")  # warm: compile outside accounting asserts
            tracing.clear_spans()
            before = _chip_counter_value("cost-app")
            assert await handle.call("infer") == pytest.approx(
                3.0 * 40 * 40, rel=1e-3
            )

            (root,) = tracing.get_spans(name="request")
            cs_root = root["attrs"].get("chip_seconds")
            assert cs_root is not None and cs_root > 0
            engine_spans = tracing.get_spans(
                name="engine.predict", trace_id=root["trace_id"]
            )
            assert engine_spans
            # root chip_seconds == sum of engine spans' chip_seconds
            assert cs_root == pytest.approx(
                sum(s["attrs"]["chip_seconds"] for s in engine_spans),
                abs=1e-5,
            )
            # each engine span: chip_seconds = its share of the device
            # time x width, never more than the time it was in hand
            for s in engine_spans:
                assert s["attrs"]["devices"] == 1
                assert s["attrs"]["chip_seconds"] == pytest.approx(
                    s["attrs"]["stage_seconds"]["compute"]
                    * s["attrs"]["devices"], abs=2e-6,
                )
                assert 0 < s["attrs"]["chip_seconds"] <= s["duration_s"]

            # the always-on counter accumulated the same cost
            counted = _chip_counter_value("cost-app") - before
            assert counted == pytest.approx(cs_root, rel=0.25)

            # surfaces: per-app rollup + per-replica describe
            status = controller.get_app_status("cost-app")
            cost = status["cost"]
            assert cost["chip_seconds_total"] > 0
            assert "entry" in cost["by_deployment"]
            assert cost["by_deployment"]["entry"]["by_method"]["infer"] > 0
            (replica,) = controller.apps["cost-app"].replicas["entry"]
            assert replica.describe()["chip_seconds_total"] == pytest.approx(
                _chip_counter_value("cost-app"), abs=1e-6
            )
        finally:
            await controller.stop()

    async def test_unsampled_requests_still_account(self, monkeypatch):
        monkeypatch.setenv("BIOENGINE_TRACE_SAMPLE", "0.0")
        tracing.reset_env_cache()
        controller = ServeController(_no_local_chips(), health_check_period=3600)
        try:
            await controller.deploy(
                "cost-unsampled",
                [
                    DeploymentSpec(
                        name="entry", instance_factory=_engine_app_factory()
                    )
                ],
            )
            handle = controller.get_handle("cost-unsampled")
            await handle.call("infer")
            tracing.clear_spans()
            before = _chip_counter_value("cost-unsampled")
            await handle.call("infer")
            # no spans minted...
            assert tracing.get_spans(include_open=True) == []
            # ...but the cost was accounted exactly the same
            assert _chip_counter_value("cost-unsampled") - before > 0
        finally:
            await controller.stop()


# ---------------------------------------------------------------------------
# incident bundle: kill a host mid-traffic (PR-4 harness)
# ---------------------------------------------------------------------------

FLIGHT_MANIFEST = """\
name: Flight App
id: flight-app
id_emoji: "\U0001F6A8"
description: engine + idempotent arithmetic for incident tests
type: tpu-serve
version: 1.0.0
deployments:
  - flight_dep:FlightDep
authorized_users: ["*"]
deployment_config:
  flight_dep:
    num_replicas: 2
    min_replicas: 2
    max_replicas: 2
    chips: 2
    autoscale: false
"""

FLIGHT_SOURCE = '''\
import numpy as np

from bioengine_tpu.rpc import schema_method
from bioengine_tpu.runtime.engine import EngineConfig, InferenceEngine


class FlightDep:
    async def async_init(self):
        config = EngineConfig(
            max_tile=16, tile=8, tile_overlap=2, pipeline_depth=2
        )
        self.engine = InferenceEngine(
            model_id="flight-toy",
            apply_fn=lambda params, x: x * params,
            params=np.float32(3.0),
            config=config,
        )

    @schema_method
    async def infer(self, size: int = 40, context=None):
        """Engine prediction through the tiled pipeline."""
        x = np.ones((1, size, size, 1), np.float32)
        y = await self.engine.predict_async(x)
        return {"sum": float(np.asarray(y).sum())}

    @schema_method
    async def add(self, a: int, b: int, context=None):
        """Idempotent arithmetic for chaos traffic."""
        return {"sum": a + b}

    async def close(self):
        self.engine.close()
'''


def _write_flight_app(tmp_path: Path) -> Path:
    app_dir = tmp_path / "flight-src"
    app_dir.mkdir(exist_ok=True)
    (app_dir / "manifest.yaml").write_text(FLIGHT_MANIFEST)
    (app_dir / "flight_dep.py").write_text(FLIGHT_SOURCE)
    return app_dir


@pytest.fixture()
async def flight_plane(tmp_path):
    server = RpcServer(host="127.0.0.1", admin_users=["admin"])
    await server.start()
    token = server.issue_token("admin", is_admin=True)
    # breaker_threshold=2: the dead replica trips deterministically
    # within a handful of failed-over calls
    controller = ServeController(
        _no_local_chips(), health_check_period=3600, breaker_threshold=2
    )
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
            except Exception:  # noqa: BLE001 — killed hosts are already down
                pass
        await controller.stop()
        await server.stop()


async def _kill_host(host: WorkerHost) -> None:
    host.rejoin = False
    host.connection.auto_reconnect = False
    host.connection._closing = True
    await host.connection._abort_connection()


async def _deploy_flight_app(controller, tmp_path):
    builder = AppBuilder(workdir_root=tmp_path / "apps")
    built = builder.build(
        app_id="flight-app", local_path=_write_flight_app(tmp_path)
    )
    await controller.deploy("flight-app", built.specs)
    return controller.apps["flight-app"].replicas["flight_dep"]


class TestIncidentBundle:
    async def test_kill_host_mid_traffic_bundle_has_the_evidence(
        self, flight_plane
    ):
        """Acceptance: kill one of two hosts under idempotent traffic;
        ``debug_bundle`` returns one time-ordered artifact containing
        the breaker-trip and re-placement events (attributed to both
        hosts), the failed request's trace tree, and a metrics
        snapshot. A normal request's trace root carries non-zero
        chip_seconds agreeing with the engine's device time x mesh
        width."""
        server, controller, spawn_host, tmp_path = flight_plane
        h1 = await spawn_host("h1")
        h2 = await spawn_host("h2")
        replicas = await _deploy_flight_app(controller, tmp_path)
        assert sorted(r.host_id for r in replicas) == ["h1", "h2"]
        handle = controller.get_handle("flight-app")

        # -- the normal request: cost lands on the trace root ----------
        await handle.call("infer")  # warm both compile paths
        tracing.clear_spans()
        result = await handle.call("infer")
        assert result["sum"] == pytest.approx(3.0 * 40 * 40, rel=1e-3)
        (root,) = tracing.get_spans(name="request")
        cs_root = root["attrs"].get("chip_seconds")
        assert cs_root is not None and cs_root > 0
        engine_spans = tracing.get_spans(
            name="engine.predict", trace_id=root["trace_id"]
        )
        assert engine_spans
        assert cs_root == pytest.approx(
            sum(
                s["attrs"]["stage_seconds"]["compute"] * s["attrs"]["devices"]
                for s in engine_spans
            ),
            abs=1e-5,
        )
        assert cs_root <= sum(s["duration_s"] for s in engine_spans)

        # -- kill h1 mid-traffic ---------------------------------------
        opts = RequestOptions(idempotent=True, deadline_s=20, max_attempts=8)
        failures: list[Exception] = []
        kill_at = asyncio.Event()

        async def traffic(worker_id: int):
            for i in range(15):
                try:
                    r = await handle.call("add", worker_id, i, options=opts)
                    assert r["sum"] == worker_id + i
                except Exception as e:  # noqa: BLE001 — counted, not raised
                    failures.append(e)
                if i == 4 and worker_id == 0:
                    kill_at.set()
                await asyncio.sleep(0.005)

        tasks = [asyncio.create_task(traffic(w)) for w in range(4)]
        await asyncio.wait_for(kill_at.wait(), 10)
        await _kill_host(h1)

        # deterministic breaker evidence: the dead host's replica stays
        # routable until the breaker notices; sequential idempotent
        # calls round-robin onto it, fail over, and feed the breaker
        # past threshold (=2) before the health loop ever runs
        for i in range(20):
            r = await handle.call("add", 100, i, options=opts)
            assert r["sum"] == 100 + i
            if flight.get_events(types=["breaker.trip"]):
                break
        assert flight.get_events(types=["breaker.trip"]), (
            "breaker did not trip on the dead host's replica"
        )

        recovered = False
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            await controller.health_tick()
            reps = controller.apps["flight-app"].replicas["flight_dep"]
            routable = [
                r
                for r in reps
                if r.state in (ReplicaState.HEALTHY, ReplicaState.TESTING)
            ]
            if len(routable) == 2 and all(
                r.host_id == "h2" for r in routable
            ):
                recovered = True
                break
            await asyncio.sleep(0.1)
        await asyncio.gather(*tasks)
        assert failures == []
        assert recovered, "replica was not re-placed on the survivor"

        # -- the artifact ----------------------------------------------
        bundle = await controller.debug_bundle()
        json.dumps(bundle, default=str)  # one JSON artifact

        events = bundle["events"]
        assert events == sorted(
            events, key=lambda e: (e["ts"], e["recorder"], e["seq"])
        ), "bundle timeline is not time-ordered"
        by_type: dict[str, list] = {}
        for e in events:
            by_type.setdefault(e["type"], []).append(e)

        # breaker trip on the dead host's replica
        trips = by_type.get("breaker.trip", [])
        assert trips and any(t["attrs"]["host"] == "h1" for t in trips)
        # host death + re-placement on the survivor
        assert any(
            e["attrs"]["host"] == "h1" for e in by_type.get("host.dead", [])
        )
        placements = by_type.get("replica.place", [])
        assert any(p["attrs"]["host"] == "h2" for p in placements)
        # both hosts appear in the one merged timeline
        hosts_seen = {
            e["attrs"].get("host")
            for e in events
            if e["attrs"].get("host") is not None
        }
        assert {"h1", "h2"} <= hosts_seen
        # replica state transitions recorded (UNHEALTHY on trip)
        assert any(
            e["attrs"].get("to") == "UNHEALTHY"
            for e in by_type.get("replica.state", [])
        )

        # the failed request's trace tree: an errored attempt span with
        # a successful sibling under the same trace_id
        errored = [
            s
            for s in bundle["traces"]
            if s["name"] == "attempt" and "error" in s
        ]
        assert errored, "no failed attempt span in the bundle"
        tree = tracing.build_trace_tree(errored[0]["trace_id"])
        attempts = [
            n
            for n in _flatten(tree["tree"])
            if n["name"] == "attempt"
        ]
        assert len(attempts) >= 2
        assert any("error" not in a for a in attempts)

        # metrics snapshot + mesh/lease state rode along
        assert "request_e2e_seconds" in bundle["metrics"]
        assert "chip_seconds_total" in bundle["metrics"]
        assert bundle["cluster"]["hosts"]["h2"]["alive"] is True
        assert bundle["apps"]["flight-app"]["cost"]["chip_seconds_total"] > 0
        # the dead host is reported unreachable, the survivor gathered
        assert bundle["hosts"]["h1"]["reachable"] is False
        assert bundle["hosts"]["h2"]["reachable"] is True
        assert "metrics" in bundle["hosts"]["h2"]
        # fault-free run: the injected-fault channel stays quiet, but
        # the dumps that the breaker trip triggered are recorded
        assert any(d["reason"] == "breaker_trip" for d in bundle["dumps"])

    async def test_flight_record_verb_and_profiling_round_trip(
        self, flight_plane, tmp_path
    ):
        """The worker-host verbs the bundle/controller use:
        get_flight_record returns this-host events; start/stop
        profiling wraps jax.profiler and writes a trace; memory_profile
        returns device stats."""
        server, controller, spawn_host, tmp_path2 = flight_plane
        host = await spawn_host("h1")
        rec = await controller._call_host(
            host.service_id, "get_flight_record", limit=50
        )
        assert rec["host_id"] == "h1"
        assert rec["recorder"] == flight.recorder_id()  # in-process harness

        trace_dir = tmp_path / "host-trace"
        started = await controller._call_host(
            host.service_id, "start_profiling", trace_dir=str(trace_dir)
        )
        assert started["profiling"] is True
        with pytest.raises(Exception, match="already active"):
            await controller._call_host(host.service_id, "start_profiling")
        import jax.numpy as jnp

        _ = float(jnp.ones((32, 32)).sum())  # give the trace content
        stopped = await controller._call_host(
            host.service_id, "stop_profiling"
        )
        assert stopped["profiling"] is False
        assert stopped["trace_dir"] == str(trace_dir)
        assert any(trace_dir.rglob("*")), "profiler trace dir is empty"

        mem = await controller._call_host(host.service_id, "memory_profile")
        assert mem["host_id"] == "h1"
        assert mem["pprof_b64"]
        assert mem["devices"]


def _flatten(tree_nodes):
    out = []
    stack = list(tree_nodes)
    while stack:
        node = stack.pop()
        out.append(node)
        stack.extend(node["children"])
    return out
