"""End-to-end worker lifecycle + code executor tests.

Mirrors the reference's e2e tier (ref tests/end_to_end/test_worker.py,
test_code_executor.py) but hermetic: in-process control plane, local
artifact paths, no external servers.
"""

import asyncio
import base64

import cloudpickle
import pytest

from bioengine_tpu.utils.permissions import create_context
from bioengine_tpu.worker.code_executor import CodeExecutor
from bioengine_tpu.worker.worker import BioEngineWorker

pytestmark = [pytest.mark.end_to_end, pytest.mark.anyio]

ADMIN_CTX = create_context("admin", workspace="bioengine")
ANON_CTX = create_context("anonymous")

REPO_APPS = __import__("pathlib").Path(__file__).resolve().parent.parent / "apps"


# ---- code executor ----------------------------------------------------------


@pytest.fixture()
def executor():
    return CodeExecutor(admin_users=["admin"], default_timeout=60.0)


async def test_run_code_source_mode(executor):
    result = await executor.run_code(
        code="def main(x, y):\n    print('working')\n    return x + y\n",
        args=[2, 3],
        context=ADMIN_CTX,
    )
    assert result["status"] == "ok"
    assert result["result"] == 5
    assert "working" in result["stdout"]


async def test_run_code_named_function_and_async(executor):
    code = (
        "import asyncio\n"
        "async def compute(n):\n"
        "    await asyncio.sleep(0)\n"
        "    return n * 2\n"
        "def other():\n    return 'no'\n"
    )
    result = await executor.run_code(
        code=code, function_name="compute", args=[21], context=ADMIN_CTX
    )
    assert result["result"] == 42


async def test_run_code_pickle_mode(executor):
    def work(a, b=1):
        return {"sum": a + b}

    payload = base64.b64encode(cloudpickle.dumps(work)).decode()
    result = await executor.run_code(
        function=payload, mode="pickle", args=[4], kwargs={"b": 6},
        context=ADMIN_CTX,
    )
    assert result["result"] == {"sum": 10}


async def test_run_code_error_propagation(executor):
    result = await executor.run_code(
        code="def main():\n    raise ValueError('boom')\n", context=ADMIN_CTX
    )
    assert result["status"] == "error"
    assert "ValueError: boom" in result["error"]
    assert result["result"] is None


async def test_run_code_timeout(executor):
    result = await executor.run_code(
        code="import time\ndef main():\n    time.sleep(30)\n",
        timeout=1.0,
        context=ADMIN_CTX,
    )
    assert result["status"] == "timeout"


async def test_run_code_stream_callbacks(executor):
    lines: list[str] = []
    result = await executor.run_code(
        code=(
            "import sys\n"
            "def main():\n"
            "    print('out1')\n"
            "    print('err1', file=sys.stderr)\n"
            "    print('out2')\n"
        ),
        write_stdout=lines.append,
        write_stderr=lines.append,
        context=ADMIN_CTX,
    )
    assert result["status"] == "ok"
    joined = "".join(lines)
    assert "out1" in joined and "err1" in joined and "out2" in joined


async def test_run_code_env_vars(executor):
    result = await executor.run_code(
        code="import os\ndef main():\n    return os.environ['MY_FLAG']\n",
        remote_options={"env_vars": {"MY_FLAG": "on"}},
        context=ADMIN_CTX,
    )
    assert result["result"] == "on"


async def test_run_code_chips_refused_from_a_process_that_holds_them():
    """One process per chip: a worker that enumerated TPU chips through
    JAX holds all of them, so a chip subprocess placed locally would die
    in libtpu at backend init. It is refused up front with a typed
    error, and no lease is taken."""
    from bioengine_tpu.cluster.state import ClusterState
    from bioengine_tpu.cluster.topology import ChipInfo, TpuTopology
    from bioengine_tpu.worker.code_executor import ChipHeldError

    state = ClusterState(
        TpuTopology(
            chips=(ChipInfo(0, "tpu", "TPU v5 lite", 0),),
            n_hosts=1,
            platform="tpu",
        )
    )
    executor = CodeExecutor(admin_users=["admin"], cluster_state=state)
    with pytest.raises(ChipHeldError, match="one process at a time"):
        await executor.run_code(
            code="def main():\n    return 1\n",
            remote_options={"num_chips": 1},
            context=ADMIN_CTX,
        )
    assert state.free_chips() == 1
    # chip-free code is unaffected
    result = await executor.run_code(
        code="def main():\n    return 1\n", context=ADMIN_CTX
    )
    assert result["result"] == 1


async def test_run_code_requires_admin(executor):
    with pytest.raises(PermissionError):
        await executor.run_code(code="def main():\n    return 1\n", context=ANON_CTX)


# ---- worker __main__ arg parsing --------------------------------------------


def test_worker_arg_parsing():
    from bioengine_tpu.worker.__main__ import (
        create_parser,
        worker_kwargs_from_args,
    )

    args = create_parser().parse_args(
        [
            "--mode", "single-machine",
            "--admin-users", "alice", "bob",
            "--startup-applications", '[{"local_path": "apps/demo-app"}]',
            "--port", "1234",
        ]
    )
    kwargs = worker_kwargs_from_args(args)
    assert kwargs["admin_users"] == ["alice", "bob"]
    assert kwargs["startup_applications"] == [{"local_path": "apps/demo-app"}]
    assert kwargs["port"] == 1234


def test_worker_startup_app_json_validation():
    from bioengine_tpu.worker.__main__ import parse_startup_applications

    assert parse_startup_applications(None) == []
    assert parse_startup_applications('{"a": 1}') == [{"a": 1}]
    with pytest.raises(ValueError):
        parse_startup_applications('["not-a-dict"]')


# ---- full worker lifecycle --------------------------------------------------


@pytest.fixture()
async def worker(tmp_path):
    w = BioEngineWorker(
        mode="single-machine",
        workspace_dir=tmp_path / "ws",
        admin_users=["admin"],
        startup_applications=[{"local_path": str(REPO_APPS / "demo-app")}],
        monitoring_interval_seconds=0.2,
        log_file="off",
    )
    await w.start()
    try:
        yield w
    finally:
        if w.is_ready:
            await w.stop()


async def test_worker_status_shape(worker):
    status = worker.get_status(context=ADMIN_CTX)
    assert status["worker"]["ready"] is True
    assert status["worker"]["uptime_seconds"] >= 0
    assert status["cluster"]["ready"] is True
    assert status["cluster"]["topology"]["n_chips"] == 8
    assert len(status["applications"]) == 1
    (app_status,) = status["applications"].values()
    assert app_status["status"] == "RUNNING"
    assert app_status["name"] == "Demo App"
    assert "ping" in app_status["available_methods"]


async def test_worker_service_call_through_rpc(worker):
    """Call the startup app through the registered RPC service surface."""
    (app_id,) = worker.apps_manager.records
    result = await worker.server.call_service_method(
        f"bioengine/{app_id}", "echo", kwargs={"message": "hi"}
    )
    assert result["echo"] == "hi"


async def test_worker_run_code_service(worker):
    result = await worker.server.call_service_method(
        "bioengine/bioengine-worker",
        "run_code",
        kwargs={"code": "def main():\n    return 7\n"},
        caller=worker.server._tokens[worker.server.issue_token("admin")],
    )
    assert result["result"] == 7


async def test_worker_monitoring_recovers_and_counts_errors(worker):
    await asyncio.sleep(0.5)  # a few monitor ticks
    assert worker._monitor_errors == 0
    assert worker.is_ready


async def test_worker_deploy_and_stop_app(worker, tmp_path):
    result = await worker.apps_manager.deploy_app(
        local_path=str(REPO_APPS / "demo-app"),
        deployment_kwargs={"demo_deployment": {"greeting": "Yo"}},
        context=ADMIN_CTX,
    )
    app_id = result["app_id"]
    echo = await worker.server.call_service_method(
        f"bioengine/{app_id}", "echo", kwargs={"message": "x"}
    )
    assert echo["greeting"] == "Yo"
    await worker.apps_manager.stop_app(app_id, context=ADMIN_CTX)
    assert app_id not in worker.apps_manager.records


async def test_worker_get_logs_requires_admin(worker):
    with pytest.raises(PermissionError):
        worker.get_logs(context=ANON_CTX)
    logs = worker.get_logs(context=ADMIN_CTX)
    assert isinstance(logs, dict)


async def test_run_code_huge_output_line(executor):
    result = await executor.run_code(
        code="def main():\n    print('x' * 200000)\n    return 1\n",
        context=ADMIN_CTX,
    )
    assert result["status"] == "ok"
    assert result["result"] == 1
    assert len(result["stdout"]) >= 200000


async def test_run_code_toplevel_exit_is_contained(executor):
    """Top-level code (incl. sys.exit) runs in the subprocess, never in
    the worker process."""
    result = await executor.run_code(
        code="import sys\nsys.exit(3)\ndef main():\n    return 1\n",
        context=ADMIN_CTX,
    )
    assert result["status"] == "error"
    assert "SystemExit" in result["error"]


async def test_stop_worker_over_websocket(tmp_path):
    """A remote stop_worker call must get its response before teardown."""
    from bioengine_tpu.rpc.client import connect_to_server

    w = BioEngineWorker(
        mode="single-machine",
        workspace_dir=tmp_path / "ws3",
        admin_users=["admin"],
        monitoring_interval_seconds=5.0,
        log_file="off",
    )
    await w.start()
    token = w.server.issue_token("admin")
    conn = await connect_to_server({"server_url": w.server.url, "token": token})
    svc = await conn.get_service("bioengine-worker")
    result = await asyncio.wait_for(svc.stop_worker(), timeout=10.0)
    assert result["status"] == "stopping"
    await conn.disconnect()
    await asyncio.wait_for(w._stop_event.wait(), timeout=10.0)
    assert not w.is_ready


async def test_worker_graceful_stop(tmp_path):
    w = BioEngineWorker(
        mode="single-machine",
        workspace_dir=tmp_path / "ws2",
        admin_users=["admin"],
        monitoring_interval_seconds=5.0,
        log_file="off",
    )
    await w.start()
    assert w.is_ready
    await w.stop()
    assert not w.is_ready
    # lock released: a second worker can start in the same workspace
    w2 = BioEngineWorker(
        mode="single-machine",
        workspace_dir=tmp_path / "ws2",
        admin_users=["admin"],
        log_file="off",
    )
    await w2.start()
    assert w2.is_ready
    await w2.stop()


async def test_worker_restart_recovers_apps(tmp_path):
    """App records persist in the workspace and a new worker on the same
    workspace re-adopts them — ref bioengine/apps/manager.py:841-935
    (VERDICT r3 missing #3)."""
    ws = tmp_path / "ws-recover"
    w = BioEngineWorker(
        mode="single-machine",
        workspace_dir=ws,
        admin_users=["admin"],
        monitoring_interval_seconds=5.0,
        log_file="off",
    )
    await w.start()
    result = await w.apps_manager.deploy_app(
        local_path=str(REPO_APPS / "demo-app"),
        app_id="persist-me",
        deployment_kwargs={"demo_deployment": {"greeting": "Back"}},
        context=ADMIN_CTX,
    )
    assert result["app_id"] == "persist-me"
    await w.stop()  # graceful stop keeps the persisted records

    w2 = BioEngineWorker(
        mode="single-machine",
        workspace_dir=ws,
        admin_users=["admin"],
        monitoring_interval_seconds=5.0,
        log_file="off",
    )
    await w2.start()
    try:
        assert "persist-me" in w2.apps_manager.records
        echo = await w2.server.call_service_method(
            "bioengine/persist-me", "echo", kwargs={"message": "again"}
        )
        assert echo["echo"] == "again"
        assert echo["greeting"] == "Back"
    finally:
        await w2.stop()


async def test_worker_restart_after_explicit_stop_forgets_apps(tmp_path):
    """An admin's explicit stop_app erases the record — only worker
    shutdown preserves deployment intent."""
    ws = tmp_path / "ws-forget"
    w = BioEngineWorker(
        mode="single-machine",
        workspace_dir=ws,
        admin_users=["admin"],
        monitoring_interval_seconds=5.0,
        log_file="off",
    )
    await w.start()
    await w.apps_manager.deploy_app(
        local_path=str(REPO_APPS / "demo-app"),
        app_id="forget-me",
        context=ADMIN_CTX,
    )
    await w.apps_manager.stop_app("forget-me", context=ADMIN_CTX)
    await w.stop()

    w2 = BioEngineWorker(
        mode="single-machine",
        workspace_dir=ws,
        admin_users=["admin"],
        monitoring_interval_seconds=5.0,
        log_file="off",
    )
    await w2.start()
    try:
        assert "forget-me" not in w2.apps_manager.records
    finally:
        await w2.stop()


async def test_worker_profiling_service(worker, tmp_path):
    """jax.profiler surface (SURVEY §5.1): trace start/stop writes
    artifacts; memory_profile returns pprof bytes + device stats."""
    trace_dir = tmp_path / "trace"
    with pytest.raises(PermissionError):
        worker.start_profiling(context=ANON_CTX)
    started = worker.start_profiling(
        trace_dir=str(trace_dir), context=ADMIN_CTX
    )
    assert started["profiling"] is True
    with pytest.raises(RuntimeError, match="already active"):
        worker.start_profiling(context=ADMIN_CTX)
    # do some device work so the trace has content
    import jax.numpy as jnp

    _ = float(jnp.ones((64, 64)).sum())
    stopped = worker.stop_profiling(context=ADMIN_CTX)
    assert stopped["trace_dir"] == str(trace_dir)
    assert any(trace_dir.rglob("*")), "trace dir is empty"
    with pytest.raises(RuntimeError, match="not active"):
        worker.stop_profiling(context=ADMIN_CTX)

    mem = worker.memory_profile(context=ADMIN_CTX)
    import base64

    assert len(base64.b64decode(mem["pprof_b64"])) > 0
    assert mem["devices"]


async def test_worker_profile_replica_routes_to_local(worker, tmp_path):
    """PR 7: profile ONE replica of a live deployment — local placement
    routes to this process's jax.profiler; the response names the
    replica that was profiled."""
    (app_id,) = worker.apps_manager.records
    with pytest.raises(PermissionError):
        await worker.profile_replica(app_id, context=ANON_CTX)
    with pytest.raises(ValueError, match="start|stop|memory"):
        await worker.profile_replica(
            app_id, action="bogus", context=ADMIN_CTX
        )
    trace_dir = tmp_path / "replica-trace"
    started = await worker.profile_replica(
        app_id, trace_dir=str(trace_dir), context=ADMIN_CTX
    )
    assert started["profiling"] is True
    assert started["host_id"] == "local"
    assert started["app_id"] == app_id
    assert started["replica_id"]
    stopped = await worker.profile_replica(
        app_id, action="stop", context=ADMIN_CTX
    )
    assert stopped["profiling"] is False
    assert any(trace_dir.rglob("*")), "trace dir is empty"
    mem = await worker.profile_replica(
        app_id, action="memory", context=ADMIN_CTX
    )
    assert mem["devices"]
    with pytest.raises(KeyError):
        await worker.profile_replica(
            app_id, replica_id="nope", context=ADMIN_CTX
        )


async def test_worker_flight_and_bundle_verbs(worker):
    """PR 7: get_flight_record (paginated) + debug_bundle return the
    incident surfaces over the worker service, admin-gated."""
    from bioengine_tpu.utils import flight

    with pytest.raises(PermissionError):
        worker.get_flight_record(context=ANON_CTX)
    flight.record("test.worker_verb", marker=1)
    record = worker.get_flight_record(limit=500, context=ADMIN_CTX)
    assert record["recorder"] == flight.recorder_id()
    assert any(
        e["type"] == "test.worker_verb" for e in record["events"]
    )
    # the startup sequence itself left evidence (replica placement)
    assert any(
        e["type"] == "replica.place" for e in record["events"]
    )
    # since-cursor pagination: nothing is older than now
    import time as _time

    assert (
        worker.get_flight_record(since=_time.time() + 60, context=ADMIN_CTX)[
            "events"
        ]
        == []
    )

    with pytest.raises(PermissionError):
        await worker.debug_bundle(context=ANON_CTX)
    bundle = await worker.debug_bundle(context=ADMIN_CTX)
    for key in (
        "events", "traces", "metrics", "cluster", "apps", "hosts", "worker",
    ):
        assert key in bundle, key
    assert bundle["worker"]["ready"] is True
    assert bundle["apps"], "deployed app missing from bundle"
    (app_status,) = bundle["apps"].values()
    assert "cost" in app_status


async def test_worker_get_traces_pagination(worker):
    """PR 7 satellite: get_traces limit/since — repeated pulls never
    re-ship the whole buffer."""
    from bioengine_tpu.utils import tracing

    tracing.clear_spans()
    for i in range(8):
        with tracing.span("verb.span", i=i):
            __import__("time").sleep(0.002)
    spans = worker.get_traces(
        name="verb.span", limit=3, context=ADMIN_CTX
    )
    assert [s["attrs"]["i"] for s in spans] == [5, 6, 7]
    cursor = worker.get_traces(name="verb.span", max_spans=100, context=ADMIN_CTX)[
        4
    ]["started_at"]
    newer = worker.get_traces(
        name="verb.span", max_spans=100, since=cursor, context=ADMIN_CTX
    )
    assert [s["attrs"]["i"] for s in newer] == [4, 5, 6, 7]


async def test_worker_dashboard_served(worker):
    """The built-in dashboard is served at /apps/_dashboard/ and its
    data endpoints (get_status via the bridge, /services) respond."""
    import aiohttp

    base = f"http://{worker.server.host}:{worker.server.port}"
    async with aiohttp.ClientSession() as http:
        async with http.get(f"{base}/apps/_dashboard/") as r:
            assert r.status == 200
            page = await r.text()
        assert "Worker Dashboard" in page
        async with http.post(
            f"{base}/call/bioengine-worker/get_status", json={}
        ) as r:
            status = (await r.json())["result"]
            assert status["worker"]["ready"] is True
            assert status["applications"]
        async with http.get(f"{base}/services") as r:
            services = await r.json()
            assert any(s["type"] == "bioengine-worker" for s in services)
