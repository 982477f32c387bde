"""Cold-start elimination: shared compile-cache tier, streamed weight
loading, and the preemption-tolerant warm pool.

Covers the three coordinated pieces end to end:
- utils/compile_cache.py — loud failure and the tier entry
  file protocol (list/read/atomic-write, unsafe names rejected);
- serving/compile_tier.py + worker_host sync — hosts publish compiled
  programs at join/replica-start and a later host FETCHES them, with
  ``program.cache_fetch`` flight evidence;
- runtime/program_cache.py — persistent-cache hits tagged apart from
  real compiles (``cache_hit`` on the program.compile flight event and
  in engine.describe()["programs"]);
- runtime/weight_stream.py + model-runner — manifest-driven streamed
  loading with BIT-IDENTICAL outputs vs eager, transparent fallback
  when no manifest exists, loud failure on a layout mismatch;
- serving/warm_pool.py + controller — pool fill/promote/refill/sweep,
  and the acceptance chaos test: a preempted host's replica is absorbed
  by a standby within the request deadline, zero failed idempotent
  requests, exact chip accounting, and ``warmpool.promote`` sits
  between ``host.dead`` and ``replica.place`` in the flight record.
"""

import asyncio
import importlib.util
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bioengine_tpu.utils import compile_cache, flight

pytestmark = [pytest.mark.integration, pytest.mark.anyio]

REPO_APPS = Path(__file__).resolve().parent.parent / "apps"


def _load_model_runner():
    spec = importlib.util.spec_from_file_location(
        "cold_start_mr_rt", REPO_APPS / "model-runner" / "runtime_deployment.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _make_package(root: Path, with_manifest: bool = True) -> Path:
    """Tiny jax_params UNet package (model-runner layout), optionally
    with the key→shape streaming manifest."""
    import jax
    import jax.numpy as jnp
    import yaml

    from bioengine_tpu.models.unet import UNet2D
    from bioengine_tpu.runtime.convert import flatten_params, save_params_npz
    from bioengine_tpu.runtime.weight_stream import write_manifest

    d = root / ("pkg-manifest" if with_manifest else "pkg-plain")
    d.mkdir(parents=True, exist_ok=True)
    model = UNet2D(features=(4, 8), out_channels=1)
    x = np.random.default_rng(0).normal(size=(1, 64, 64, 1)).astype(np.float32)
    params = model.init(jax.random.key(0), jnp.asarray(x))["params"]
    save_params_npz(str(d / "weights.npz"), params)
    if with_manifest:
        write_manifest(d / "weights.npz", flatten_params(params))
    np.save(d / "test_input.npy", x)
    (d / "rdf.yaml").write_text(
        yaml.safe_dump(
            {
                "type": "model",
                "name": "ColdStart Test UNet",
                "description": "cold-start test model",
                "inputs": [{"name": "input0", "axes": "byxc"}],
                "outputs": [{"name": "output0", "axes": "byxc"}],
                "test_inputs": ["test_input.npy"],
                "documentation": "README.md",
                "weights": {
                    "jax_params": {
                        "source": "weights.npz",
                        "architecture": {
                            "name": "unet2d",
                            "kwargs": {"features": [4, 8], "out_channels": 1},
                        },
                    }
                },
            }
        )
    )
    (d / "README.md").write_text("docs")
    return d


# ---------------------------------------------------------------------------
# compile_cache: loud failure + tier entry file protocol
# ---------------------------------------------------------------------------


class TestCompileCache:
    def test_unusable_directory_raises(self, tmp_path, monkeypatch):
        """A cache that cannot be enabled is an error, not a warning: a
        worker that recompiles everything on every start must not come
        up looking healthy."""
        blocker = tmp_path / "a-file"
        blocker.write_text("not a directory")
        monkeypatch.setenv(
            "JAX_COMPILATION_CACHE_DIR", str(blocker / "sub" / "dir")
        )
        compile_cache.reset_for_tests()
        try:
            with pytest.raises(OSError):
                compile_cache.enable_persistent_compilation_cache()
            assert compile_cache.enabled_dir() is None
        finally:
            compile_cache.reset_for_tests()

    def test_entry_io_roundtrip_and_safety(self, tmp_path):
        d = tmp_path / "cache"
        d.mkdir()
        name = "jit_fn-abc123-cache"
        assert compile_cache.write_entry(name, b"program-bytes", d)
        # idempotent: an existing entry is never overwritten
        assert not compile_cache.write_entry(name, b"other", d)
        assert compile_cache.read_entry(name, d) == b"program-bytes"
        assert compile_cache.list_entries(d) == {name: 13}
        # atime bookkeeping files and foreign files never list
        (d / "jit_fn-abc123-atime").write_bytes(b"x")
        (d / "random.txt").write_bytes(b"x")
        assert list(compile_cache.list_entries(d)) == [name]
        # names cross the RPC plane: traversal/dotfiles/suffix rejected
        for bad in ("../evil-cache", "a/b-cache", ".hidden-cache", "x"):
            assert not compile_cache.write_entry(bad, b"x", d)
            assert compile_cache.read_entry(bad, d) is None


class TestCompileTierStore:
    def test_publish_fetch_list_stats(self, tmp_path):
        from bioengine_tpu.serving.compile_tier import CompileCacheTier

        tier = CompileCacheTier(tmp_path / "tier", max_bytes=10_000)
        assert tier.fetch("jit_a-1-cache") is None  # miss counted
        assert tier.publish("jit_a-1-cache", b"A" * 100)
        assert not tier.publish("jit_a-1-cache", b"B" * 100)  # first copy kept
        assert tier.fetch("jit_a-1-cache") == b"A" * 100
        assert tier.list() == {"jit_a-1-cache": 100}
        st = tier.stats()
        assert st["entries"] == 1
        assert st["served"] == 1 and st["missed"] == 1
        assert st["hit_rate"] == 0.5
        assert not tier.publish("../evil-cache", b"x")

    def test_size_bound_evicts_lru(self, tmp_path):
        from bioengine_tpu.serving.compile_tier import CompileCacheTier

        tier = CompileCacheTier(tmp_path / "tier", max_bytes=250)
        tier.publish("jit_a-1-cache", b"A" * 100)
        time.sleep(0.02)
        tier.publish("jit_b-2-cache", b"B" * 100)
        time.sleep(0.02)
        tier.publish("jit_c-3-cache", b"C" * 100)  # 300 bytes > 250
        listing = tier.list()
        assert sum(listing.values()) <= 250
        assert "jit_c-3-cache" in listing  # newest survives
        assert tier.stats()["evicted"] >= 1


# ---------------------------------------------------------------------------
# program cache: persistent-hit tagging
# ---------------------------------------------------------------------------


class TestCacheHitTagging:
    def test_fast_build_with_persistent_cache_tags_hit(self, monkeypatch):
        from bioengine_tpu.runtime.program_cache import CompiledProgramCache

        monkeypatch.setattr(compile_cache, "_enabled_dir", "/tmp/fake-cache")
        monkeypatch.setenv("BIOENGINE_COMPILE_HIT_THRESHOLD_S", "10")
        flight.clear()
        cache = CompiledProgramCache()
        cache.get_or_compile(("m", 1), lambda: (lambda *a: None))
        assert cache.stats.persistent_hits == 1
        info = cache.compile_info_snapshot()
        assert info[str(("m", 1))]["cache_hit"] is True
        events = [
            e
            for e in flight.get_record()["events"]
            if e["type"] == "program.compile"
        ]
        assert events and events[-1]["attrs"]["cache_hit"] is True

    def test_no_persistent_cache_means_no_hit_tag(self, monkeypatch):
        from bioengine_tpu.runtime.program_cache import CompiledProgramCache

        monkeypatch.setattr(compile_cache, "_enabled_dir", None)
        monkeypatch.setenv("BIOENGINE_COMPILE_HIT_THRESHOLD_S", "10")
        cache = CompiledProgramCache()
        cache.get_or_compile(("m", 1), lambda: (lambda *a: None))
        assert cache.stats.persistent_hits == 0
        assert cache.compile_info_snapshot()[str(("m", 1))]["cache_hit"] is False

    def test_slow_build_is_a_real_compile(self, monkeypatch):
        from bioengine_tpu.runtime.program_cache import CompiledProgramCache

        monkeypatch.setattr(compile_cache, "_enabled_dir", "/tmp/fake-cache")
        monkeypatch.setenv("BIOENGINE_COMPILE_HIT_THRESHOLD_S", "0.01")

        def build():
            time.sleep(0.05)
            return lambda *a: None

        cache = CompiledProgramCache()
        cache.get_or_compile(("m", 2), build)
        assert cache.stats.persistent_hits == 0


# ---------------------------------------------------------------------------
# streamed weight loading
# ---------------------------------------------------------------------------


class TestWeightStreaming:
    def test_streamed_outputs_bit_identical_to_eager(self, tmp_path, monkeypatch):
        rt = _load_model_runner()
        pkg = _make_package(tmp_path, with_manifest=True)
        x = np.load(pkg / "test_input.npy")
        streamed = rt.Pipeline(pkg)
        assert streamed.load_info["streamed"] is True
        y_streamed = streamed.predict(x)["output0"]
        monkeypatch.setenv("BIOENGINE_WEIGHT_STREAMING", "0")
        eager = rt.Pipeline(pkg)
        assert eager.load_info["streamed"] is False
        y_eager = eager.predict(x)["output0"]
        # parity pin: same checkpoint, same programs — BIT identical
        assert np.array_equal(y_streamed, y_eager)
        info = streamed.cold_start_info()
        assert info["stream_done"] is True
        assert info["bytes_loaded"] > 0
        streamed.close()
        eager.close()

    def test_missing_manifest_falls_back_to_eager(self, tmp_path):
        rt = _load_model_runner()
        pkg = _make_package(tmp_path, with_manifest=False)
        x = np.load(pkg / "test_input.npy")
        p = rt.Pipeline(pkg)
        assert p.load_info["streamed"] is False
        assert p.predict(x)["output0"].shape == (1, 64, 64, 1)
        p.close()

    def test_manifest_shape_mismatch_fails_loudly(self, tmp_path):
        rt = _load_model_runner()
        pkg = _make_package(tmp_path, with_manifest=True)
        mpath = pkg / "weights.npz.manifest.json"
        manifest = json.loads(mpath.read_text())
        key = next(iter(manifest))
        manifest[key]["shape"] = [
            int(d) + 1 for d in manifest[key]["shape"]
        ]
        mpath.write_text(json.dumps(manifest))
        x = np.load(pkg / "test_input.npy")
        p = rt.Pipeline(pkg)
        with pytest.raises(RuntimeError, match="stream"):
            p.predict(x)
        p.close()

    def test_engine_gate_blocks_until_complete(self):
        import jax
        import jax.numpy as jnp

        from bioengine_tpu.models.unet import UNet2D
        from bioengine_tpu.runtime.engine import EngineConfig, InferenceEngine
        from bioengine_tpu.runtime.program_cache import CompiledProgramCache

        model = UNet2D(features=(4, 8), out_channels=1)
        x = np.random.default_rng(1).normal(size=(1, 64, 64, 1)).astype(
            np.float32
        )
        params = model.init(jax.random.key(0), jnp.asarray(x))["params"]
        zeros = jax.tree.map(np.zeros_like, params)

        eager = InferenceEngine(
            "gate-eager",
            lambda p, t: model.apply({"params": p}, t),
            params,
            divisor=model.divisor,
            config=EngineConfig(max_tile=64),
            cache=CompiledProgramCache(),
        )
        streamed = InferenceEngine(
            "gate-streamed",
            lambda p, t: model.apply({"params": p}, t),
            zeros,
            divisor=model.divisor,
            config=EngineConfig(max_tile=64),
            cache=CompiledProgramCache(),
        )
        streamed.begin_param_streaming()
        assert not streamed.params_resident
        # complete on a timer thread while predict blocks on the gate
        import threading

        threading.Timer(
            0.15, streamed.complete_param_streaming, args=(params,)
        ).start()
        t0 = time.perf_counter()
        y_streamed = streamed.predict(x)
        assert time.perf_counter() - t0 >= 0.1  # it actually waited
        assert streamed.params_resident
        y_eager = eager.predict(x)
        assert np.array_equal(y_streamed, y_eager)
        d = streamed.describe()
        assert d["params_resident"] is True
        eager.close()
        streamed.close()

    def test_loader_error_surfaces_on_predict(self):
        import jax
        import jax.numpy as jnp

        from bioengine_tpu.models.unet import UNet2D
        from bioengine_tpu.runtime.engine import EngineConfig, InferenceEngine
        from bioengine_tpu.runtime.program_cache import CompiledProgramCache

        model = UNet2D(features=(4, 8), out_channels=1)
        x = np.zeros((1, 64, 64, 1), np.float32)
        params = model.init(jax.random.key(0), jnp.asarray(x))["params"]
        engine = InferenceEngine(
            "gate-error",
            lambda p, t: model.apply({"params": p}, t),
            params,
            divisor=model.divisor,
            config=EngineConfig(max_tile=64),
            cache=CompiledProgramCache(),
        )
        engine.begin_param_streaming()
        engine.fail_param_streaming(ValueError("manifest mismatch"))
        with pytest.raises(RuntimeError, match="manifest mismatch"):
            engine.predict(x)
        engine.close()

    def test_manifest_helpers(self, tmp_path):
        from bioengine_tpu.runtime.weight_stream import (
            group_keys,
            load_manifest,
            manifest_path_for,
            skeleton_from_manifest,
            write_manifest,
        )

        weights = tmp_path / "w.npz"
        flat = {
            "enc/conv/kernel": np.zeros((3, 3, 1, 4), np.float32),
            "enc/conv/bias": np.zeros((4,), np.float16),
            "dec/out": np.zeros((4, 1), np.float32),
        }
        np.savez(weights, **flat)
        p = write_manifest(weights, flat)
        assert p == manifest_path_for(weights)
        manifest = load_manifest(weights)
        assert manifest == {
            "enc/conv/kernel": {"shape": [3, 3, 1, 4], "dtype": "float32"},
            "enc/conv/bias": {"shape": [4], "dtype": "float16"},
            "dec/out": {"shape": [4, 1], "dtype": "float32"},
        }
        assert sorted(group_keys(manifest)) == ["dec", "enc"]
        skel = skeleton_from_manifest(manifest)
        assert skel["enc"]["conv"]["kernel"].shape == (3, 3, 1, 4)
        # the skeleton carries the checkpoint's dtypes — a wrong-dtype
        # skeleton would warm executables the real params retrace past
        assert skel["enc"]["conv"]["bias"].dtype == np.float16
        # legacy shape-only manifests (the PR 3 committed fixtures'
        # format) normalize with dtype float32
        legacy = tmp_path / "legacy.npz"
        np.savez(legacy, **{"a/b": np.zeros((2, 2), np.float32)})
        (tmp_path / "legacy.npz.manifest.json").write_text(
            json.dumps({"a/b": [2, 2]})
        )
        assert load_manifest(legacy) == {
            "a/b": {"shape": [2, 2], "dtype": "float32"}
        }
        # absent manifest → None (the eager-fallback trigger)
        assert load_manifest(tmp_path / "other.npz") is None


# ---------------------------------------------------------------------------
# warm pool: fill / promote / refill / sweep, and status surfaces
# ---------------------------------------------------------------------------


class PingApp:
    async def async_init(self):
        pass

    async def ping(self):
        return "ok"


def _warm_spec(size=1, refill=True, name="e"):
    from bioengine_tpu.serving import DeploymentSpec, WarmPoolConfig

    return DeploymentSpec(
        name=name,
        instance_factory=PingApp,
        num_replicas=1,
        max_replicas=4,
        autoscale=False,
        warm_pool=WarmPoolConfig(size=size, refill=refill),
    )


async def _wait_for(predicate, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        await asyncio.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


class TestWarmPool:
    async def test_deploy_fills_pool_and_scale_up_promotes(self):
        from bioengine_tpu.cluster.state import ClusterState
        from bioengine_tpu.serving import ServeController

        flight.clear()
        controller = ServeController(ClusterState(), health_check_period=3600)
        spec = _warm_spec(size=1)
        app = await controller.deploy("wp", [spec])
        pool = controller._warm_pools[("wp", "e")]
        assert len(app.replicas["e"]) == 1            # serving set
        assert len(pool.standbys) == 1                # standby OUT of it
        standby_id = pool.standbys[0].replica_id
        status = controller.get_app_status("wp")
        cold = status["deployments"]["e"]["cold_start"]
        assert cold["warm_pool"]["occupancy"] == 1
        assert cold["warm_pool"]["promotions"] == 0

        # scale-up: the standby is PROMOTED, not cold-started
        promoted = await controller._add_replica(app, spec)
        assert promoted.replica_id == standby_id
        assert promoted.promoted_from_warm_pool is True
        assert promoted in app.replicas["e"]
        assert "standby_seconds" in promoted.ttfr
        # a promoted replica serves immediately and records its TTFR
        assert await promoted.call("ping") == "ok"
        assert promoted.ttfr["ttfr_seconds"] < 1.0
        types = [e["type"] for e in flight.get_record()["events"]]
        assert "warmpool.fill" in types
        assert "warmpool.promote" in types
        assert "replica.first_request" in types
        # background refill restores the pool
        await _wait_for(
            lambda: len(pool.standbys) == 1, msg="warm-pool refill"
        )
        status = controller.get_app_status("wp")
        cold = status["deployments"]["e"]["cold_start"]
        assert cold["warm_pool"]["promotions"] == 1
        assert cold["last_replica_ttfr"]["promoted_from_warm_pool"] is True
        await controller.stop()
        assert controller._warm_pools == {}

    async def test_unhealthy_replica_restart_promotes_standby(self):
        from bioengine_tpu.cluster.state import ClusterState
        from bioengine_tpu.serving import ReplicaState, ServeController

        flight.clear()
        controller = ServeController(ClusterState(), health_check_period=3600)
        spec = _warm_spec(size=1, refill=False)
        app = await controller.deploy("wp2", [spec])
        pool = controller._warm_pools[("wp2", "e")]
        standby_id = pool.standbys[0].replica_id
        victim = app.replicas["e"][0]
        victim.state = ReplicaState.UNHEALTHY
        await controller.health_tick()
        ids = [r.replica_id for r in app.replicas["e"]]
        assert standby_id in ids and victim.replica_id not in ids
        assert pool.standbys == []  # refill=False → pool spent
        events = flight.get_record()["events"]
        promote = [e for e in events if e["type"] == "warmpool.promote"]
        place = [
            e
            for e in events
            if e["type"] == "replica.place"
            and e["attrs"].get("warm_pool") is True
        ]
        assert promote and place
        await controller.stop()

    async def test_dead_standby_is_released_and_refilled(self):
        from bioengine_tpu.cluster.state import ClusterState
        from bioengine_tpu.serving import ReplicaState, ServeController

        controller = ServeController(ClusterState(), health_check_period=3600)
        spec = _warm_spec(size=1)
        app = await controller.deploy("wp3", [spec])
        pool = controller._warm_pools[("wp3", "e")]
        dead = pool.standbys[0]
        dead.state = ReplicaState.UNHEALTHY
        await controller.health_tick()
        # the tick releases the dead standby immediately; the refill is
        # a cold start and runs OFF the health loop (background task)
        await _wait_for(
            lambda: len(pool.standbys) == 1
            and pool.standbys[0].replica_id != dead.replica_id,
            msg="dead standby replaced",
        )
        assert dead.state == ReplicaState.STOPPED
        await controller.stop()

    async def test_undeploy_sweeps_standbys(self):
        from bioengine_tpu.cluster.state import ClusterState
        from bioengine_tpu.serving import ReplicaState, ServeController

        controller = ServeController(ClusterState(), health_check_period=3600)
        spec = _warm_spec(size=2)
        await controller.deploy("wp4", [spec])
        pool = controller._warm_pools[("wp4", "e")]
        standbys = list(pool.standbys)
        assert len(standbys) == 2
        await controller.undeploy("wp4")
        assert ("wp4", "e") not in controller._warm_pools
        assert all(r.state == ReplicaState.STOPPED for r in standbys)
        await controller.stop()

    def test_target_size_follows_telemetry(self):
        from bioengine_tpu.serving import WarmPool, WarmPoolConfig

        class RisingRate:
            def series(self, app, dep, name):
                assert name == "request_rate"
                return [{"t": 0, "value": v} for v in (1.0, 1.0, 5.0)]

        class FlatRate:
            def series(self, app, dep, name):
                return [{"t": 0, "value": 1.0}] * 4

        pool = WarmPool(
            "a", "d", WarmPoolConfig(size=1, max_size=2, telemetry_sized=True)
        )
        assert pool.target_size(RisingRate()) == 2   # burst → deepen
        assert pool.target_size(FlatRate()) == 1     # steady → configured
        assert pool.target_size(None) == 1
        capped = WarmPool(
            "a", "d", WarmPoolConfig(size=2, max_size=2, telemetry_sized=True)
        )
        assert capped.target_size(RisingRate()) == 2  # never past max_size

    def test_builder_parses_warm_pool_block(self, tmp_path):
        import yaml

        from bioengine_tpu.apps.builder import AppBuilder, AppBuildError

        def write_app(warm_pool):
            d = tmp_path / "app-src"
            d.mkdir(exist_ok=True)
            (d / "manifest.yaml").write_text(
                yaml.safe_dump(
                    {
                        "name": "WP App",
                        "id": "wp-app",
                        "id_emoji": "x",
                        "description": "d",
                        "type": "tpu-serve",
                        "version": "1.0.0",
                        "deployments": ["dep:Dep"],
                        "authorized_users": ["*"],
                        "deployment_config": {
                            "dep": {"warm_pool": warm_pool}
                        },
                    }
                )
            )
            (d / "dep.py").write_text(
                "from bioengine_tpu.rpc import schema_method\n\n\n"
                "class Dep:\n"
                "    @schema_method\n"
                "    async def ping(self, context=None):\n"
                '        """Ping."""\n'
                "        return 'ok'\n"
            )
            return d

        builder = AppBuilder(workdir_root=tmp_path / "apps")
        built = builder.build(
            app_id="wp-app",
            local_path=write_app({"size": 2, "telemetry_sized": True}),
        )
        spec = built.specs[0]
        assert spec.warm_pool is not None
        assert spec.warm_pool.size == 2
        assert spec.warm_pool.telemetry_sized is True
        with pytest.raises(AppBuildError, match="warm_pool"):
            builder.build(
                app_id="wp-app-bad",
                local_path=write_app({"pool_size": 2}),
            )


# ---------------------------------------------------------------------------
# shared compile-cache tier over the in-process multi-host control plane
# ---------------------------------------------------------------------------

WARM_CHAOS_MANIFEST = """\
name: Warm Chaos App
id: warm-chaos-app
id_emoji: "\\U0001F525"
description: idempotent arithmetic for warm-pool chaos traffic
type: tpu-serve
version: 1.0.0
deployments:
  - chaos_dep:ChaosDep
authorized_users: ["*"]
deployment_config:
  chaos_dep:
    num_replicas: 2
    min_replicas: 2
    max_replicas: 3
    chips: 3
    autoscale: false
    warm_pool:
      size: 1
      refill: false
"""

CHAOS_SOURCE = '''\
from bioengine_tpu.rpc import schema_method


class ChaosDep:
    def __init__(self):
        self.calls = 0

    @schema_method
    async def add(self, a: int, b: int, context=None):
        """Idempotent arithmetic."""
        self.calls += 1
        return {"sum": a + b}
'''


def _no_local_chips():
    from bioengine_tpu.cluster.state import ClusterState
    from bioengine_tpu.cluster.topology import TpuTopology

    return ClusterState(TpuTopology(chips=(), n_hosts=1, platform="cpu"))


@pytest.fixture()
async def control_plane(tmp_path):
    from bioengine_tpu.rpc.server import RpcServer
    from bioengine_tpu.serving import ServeController
    from bioengine_tpu.worker_host import WorkerHost

    server = RpcServer(host="127.0.0.1", admin_users=["admin"])
    await server.start()
    token = server.issue_token("admin", is_admin=True)
    controller = ServeController(_no_local_chips(), health_check_period=3600)
    # per-test tier directory (the default is a real home-dir path)
    from bioengine_tpu.serving.compile_tier import CompileCacheTier

    controller.compile_tier = CompileCacheTier(tmp_path / "tier")
    controller.attach_rpc(server, admin_users=["admin"])
    hosts = []

    async def spawn_host(host_id: str, **kwargs) -> WorkerHost:
        host = WorkerHost(
            server_url=server.url,
            token=token,
            host_id=host_id,
            workspace_dir=tmp_path / f"ws-{host_id}",
            **kwargs,
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


class TestCompileTierSync:
    async def test_join_publishes_and_later_host_fetches(
        self, control_plane
    ):
        """h1 joins with two locally-compiled entries → they land in
        the controller tier; h2 joins with an empty directory → the
        entries are fetched into it (a fresh autoscaled host starts
        with the fleet's programs), with program.cache_fetch flight
        evidence and tier hit accounting."""
        server, controller, spawn_host, tmp_path = control_plane
        flight.clear()
        dir_a = tmp_path / "xla-a"
        dir_a.mkdir()
        (dir_a / "jit_model-k1-cache").write_bytes(b"P1" * 600)
        (dir_a / "jit_model-k2-cache").write_bytes(b"P2" * 600)
        (dir_a / "jit_model-k1-atime").write_bytes(b"t")  # local-only
        dir_b = tmp_path / "xla-b"
        dir_b.mkdir()

        h1 = await spawn_host("h1", compile_cache_dir=dir_a)
        assert h1.tier_published_count == 2
        assert set(controller.compile_tier.list()) == {
            "jit_model-k1-cache",
            "jit_model-k2-cache",
        }

        h2 = await spawn_host("h2", compile_cache_dir=dir_b)
        assert h2.tier_fetched == 2
        assert compile_cache.list_entries(dir_b) == {
            "jit_model-k1-cache": 1200,
            "jit_model-k2-cache": 1200,
        }
        assert (dir_b / "jit_model-k1-cache").read_bytes() == b"P1" * 600
        # the fetch is flight-recorded (the trace of WHY a cold compile
        # became a disk read)
        fetches = [
            e
            for e in flight.get_record()["events"]
            if e["type"] == "program.cache_fetch"
        ]
        assert len(fetches) == 2
        assert all(e["attrs"]["host"] == "h2" for e in fetches)
        stats = controller.compile_tier.stats()
        assert stats["served"] == 2 and stats["stored"] == 2
        assert stats["hit_rate"] == 1.0
        # host describe carries the sync counters
        assert h2.describe()["compile_tier"]["fetched"] == 2
        assert h1.describe()["compile_tier"]["published"] == 2

    async def test_replica_start_resyncs_and_publishes(
        self, control_plane
    ):
        """Entries published AFTER a host joined are pulled before its
        next replica build, and entries the build compiles are pushed
        back — the start_replica hook, proven at file level."""
        from pathlib import Path

        from bioengine_tpu.apps.builder import AppBuilder
        from bioengine_tpu.serving import RequestOptions

        server, controller, spawn_host, tmp_path = control_plane
        dir_a = tmp_path / "xla-h1"
        dir_a.mkdir()
        h1 = await spawn_host("h1", compile_cache_dir=dir_a)
        # a LATER publisher (another host's compile)
        controller.compile_tier.publish("jit_late-k9-cache", b"LATE" * 300)

        app_dir = tmp_path / "app-src"
        app_dir.mkdir()
        (app_dir / "manifest.yaml").write_text(WARM_CHAOS_MANIFEST.replace(
            "num_replicas: 2", "num_replicas: 1"
        ).replace("min_replicas: 2", "min_replicas: 1").replace(
            "    warm_pool:\n      size: 1\n      refill: false\n", ""
        ))
        (app_dir / "chaos_dep.py").write_text(CHAOS_SOURCE)
        builder = AppBuilder(workdir_root=tmp_path / "apps")
        built = builder.build(app_id="warm-chaos-app", local_path=app_dir)
        await controller.deploy("warm-chaos-app", built.specs)
        # the pre-build sync installed the late entry locally
        assert "jit_late-k9-cache" in compile_cache.list_entries(dir_a)
        # and a "compile" this replica produced locally is published back
        (Path(dir_a) / "jit_fresh-k5-cache").write_bytes(b"F" * 100)
        await h1._publish_compile_cache()
        assert "jit_fresh-k5-cache" in controller.compile_tier.list()
        handle = controller.get_handle("warm-chaos-app")
        r = await handle.call(
            "add", 1, 2, options=RequestOptions(idempotent=True)
        )
        assert r["sum"] == 3


# ---------------------------------------------------------------------------
# acceptance: preemption chaos with a warm pool
# ---------------------------------------------------------------------------


class TestWarmPoolChaos:
    async def test_preemption_absorbed_by_standby(self, control_plane):
        """Kill the host serving a replica mid-traffic: the warm
        standby absorbs the loss within the request deadline — ZERO
        failed idempotent requests, chip accounting exact, and the
        flight record shows warmpool.promote between host.dead and
        replica.place."""
        from bioengine_tpu.apps.builder import AppBuilder
        from bioengine_tpu.serving import ReplicaState, RequestOptions

        server, controller, spawn_host, tmp_path = control_plane
        flight.clear()
        h1 = await spawn_host("h1")
        h2 = await spawn_host("h2")
        app_dir = tmp_path / "chaos-src"
        app_dir.mkdir()
        (app_dir / "manifest.yaml").write_text(WARM_CHAOS_MANIFEST)
        (app_dir / "chaos_dep.py").write_text(CHAOS_SOURCE)
        builder = AppBuilder(workdir_root=tmp_path / "apps")
        built = builder.build(app_id="warm-chaos-app", local_path=app_dir)
        await controller.deploy("warm-chaos-app", built.specs)
        app = controller.apps["warm-chaos-app"]
        replicas = app.replicas["chaos_dep"]
        assert sorted(r.host_id for r in replicas) == ["h1", "h2"]
        pool = controller._warm_pools[("warm-chaos-app", "chaos_dep")]
        assert len(pool.standbys) == 1
        standby = pool.standbys[0]
        # kill the host that serves a replica but does NOT hold the
        # standby — the standby must survive to absorb the preemption
        victim_host = next(
            h for h in (h1, h2)
            if h.host_id != standby.host_id
            and any(r.host_id == h.host_id for r in replicas)
        )
        survivor = h1 if victim_host is h2 else h2

        handle = controller.get_handle("warm-chaos-app")
        opts = RequestOptions(idempotent=True, deadline_s=20, max_attempts=8)
        failures: list = []
        successes = [0]
        kill_at = asyncio.Event()

        async def traffic(worker_id: int):
            for i in range(25):
                try:
                    r = await handle.call("add", worker_id, i, options=opts)
                    assert r["sum"] == worker_id + i
                    successes[0] += 1
                except Exception as e:  # noqa: BLE001 — counted, not raised
                    failures.append(e)
                if i == 6 and worker_id == 0:
                    kill_at.set()
                await asyncio.sleep(0.004)

        tasks = [asyncio.create_task(traffic(w)) for w in range(4)]
        await asyncio.wait_for(kill_at.wait(), 10)
        # the in-process analog of SIGKILL/preemption (test_chaos)
        victim_host.rejoin = False
        victim_host.connection.auto_reconnect = False
        victim_host.connection._closing = True
        await victim_host.connection._abort_connection()

        t_kill = time.monotonic()
        recovered = False
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            await controller.health_tick()
            reps = app.replicas["chaos_dep"]
            routable = [
                r
                for r in reps
                if r.state in (ReplicaState.HEALTHY, ReplicaState.TESTING)
            ]
            if len(routable) == 2:
                recovered = True
                break
            await asyncio.sleep(0.05)
        recovery_s = time.monotonic() - t_kill
        await asyncio.gather(*tasks)

        assert failures == []          # ZERO failed idempotent requests
        assert successes[0] == 100
        assert recovered, "standby was not promoted in time"
        assert recovery_s < 15.0       # well inside the request deadline
        # the standby WAS the absorber
        ids = [r.replica_id for r in app.replicas["chaos_dep"]]
        assert standby.replica_id in ids
        assert standby.promoted_from_warm_pool is True
        assert pool.standbys == []     # refill=false → pool spent

        # flight timeline: host.dead → warmpool.promote → replica.place
        events = flight.get_record(limit=2000)["events"]
        i_dead = next(
            i for i, e in enumerate(events)
            if e["type"] == "host.dead"
            and e["attrs"].get("host") == victim_host.host_id
        )
        i_promote = next(
            i for i, e in enumerate(events)
            if e["type"] == "warmpool.promote"
            and e["attrs"].get("replica") == standby.replica_id
        )
        i_place = next(
            i for i, e in enumerate(events)
            if e["type"] == "replica.place"
            and e["attrs"].get("replica") == standby.replica_id
            and e["attrs"].get("warm_pool") is True
        )
        assert i_dead < i_promote < i_place

        # chip accounting exact: the dead host leaks nothing; the
        # survivor holds its original replica + the promoted standby
        # (2 leases x 3 chips), no double lease
        state = controller.cluster_state
        assert state.hosts[victim_host.host_id].chips_in_use == {}
        assert not state.hosts[victim_host.host_id].alive
        surviving = state.hosts[survivor.host_id].chips_in_use
        assert len(surviving) == 6
        assert len(set(surviving.values())) == 2

        # the cold-start status surface reports the promotion
        cold = controller.get_app_status("warm-chaos-app")["deployments"][
            "chaos_dep"
        ]["cold_start"]
        assert cold["warm_pool"]["promotions"] == 1
        assert cold["warm_pool"]["occupancy"] == 0
