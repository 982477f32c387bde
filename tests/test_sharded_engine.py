"""Mesh-aware sharded serving (the multi-chip inference engine).

Hermetic on the forced 8-virtual-host-device CPU mesh (tests/conftest.py
sets ``--xla_force_host_platform_device_count=8`` — the same trick as
the MULTICHIP dryruns), exercising the guarantees the engine makes:

- a 1-chip engine is BIT-IDENTICAL to the legacy single-device path;
- a dp=4 engine matches the single-device result within float tolerance
  on both the planar direct path and the overlap-tiled path;
- uneven batches pad to a dp multiple (equal shards) and crop back;
- compiled-program cache keys separate per mesh shape, so engines with
  different chip groups sharing one cache never mix executables;
- the replica lifecycle hands the leased chip group to the instance,
  and killing a sharded replica returns every leased chip (no leak).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bioengine_tpu.cluster.state import ClusterState
from bioengine_tpu.models.unet import UNet2D
from bioengine_tpu.runtime.buckets import bucket_batch
from bioengine_tpu.runtime.engine import (
    EngineConfig,
    InferenceEngine,
    resolve_devices,
)
from bioengine_tpu.runtime.program_cache import CompiledProgramCache
from bioengine_tpu.serving import DeploymentSpec, ReplicaState, ServeController

pytestmark = pytest.mark.unit


@pytest.fixture(scope="module")
def unet():
    model = UNet2D(features=(4, 8), out_channels=1)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 64, 64, 1), jnp.float32)
    )["params"]
    return model, params


def _make_engine(unet, devices, config=None, cache=None, **kw):
    model, params = unet
    return InferenceEngine(
        "sharded-test",
        lambda p, x: model.apply({"params": p}, x),
        params,
        divisor=model.divisor,
        config=config,
        # `cache or ...` would discard an EMPTY cache (len 0 is falsy)
        cache=cache if cache is not None else CompiledProgramCache(),
        devices=devices,
        **kw,
    )


@pytest.fixture(scope="module")
def images():
    rng = np.random.default_rng(7)
    return rng.standard_normal((3, 70, 70, 1)).astype(np.float32)


class TestBucketBatchDp:
    def test_multiple_of_rounds_up_within_ladder(self):
        assert bucket_batch(3, multiple_of=4) == 4
        assert bucket_batch(5, multiple_of=4) == 8
        assert bucket_batch(2, multiple_of=4) == 4
        assert bucket_batch(4, multiple_of=4) == 4

    def test_multiple_of_one_is_legacy(self):
        for n in (1, 2, 3, 5, 17, 65, 200):
            assert bucket_batch(n) == bucket_batch(n, multiple_of=1)

    def test_off_ladder_fallback_stays_divisible(self):
        got = bucket_batch(130, multiple_of=3)
        assert got >= 130 and got % 3 == 0

    def test_non_power_of_two_dp_small_batches_stay_small(self):
        # dp=3 divides no default ladder entry; the fallback must NOT
        # balloon a 1-image request to a 64-ceil batch (observed 66)
        assert bucket_batch(1, multiple_of=3) == 3
        assert bucket_batch(5, multiple_of=3) == 6
        assert bucket_batch(7, multiple_of=3) == 12


class TestMeshParity:
    def test_one_chip_bit_identical_to_legacy(self, unet, images):
        legacy = _make_engine(unet, None)  # today's device path
        one = _make_engine(unet, jax.devices()[:1])
        try:
            assert one.mesh is None  # degenerate mesh IS the legacy path
            a = legacy.predict(images)
            b = one.predict(images)
            np.testing.assert_array_equal(a, b)
        finally:
            legacy.close()
            one.close()

    def test_dp4_planar_matches_single(self, unet, images):
        e1 = _make_engine(unet, jax.devices()[:1])
        e4 = _make_engine(unet, jax.devices()[:4])
        try:
            assert e4.mesh_shape == {"dp": 4}
            y1 = e1.predict(images)
            y4 = e4.predict(images)
            assert y4.shape == y1.shape
            np.testing.assert_allclose(y4, y1, rtol=1e-5, atol=1e-6)
        finally:
            e1.close()
            e4.close()

    def test_dp4_tiled_matches_single(self, unet, images):
        cfg = EngineConfig(
            max_tile=64, tile=48, tile_overlap=8, tile_batch=4
        )
        e1 = _make_engine(unet, jax.devices()[:1], config=cfg)
        e4 = _make_engine(unet, jax.devices()[:4], config=cfg)
        try:
            y1 = e1.predict(images)  # 70 > max_tile: overlap-tiled
            y4 = e4.predict(images)
            np.testing.assert_allclose(y4, y1, rtol=1e-5, atol=1e-6)
        finally:
            e1.close()
            e4.close()

    def test_dp4_requests_served_together_reply_like_serial(self, unet):
        """On a dp mesh the stream's chunks stay dp-divisible (every row
        count it may choose came off the batch ladder with the same dp),
        and requests that share them still reply bit-identically to the
        serial path on the same mesh; their bills sum to the device
        time x mesh width."""
        import threading
        import time

        from bioengine_tpu.utils import tracing

        cfg = EngineConfig(max_tile=64, tile=48, tile_overlap=8, tile_batch=8)
        e4 = _make_engine(unet, jax.devices()[:4], config=cfg)
        rng = np.random.default_rng(3)
        # 4, 6 and 9 tiles: alone 4 | 8 (6 padded) | 8 + 4 (1 padded)
        inputs = [
            rng.standard_normal((1, h, w, 1)).astype(np.float32)
            for h, w in ((70, 70), (70, 100), (100, 100), (70, 70))
        ]
        plug = rng.standard_normal((1, 100, 100, 1)).astype(np.float32)
        gate = threading.Event()
        gate.set()
        sound = e4._stream._force

        def held(flight):  # what is dispatched stays in flight while it is shut
            gate.wait(30)
            return sound(flight)

        e4._stream._force = held
        accounts = []

        def accounted(x):
            acc, token = tracing.start_chip_accounting()
            try:
                return e4.predict(x)
            finally:
                tracing.stop_chip_accounting(token)
                accounts.append(acc)

        def wait_for(condition):
            deadline = time.monotonic() + 60
            while not condition():
                assert time.monotonic() < deadline
                time.sleep(0.002)

        try:
            serial = [e4.predict_serial(x) for x in inputs]
            e4.predict(plug)  # compile outside the bills
            busy = e4.pipeline_stats.compute_seconds
            issued = e4.pipeline_stats.chunks
            gate.clear()
            # the plug's two chunks hold the window: the four requests
            # enrol behind them, and which rows share which chunk is
            # settled before another chunk can go
            futures = [e4.submit(accounted, plug)]
            wait_for(lambda: e4.pipeline_stats.chunks - issued == 2)
            before = e4.pipeline_stats.as_dict()
            for x in inputs:
                futures.append(e4.submit(accounted, x))
                wait_for(lambda: len(e4._stream._pending) == len(futures))
            gate.set()
            replies = [f.result(timeout=120) for f in futures][1:]
            after = e4.pipeline_stats.as_dict()
        finally:
            gate.set()
            e4.close()
        for reply, want in zip(replies, serial):
            np.testing.assert_allclose(reply, want, rtol=0, atol=0)
        # 4 (alone it runs 4 rows: its chunk is full) | 6 + 2 | 7 + 1 |
        # 3 -> 4: each chunk at a dp multiple off the ladder
        assert after["chunks_shared"] - before["chunks_shared"] == 2
        assert after["rows_useful"] - before["rows_useful"] == 23
        assert after["rows_executed"] - before["rows_executed"] == 24
        batch_dims = {
            key[1] for key in e4.cache._programs
            if key[-1].split("@")[0] == "dp4"
        }
        assert batch_dims <= {4, 8}
        assert sum(acc.seconds for acc in accounts) == pytest.approx(
            4 * (e4.pipeline_stats.compute_seconds - busy), rel=1e-6
        )

    def test_dp4_serial_tiled_matches_too(self, unet, images):
        cfg = EngineConfig(max_tile=64, tile=48, tile_overlap=8)
        e4 = _make_engine(unet, jax.devices()[:4], config=cfg)
        e1 = _make_engine(unet, jax.devices()[:1], config=cfg)
        try:
            np.testing.assert_allclose(
                e4.predict_serial(images),
                e1.predict_serial(images),
                rtol=1e-5,
                atol=1e-6,
            )
        finally:
            e4.close()
            e1.close()

    def test_uneven_batch_pads_to_dp_multiple_and_crops(self, unet):
        e4 = _make_engine(unet, jax.devices()[:4])
        try:
            rng = np.random.default_rng(0)
            for b in (1, 3, 5):
                x = rng.standard_normal((b, 64, 64, 1)).astype(np.float32)
                y = e4.predict(x)
                assert y.shape[0] == b  # cropped back to the request
            # the compiled batch dims are the padded dp multiples
            batch_dims = {
                key[1]
                for key in e4.cache._programs
                if key[-1].split("@")[0] == "dp4"
            }
            assert batch_dims == {4, 8}  # 1,3 -> 4; 5 -> 8
        finally:
            e4.close()

    def test_dp_padding_rows_do_not_contaminate(self, unet):
        """Padded batch rows are zeros on the last shard; real rows must
        come back identical to a full-batch run (per-sample model)."""
        e4 = _make_engine(unet, jax.devices()[:4])
        try:
            rng = np.random.default_rng(1)
            x4 = rng.standard_normal((4, 64, 64, 1)).astype(np.float32)
            full = e4.predict(x4)
            part = e4.predict(x4[:3])
            np.testing.assert_allclose(
                part, full[:3], rtol=1e-6, atol=1e-7
            )
        finally:
            e4.close()


class TestTensorParallel:
    def test_tp_vit_embedder_matches_single(self):
        from bioengine_tpu.models.vit import ViT
        from bioengine_tpu.parallel.tensor_parallel import (
            VIT_TP_RULES,
            shard_fraction,
        )

        vit = ViT(patch_size=8, dim=64, depth=2, num_heads=4,
                  dtype=jnp.float32)
        x0 = jnp.zeros((1, 64, 64, 3), jnp.float32)
        params = vit.init(jax.random.key(0), x0)["params"]

        def apply_fn(p, x):
            return vit.apply({"params": p}, x)

        rng = np.random.default_rng(2)
        x = rng.standard_normal((4, 64, 64, 3)).astype(np.float32)
        e1 = InferenceEngine(
            "vit-tp", apply_fn, params, cache=CompiledProgramCache(),
            devices=jax.devices()[:1],
        )
        etp = InferenceEngine(
            "vit-tp", apply_fn, params, cache=CompiledProgramCache(),
            devices=jax.devices()[:4], tp=2, tp_rules=VIT_TP_RULES,
        )
        try:
            assert etp.mesh_shape == {"dp": 2, "tp": 2}
            # weights genuinely distributed, not replicated
            assert shard_fraction(etp.params) < 0.9
            np.testing.assert_allclose(
                etp.predict(x), e1.predict(x), rtol=1e-4, atol=1e-5
            )
        finally:
            e1.close()
            etp.close()

    def test_tp_must_divide_group(self, unet):
        with pytest.raises(ValueError, match="tp=3"):
            _make_engine(unet, jax.devices()[:4], tp=3)


class TestProgramCacheMeshKeys:
    def test_keys_separate_per_mesh_shape(self, unet, images):
        cache = CompiledProgramCache()
        e1 = _make_engine(unet, jax.devices()[:1], cache=cache)
        e4 = _make_engine(unet, jax.devices()[:4], cache=cache)
        try:
            e1.predict(images)
            e4.predict(images)
            tags = sorted(key[-1].split("@")[0] for key in cache._programs)
            assert tags == ["1dev", "dp4"]
            # same bucket shape in both keys — only the placement differs
            shapes = {key[1:-2] for key in cache._programs}
            assert len(shapes) == 1
            assert cache.stats.misses == 2
        finally:
            e1.close()
            e4.close()

    def test_same_shape_different_chip_groups_do_not_collide(
        self, unet, images
    ):
        # Two dp=2 engines over DISJOINT device pairs sharing one cache:
        # a shape-only key would hand engine B engine A's warmed
        # executable, and B's first hot request would silently retrace
        # and recompile on its own mesh. Placement-qualified keys give
        # each group its own entry (and its own warmup).
        cache = CompiledProgramCache()
        a = _make_engine(unet, jax.devices()[:2], cache=cache)
        b = _make_engine(unet, jax.devices()[2:4], cache=cache)
        try:
            out_a = a.predict(images)
            out_b = b.predict(images)
            assert cache.stats.misses == 2
            np.testing.assert_allclose(out_a, out_b, rtol=1e-5, atol=1e-5)
        finally:
            a.close()
            b.close()

    def test_same_mesh_shape_reuses_program(self, unet, images):
        cache = CompiledProgramCache()
        a = _make_engine(unet, jax.devices()[:4], cache=cache)
        b = _make_engine(unet, jax.devices()[:4], cache=cache)
        try:
            a.predict(images)
            b.predict(images)
            assert cache.stats.misses == 1
            assert cache.stats.hits >= 1
        finally:
            a.close()
            b.close()


class TestResolveDevices:
    def test_matches_by_id(self):
        devs = jax.local_devices()
        got = resolve_devices([devs[2].id, devs[0].id])
        assert got == [devs[2], devs[0]]

    def test_unknown_ids_preserve_width(self):
        # TpuTopology-numbered lease exercised on the CPU mesh: ids
        # don't exist here, but the mesh width must survive
        got = resolve_devices([1001, 1002, 1003, 1004])
        assert got == jax.local_devices()[:4]

    def test_oversized_lease_raises(self):
        with pytest.raises(ValueError, match="local devices"):
            resolve_devices(list(range(1000, 1099)))

    def test_partial_id_match_is_a_loud_conflict(self):
        # ids 0..98: 0-7 exist here, the rest don't — remapping would
        # stack disjoint leases onto the same chips, so it must raise
        with pytest.raises(ValueError, match="numbering conflict"):
            resolve_devices(list(range(99)))

    def test_empty_lease_is_single_device(self):
        assert resolve_devices(None) == jax.local_devices()[:1]


class MeshAwareApp:
    """Deployment that records the injected chip group (the contract
    model-runner's RuntimeDeployment consumes in async_init)."""

    def __init__(self):
        self.seen_lease = None

    async def async_init(self):
        self.seen_lease = list(getattr(self, "bioengine_device_ids", []))

    def mesh_info(self):
        return {
            "lease": self.seen_lease,
            "mesh_shape": {"dp": len(self.seen_lease or [1])},
        }

    async def echo(self, value):
        return {"echo": value}


@pytest.fixture
async def controller():
    # explicit 8-chip topology: chip ACCOUNTING must not depend on how
    # many virtual devices the current process happens to expose
    from bioengine_tpu.cluster.topology import ChipInfo, TpuTopology

    topo = TpuTopology(
        chips=tuple(
            ChipInfo(device_id=i, platform="cpu", kind="virtual",
                     process_index=0)
            for i in range(8)
        ),
        n_hosts=1,
        platform="cpu",
    )
    c = ServeController(ClusterState(topo), health_check_period=3600)
    yield c
    await c.stop()


@pytest.mark.integration
@pytest.mark.anyio
class TestShardedReplicaLifecycle:
    async def test_lease_injected_into_instance(self, controller):
        app = await controller.deploy(
            "mesh-app",
            [
                DeploymentSpec(
                    name="rt",
                    instance_factory=MeshAwareApp,
                    chips_per_replica=4,
                    autoscale=False,
                )
            ],
        )
        replica = app.replicas["rt"][0]
        assert len(replica.device_ids) == 4
        assert replica.instance.seen_lease == list(replica.device_ids)
        # describe surfaces the mesh + queue fields for the controller
        d = replica.describe()
        assert d["mesh"]["mesh_shape"] == {"dp": 4}
        assert d["queued_requests"] == 0

    async def test_status_surfaces_load_and_mesh(self, controller):
        await controller.deploy(
            "mesh-app2",
            [
                DeploymentSpec(
                    name="rt",
                    instance_factory=MeshAwareApp,
                    chips_per_replica=2,
                    autoscale=False,
                )
            ],
        )
        status = controller.get_app_status("mesh-app2")
        dep = status["deployments"]["rt"]
        for key in (
            "outstanding_calls",
            "queued_calls",
            "avg_load",
            "mesh_shapes",
            "queue_depth",
        ):
            assert key in dep, key
        assert dep["outstanding_calls"] == 0
        [shape] = dep["mesh_shapes"].values()
        assert shape == {"dp": 2}

    async def test_killed_sharded_replica_returns_all_chips(self, controller):
        """Kill a K-chip replica -> all K chips come back; the restarted
        replica leases K again; undeploy leaks nothing."""
        state = controller.cluster_state
        app = await controller.deploy(
            "mesh-app3",
            [
                DeploymentSpec(
                    name="rt",
                    instance_factory=MeshAwareApp,
                    chips_per_replica=4,
                    autoscale=False,
                )
            ],
        )
        assert state.free_chips() == 4
        old = app.replicas["rt"][0]
        old_lease = list(old.device_ids)
        assert len(old_lease) == 4
        # kill: the health loop notices and restarts on fresh chips
        old.state = ReplicaState.UNHEALTHY
        await controller.health_tick()
        await asyncio.sleep(0.05)
        new = app.replicas["rt"][0]
        assert new.replica_id != old.replica_id
        assert len(new.device_ids) == 4
        # exactly one 4-chip lease outstanding — no double-lease, no leak
        assert state.free_chips() == 4
        await controller.undeploy("mesh-app3")
        assert state.free_chips() == 8
