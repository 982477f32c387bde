import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bioengine_tpu.runtime import pipeline
from bioengine_tpu.runtime.buckets import (
    bucket_batch,
    bucket_dim,
    bucket_shape,
    crop_to,
    pad_to,
)
from bioengine_tpu.runtime.convert import (
    conv_kernel,
    convert_state_dict,
    dinov2_name_map,
    linear_kernel,
)
from bioengine_tpu.runtime.engine import EngineConfig, InferenceEngine
from bioengine_tpu.runtime.program_cache import CompiledProgramCache
from bioengine_tpu.runtime.rdf import (
    apply_processing,
    from_nhwc,
    load_model_rdf,
    to_nhwc,
)
from bioengine_tpu.utils import tracing

pytestmark = pytest.mark.unit


class TestBuckets:
    def test_bucket_dim_ladder(self):
        assert bucket_dim(200) == 256
        assert bucket_dim(256) == 256
        assert bucket_dim(257) == 384

    def test_bucket_dim_divisor(self):
        assert bucket_dim(100, divisor=8) % 8 == 0

    def test_bucket_fallback_respects_odd_divisor(self):
        # divisor 5 divides no ladder entry: the fallback must still
        # return a multiple of 5 (a downstream shape error otherwise),
        # quantized geometrically so compilations stay bounded
        assert bucket_dim(8, (8, 16, 24, 32), 5) == 10
        assert bucket_dim(101, (8, 16), 5) == 160  # 5 * 2^5
        assert bucket_dim(106, (8, 16), 5) == 160  # same bucket, no recompile
        # power-of-two divisors keep the 128 alignment above the ladder
        assert bucket_dim(3000, (64, 128), 2) == 3072

    def test_bucket_above_ladder(self):
        assert bucket_dim(5000) >= 5000

    def test_bucket_batch(self):
        assert bucket_batch(3) == 4
        assert bucket_batch(64) == 64

    def test_pad_crop_roundtrip(self):
        x = np.random.rand(1, 50, 70, 3).astype(np.float32)
        bh, bw = bucket_shape((50, 70))
        padded = pad_to(x, (bh, bw))
        assert padded.shape == (1, bh, bw, 3)
        np.testing.assert_array_equal(crop_to(padded, (50, 70)), x)

    def test_pad_rejects_oversize(self):
        with pytest.raises(ValueError):
            pad_to(np.zeros((1, 300, 300, 1)), (256, 256))


class TestProgramCache:
    def test_hit_miss_eviction(self):
        cache = CompiledProgramCache(max_programs=2)
        calls = []
        for key in ["a", "b", "a", "c"]:
            cache.get_or_compile(key, lambda k=key: calls.append(k) or k)
        assert calls == ["a", "b", "c"]  # "a" second time was a hit
        assert cache.stats.hits == 1
        assert cache.stats.evictions == 1  # "a" evicted when "c" arrived (LRU=a? no: a was touched)
        assert len(cache) == 2

    def test_concurrent_build_single_compile(self):
        cache = CompiledProgramCache()
        n_builds = []
        barrier = threading.Barrier(4)

        def build():
            n_builds.append(1)
            return "prog"

        def worker():
            barrier.wait()
            assert cache.get_or_compile("k", build) == "prog"

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(n_builds) == 1

    def test_evict_predicate(self):
        cache = CompiledProgramCache()
        cache.get_or_compile(("m1", 256), lambda: 1)
        cache.get_or_compile(("m2", 256), lambda: 2)
        assert cache.evict(lambda k: k[0] == "m1") == 1
        assert cache.keys() == [("m2", 256)]

    def test_eviction_drops_compile_seconds(self):
        """compile_seconds must not keep entries for evicted programs
        (a long-lived replica cycling shapes would leak the dict), on
        BOTH eviction paths; the lifetime total survives."""
        cache = CompiledProgramCache(max_programs=2)
        for key in ["a", "b", "c"]:  # "a" evicted by LRU pressure
            cache.get_or_compile(key, lambda k=key: k)
        assert set(cache.stats.compile_seconds) == {"b", "c"}
        cache.evict(lambda k: k == "b")  # predicate path
        assert set(cache.stats.compile_seconds) == {"c"}
        d = cache.stats.as_dict()
        assert d["total_compile_seconds"] >= d["live_compile_seconds"]
        # lifetime total still counts all three compiles
        assert (
            cache.stats.cumulative_compile_seconds
            > sum(cache.stats.compile_seconds.values()) * 0.99
        )


class TestEngine:
    @pytest.fixture(scope="class")
    def engine(self):
        # identity-ish model: 1x1 conv equivalent via simple lambda
        def apply_fn(params, x):
            return x * params["scale"]

        return InferenceEngine(
            "ident",
            apply_fn,
            {"scale": jnp.asarray(2.0)},
            cache=CompiledProgramCache(),
        )

    def test_predict_exact_bucket(self, engine):
        x = np.ones((1, 64, 64, 1), np.float32)
        out = engine.predict(x)
        np.testing.assert_allclose(out, 2.0 * x)

    def test_predict_odd_shape_cropped_back(self, engine):
        x = np.random.rand(2, 50, 77, 3).astype(np.float32)
        out = engine.predict(x)
        assert out.shape == (2, 50, 77, 3)
        np.testing.assert_allclose(out, 2 * x, rtol=1e-5)

    def test_same_bucket_reuses_program(self, engine):
        engine.predict(np.ones((1, 60, 60, 1), np.float32))
        misses_before = engine.cache.stats.misses
        engine.predict(np.ones((1, 64, 64, 1), np.float32))  # same bucket
        assert engine.cache.stats.misses == misses_before

    def test_tiled_prediction_matches_direct(self):
        def apply_fn(params, x):
            return x + 1.0

        cfg = EngineConfig(max_tile=64, tile=48, tile_overlap=16)
        eng = InferenceEngine(
            "plus1", apply_fn, {}, config=cfg, cache=CompiledProgramCache()
        )
        x = np.random.rand(1, 100, 90, 2).astype(np.float32)
        out = eng.predict(x)
        assert out.shape == x.shape
        np.testing.assert_allclose(out, x + 1.0, rtol=1e-4, atol=1e-5)

    def test_volume_bucketed_predict(self, engine):
        # 5D input routes through the volumetric path; odd sizes pad to
        # the z/xy buckets and crop back
        x = np.random.rand(1, 5, 50, 70, 2).astype(np.float32)
        out = engine.predict(x)
        assert out.shape == x.shape
        np.testing.assert_allclose(out, 2 * x, rtol=1e-5)

    def test_volume_tiled_matches_direct(self):
        def apply_fn(params, x):
            return x * 3.0

        cfg = EngineConfig(
            max_tile=32, tile=24, tile_overlap=8,
            max_tile_z=8, tile_z=6, tile_overlap_z=2,
            ladder_z=(2, 4, 6, 8),
        )
        eng = InferenceEngine(
            "times3-3d", apply_fn, {}, config=cfg,
            cache=CompiledProgramCache(),
        )
        x = np.random.rand(1, 13, 40, 50, 1).astype(np.float32)
        out = eng.predict(x)
        assert out.shape == x.shape
        np.testing.assert_allclose(out, 3 * x, rtol=1e-4, atol=1e-5)

    def test_thin_wide_stack_clamps_z_overlap(self):
        # D smaller than tile_overlap_z: the z tile clamps to D and the
        # overlap clamps below the tile instead of crashing the ramp
        def apply_fn(params, x):
            return x + 2.0

        cfg = EngineConfig(
            max_tile=32, tile=24, tile_overlap=8,
            max_tile_z=16, tile_z=12, tile_overlap_z=8,
        )
        eng = InferenceEngine(
            "plus2-thin", apply_fn, {}, config=cfg,
            cache=CompiledProgramCache(),
        )
        x = np.random.rand(1, 4, 60, 40, 1).astype(np.float32)
        out = eng.predict(x)
        assert out.shape == x.shape
        np.testing.assert_allclose(out, x + 2.0, rtol=1e-4, atol=1e-5)

    def test_tiled_chunks_bound_device_batch(self):
        # tile_batch=2 forces multiple chunks; stitching must still be
        # exact and the largest compiled batch must stay at the chunk cap
        def apply_fn(params, x):
            return x * 2.0

        cfg = EngineConfig(
            max_tile=16, tile=16, tile_overlap=4, tile_batch=2,
            ladder=(16,),
        )
        cache = CompiledProgramCache()
        eng = InferenceEngine(
            "times2-chunk", apply_fn, {}, config=cfg, cache=cache
        )
        x = np.random.rand(1, 50, 50, 1).astype(np.float32)
        out = eng.predict(x)
        np.testing.assert_allclose(out, x * 2.0, rtol=1e-4, atol=1e-5)
        batches = {key[1] for key in cache._programs}  # (model, B, ...)
        assert max(batches) <= 2, batches

    def test_volume_respects_z_divisor(self):
        """A real 3D conv model: padding must land on the pooling
        divisor in every axis or the forward would shape-error."""
        import jax

        from bioengine_tpu.models.unet3d import UNet3D

        model = UNet3D(features=(2, 4), out_channels=1)
        x = np.random.rand(1, 6, 20, 24, 1).astype(np.float32)
        params = model.init(jax.random.key(0), jnp.zeros((1, 8, 32, 32, 1)))[
            "params"
        ]
        eng = InferenceEngine(
            "unet3d-test",
            lambda p, a: model.apply({"params": p}, a),
            params,
            divisor=model.divisor,
            z_divisor=model.z_divisor,
            cache=CompiledProgramCache(),
        )
        out = eng.predict(x)
        assert out.shape == (1, 6, 20, 24, 1)


class TestConvert:
    def test_conv_kernel_layout(self):
        w = np.arange(2 * 3 * 5 * 7).reshape(2, 3, 5, 7).astype(np.float32)
        assert conv_kernel(w).shape == (5, 7, 3, 2)

    def test_linear_kernel(self):
        assert linear_kernel(np.zeros((4, 8))).shape == (8, 4)

    def test_convert_strict_raises_on_unmapped(self):
        with pytest.raises(KeyError):
            convert_state_dict({"weird.key": np.zeros(3)}, {})

    def test_dinov2_map_round_trip_into_vit(self):
        from bioengine_tpu.models.vit import ViT

        depth, dim, heads, patch = 2, 32, 4, 14
        model = ViT(patch_size=patch, dim=dim, depth=depth, num_heads=heads)
        x = jnp.zeros((1, 28, 28, 3))
        ref_params = model.init(jax.random.key(0), x)["params"]

        # Build a fake torch state dict with matching shapes.
        sd = {
            "cls_token": np.zeros((1, 1, dim), np.float32),
            "pos_embed": np.zeros((1, 5, dim), np.float32),
            "patch_embed.proj.weight": np.zeros((dim, 3, patch, patch), np.float32),
            "patch_embed.proj.bias": np.zeros(dim, np.float32),
            "norm.weight": np.ones(dim, np.float32),
            "norm.bias": np.zeros(dim, np.float32),
        }
        for i in range(depth):
            sd.update(
                {
                    f"blocks.{i}.norm1.weight": np.ones(dim, np.float32),
                    f"blocks.{i}.norm1.bias": np.zeros(dim, np.float32),
                    f"blocks.{i}.attn.qkv.weight": np.zeros((3 * dim, dim), np.float32),
                    f"blocks.{i}.attn.qkv.bias": np.zeros(3 * dim, np.float32),
                    f"blocks.{i}.attn.proj.weight": np.zeros((dim, dim), np.float32),
                    f"blocks.{i}.attn.proj.bias": np.zeros(dim, np.float32),
                    f"blocks.{i}.ls1.gamma": np.ones(dim, np.float32),
                    f"blocks.{i}.ls2.gamma": np.ones(dim, np.float32),
                    f"blocks.{i}.norm2.weight": np.ones(dim, np.float32),
                    f"blocks.{i}.norm2.bias": np.zeros(dim, np.float32),
                    f"blocks.{i}.mlp.fc1.weight": np.zeros((4 * dim, dim), np.float32),
                    f"blocks.{i}.mlp.fc1.bias": np.zeros(4 * dim, np.float32),
                    f"blocks.{i}.mlp.fc2.weight": np.zeros((dim, 4 * dim), np.float32),
                    f"blocks.{i}.mlp.fc2.bias": np.zeros(dim, np.float32),
                }
            )
        params = convert_state_dict(sd, dinov2_name_map(depth))
        # Same tree structure as a natively initialized model.
        ref_paths = {"/".join(str(k) for k in p) for p, _ in jax.tree_util.tree_flatten_with_path(ref_params)[0]}
        got_paths = {"/".join(str(k) for k in p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
        assert ref_paths == got_paths
        # And the converted params actually run through the model.
        out = model.apply({"params": params}, x)
        assert out.shape == (1, dim)


class TestRDF:
    def test_load_and_axes(self, tmp_path):
        rdf = {
            "name": "test-unet",
            "type": "model",
            "inputs": [
                {
                    "name": "raw",
                    "axes": "bcyx",
                    "preprocessing": [
                        {"name": "zero_mean_unit_variance", "kwargs": {}}
                    ],
                }
            ],
            "outputs": [{"name": "mask", "axes": "bcyx"}],
            "weights": {"pytorch_state_dict": {"source": "weights.pt"}},
        }
        p = tmp_path / "rdf.yaml"
        import yaml

        p.write_text(yaml.safe_dump(rdf))
        model = load_model_rdf(p)
        assert model.name == "test-unet"
        fmt, _ = model.preferred_weights
        assert fmt == "pytorch_state_dict"

    def test_to_from_nhwc_roundtrip(self):
        x = np.random.rand(2, 3, 10, 12).astype(np.float32)  # bcyx
        nhwc = to_nhwc(x, "bcyx")
        assert nhwc.shape == (2, 10, 12, 3)
        back = from_nhwc(nhwc, "bcyx")
        np.testing.assert_array_equal(back, x)

    def test_volumetric_axes_roundtrip(self):
        from bioengine_tpu.runtime.rdf import canonical_layout

        assert canonical_layout("bczyx") == "bzyxc"
        assert canonical_layout("byxc") == "byxc"
        x = np.random.rand(2, 3, 5, 10, 12).astype(np.float32)  # bczyx
        vol = to_nhwc(x, "bczyx")
        assert vol.shape == (2, 5, 10, 12, 3)
        back = from_nhwc(vol, "bczyx")
        np.testing.assert_array_equal(back, x)
        # batchless 0.4-style volume: zyx gains batch + channel dims
        y = np.random.rand(4, 6, 8).astype(np.float32)
        vol = to_nhwc(y, "bzyx")  # implicit batch from ndim mismatch
        assert vol.shape == (1, 4, 6, 8, 1)

    def test_unsupported_axes_rejected_loudly(self):
        # a time axis must not be silently misrouted into the
        # volumetric path as if it were z
        x = np.zeros((1, 3, 2, 8, 9), np.float32)
        with pytest.raises(ValueError, match="not support"):
            to_nhwc(x, "btcyx")

    def test_axes_dict_form(self):
        from bioengine_tpu.runtime.rdf import _axes_string

        axes = [
            {"type": "batch"},
            {"type": "channel"},
            {"type": "space", "id": "y"},
            {"type": "space", "id": "x"},
        ]
        assert _axes_string(axes) == "bcyx"

    def test_processing_ops(self):
        x = np.random.rand(1, 8, 8, 1).astype(np.float32) * 100
        out = apply_processing(
            x, [{"name": "zero_mean_unit_variance", "kwargs": {}}]
        )
        assert abs(out.mean()) < 1e-4
        out2 = apply_processing(x, [{"name": "scale_range", "kwargs": {"min_percentile": 1, "max_percentile": 99}}])
        assert out2.min() >= -0.1 and out2.max() <= 1.1
        with pytest.raises(NotImplementedError):
            apply_processing(x, [{"name": "nonexistent_op"}])


class TestFlows:
    def test_masks_to_flows_unit_norm_inside(self):
        from bioengine_tpu.ops.flows import masks_to_flows

        masks = np.zeros((32, 32), np.int32)
        masks[8:24, 8:24] = 1
        flows = masks_to_flows(masks)
        mag = np.sqrt(flows[0] ** 2 + flows[1] ** 2)
        inside = masks > 0
        assert mag[inside].mean() > 0.5
        assert mag[~inside].max() == 0.0

    def test_follow_flows_converges_to_center(self):
        from bioengine_tpu.ops.flows import follow_flows

        H = W = 16
        yy, xx = np.meshgrid(np.arange(H), np.arange(W), indexing="ij")
        # flow pointing at center (8, 8)
        dy = np.clip(8 - yy, -1, 1).astype(np.float32)
        dx = np.clip(8 - xx, -1, 1).astype(np.float32)
        p = np.asarray(follow_flows(jnp.stack([jnp.asarray(dy), jnp.asarray(dx)]), n_iter=40))
        assert np.abs(p[0] - 8).max() < 1.5
        assert np.abs(p[1] - 8).max() < 1.5

    def test_masks_from_flows_two_cells(self):
        from bioengine_tpu.ops.flows import masks_from_flows, masks_to_flows

        masks = np.zeros((48, 48), np.int32)
        masks[6:20, 6:20] = 1
        masks[28:44, 28:44] = 2
        flows = masks_to_flows(masks)
        cellprob = np.where(masks > 0, 5.0, -5.0).astype(np.float32)
        rec = masks_from_flows(flows, cellprob, n_iter=100)
        assert rec.max() == 2  # two instances recovered
        # instance regions should match reasonably (IoU > 0.7 each)
        for lbl in (1, 2):
            ref = masks == lbl
            cand = [np.mean((rec == r) & ref) / max(np.mean((rec == r) | ref), 1e-9) for r in range(1, rec.max() + 1)]
            assert max(cand) > 0.7


    def test_follow_flows_3d_converges_to_center(self):
        from bioengine_tpu.ops.flows import follow_flows_3d

        D = H = W = 11
        zz, yy, xx = np.meshgrid(
            np.arange(D), np.arange(H), np.arange(W), indexing="ij"
        )
        flow = np.stack(
            [
                np.clip(5 - zz, -1, 1),
                np.clip(5 - yy, -1, 1),
                np.clip(5 - xx, -1, 1),
            ]
        ).astype(np.float32)
        p = np.asarray(follow_flows_3d(jnp.asarray(flow), n_iter=30))
        assert np.abs(p - 5).max() < 1.5

    def test_aggregate_orthogonal_flows_recovers_field(self):
        """Per-orientation predictions built from a known 3D field must
        aggregate back to exactly that field (each component is the
        mean of two identical contributions)."""
        from bioengine_tpu.ops.flows import aggregate_orthogonal_flows

        rng = np.random.default_rng(0)
        D, H, W = 4, 5, 6
        F = rng.normal(size=(3, D, H, W)).astype(np.float32)  # dz, dy, dx
        cp = rng.normal(size=(D, H, W)).astype(np.float32)
        pred_yx = np.stack([F[1], F[2], cp], axis=-1)  # [z, y, x, c]
        pred_zx = np.transpose(
            np.stack([F[0], F[2], cp], axis=-1), (1, 0, 2, 3)
        )  # -> [y, z, x, c]
        pred_zy = np.transpose(
            np.stack([F[0], F[1], cp], axis=-1), (2, 0, 1, 3)
        )  # -> [x, z, y, c]
        flow, cellprob = aggregate_orthogonal_flows(pred_yx, pred_zx, pred_zy)
        np.testing.assert_allclose(flow, F, rtol=1e-6)
        np.testing.assert_allclose(cellprob, cp, rtol=1e-6)

    def test_masks_from_flows_3d_two_cells(self):
        from bioengine_tpu.ops.flows import masks_from_flows

        D = H = W = 24
        masks = np.zeros((D, H, W), np.int32)
        masks[4:10, 4:10, 4:10] = 1
        masks[14:21, 14:21, 14:21] = 2
        centers = {1: (7.0, 7.0, 7.0), 2: (17.0, 17.0, 17.0)}
        zz, yy, xx = np.meshgrid(
            np.arange(D), np.arange(H), np.arange(W), indexing="ij"
        )
        flow = np.zeros((3, D, H, W), np.float32)
        for lbl, (cz, cy, cx) in centers.items():
            sel = masks == lbl
            vec = np.stack([cz - zz, cy - yy, cx - xx]).astype(np.float32)
            norm = np.sqrt((vec**2).sum(0)) + 1e-6
            for d in range(3):
                flow[d][sel] = (vec[d] / norm)[sel]
        cellprob = np.where(masks > 0, 5.0, -5.0).astype(np.float32)
        rec = masks_from_flows(flow, cellprob, n_iter=60)
        assert rec.max() == 2
        for lbl in (1, 2):
            ref = masks == lbl
            ious = [
                np.mean((rec == r) & ref) / max(np.mean((rec == r) | ref), 1e-9)
                for r in range(1, rec.max() + 1)
            ]
            assert max(ious) > 0.7


class _GatedEngine(InferenceEngine):
    """An engine whose read-back waits for ``gate``: what is dispatched
    stays in flight, and what is enrolled meanwhile piles up behind it,
    so which rows share which chunk does not depend on thread timing."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.gate = threading.Event()
        self.gate.set()

    # set: every read-back reads as one that found its chunk done
    found_done = False

    def _force_chunk(self, flight):
        self.gate.wait(30)
        host = super()._force_chunk(flight)
        if self.found_done:
            flight.waited_ns = 0
        return host

    def served_together(self, inputs, plug):
        """Futures of ``inputs``' predictions, all enrolled while
        ``plug``'s two chunks hold the in-flight window: the stream has
        every one of them in hand before it can issue another chunk."""
        self.gate.clear()
        before = self.pipeline_stats.chunks
        futures = [self.submit(self.predict, plug)]
        self.wait_for(lambda: self.pipeline_stats.chunks - before == 2)
        for x in inputs:
            futures.append(self.submit(self.predict, x))
            self.wait_for(lambda: len(self._stream._pending) == len(futures))
        self.wait_for(lambda: not self._stream._cutter_busy)
        self.gate.set()
        return futures[1:]

    @staticmethod
    def wait_for(condition, seconds=30):
        deadline = time.monotonic() + seconds
        while not condition():
            assert time.monotonic() < deadline, "the stream stood still"
            time.sleep(0.002)


def _program_shapes(eng):
    return set(eng.describe()["programs"]["compile_seconds"])


class TestTileStream:
    """The engine's one tile stream (runtime/pipeline.py) against the
    serial baseline: bit-identical replies whoever shares a chunk, chunks
    filled across the requests in hand and closed late, a bounded
    in-flight window, reusable staging buffers, failures that stay with
    the requests that rode the chunk, and the async front door."""

    def _engine(self, apply_fn=None, cls=InferenceEngine, **cfg_overrides):
        cfg_kw = dict(
            max_tile=64, tile=48, tile_overlap=16, tile_batch=3,
            pipeline_depth=2,
        )
        cfg_kw.update(cfg_overrides)
        return cls(
            "pipe",
            apply_fn or (lambda p, x: x * p["scale"] + 0.25),
            {"scale": jnp.asarray(1.7)},
            config=EngineConfig(**cfg_kw),
            cache=CompiledProgramCache(),
        )

    @staticmethod
    def _image(tiles_per_axis, channels=1):
        # stride 32 over tile 48: 3, 4, 5 tiles an axis at 112, 144, 176 px
        size = 48 + 32 * (tiles_per_axis - 1)
        return np.random.rand(1, size, size, channels).astype(np.float32)

    def test_planar_identical_to_serial(self):
        # tile 48 buckets to 64: the staging-buffer pad margins are
        # exercised, and rtol=0 (exact equality) must still hold
        eng = self._engine()
        x = np.random.rand(3, 100, 90, 2).astype(np.float32)
        serial = eng.predict_serial(x)
        piped = eng.predict(x)
        np.testing.assert_allclose(piped, serial, rtol=0, atol=0)
        np.testing.assert_allclose(piped, x * 1.7 + 0.25, rtol=1e-4, atol=1e-5)

    def test_volumetric_identical_to_serial(self):
        eng = InferenceEngine(
            "pipe3d",
            lambda p, x: x * 3.0,
            {},
            config=EngineConfig(
                max_tile=32, tile=24, tile_overlap=8,
                max_tile_z=8, tile_z=6, tile_overlap_z=2,
                ladder_z=(2, 4, 6, 8), tile_batch=2, pipeline_depth=3,
            ),
            cache=CompiledProgramCache(),
        )
        x = np.random.rand(2, 13, 40, 50, 1).astype(np.float32)
        serial = eng.predict_serial(x)
        piped = eng.predict(x)
        np.testing.assert_allclose(piped, serial, rtol=0, atol=0)
        assert piped.shape == x.shape

    @pytest.mark.parametrize("ndim", [4, 5])
    def test_requests_served_together_reply_like_serial(self, ndim):
        """9-, 16- and 25-tile inputs from several threads at once: each
        reply is bit-identical to the serial path's, whoever shared its
        chunks (5-D: a stack tiled in z as well)."""
        if ndim == 4:
            eng = self._engine(cls=_GatedEngine, tile_batch=16)
            inputs = [self._image(n) for n in (3, 4, 5, 3, 5, 4)]
        else:
            eng = _GatedEngine(
                "pipe3d", lambda p, x: x * 3.0 + 1.0, {},
                config=EngineConfig(
                    max_tile=32, tile=24, tile_overlap=8,
                    max_tile_z=8, tile_z=6, tile_overlap_z=2,
                    ladder_z=(2, 4, 6, 8), tile_batch=16,
                ),
                cache=CompiledProgramCache(),
            )
            inputs = [
                np.random.rand(1, d, h, w, 1).astype(np.float32)
                for d, h, w in ((13, 40, 50), (10, 40, 40), (13, 56, 40), (6, 40, 50))
            ]
        serial = [eng.predict_serial(x) for x in inputs]
        replies = [None] * len(inputs)

        def ask(i):
            replies[i] = eng.predict(inputs[i])

        threads = [
            threading.Thread(target=ask, args=(i,)) for i in range(len(inputs))
        ]
        # a held read-back: the requests pile up behind the first chunks,
        # so chunks are shared for certain
        eng.gate.clear()
        try:
            for t in threads:
                t.start()
            eng.wait_for(lambda: len(eng._stream._pending) == len(inputs))
            eng.gate.set()
            for t in threads:
                t.join(timeout=60)
                assert not t.is_alive()
        finally:
            eng.gate.set()
            eng.close()
        for reply, want in zip(replies, serial):
            np.testing.assert_allclose(reply, want, rtol=0, atol=0)
        assert eng.pipeline_stats.chunks_shared >= 1

    def test_chunks_are_filled_across_the_requests_in_hand(self):
        """Three 9-tile requests: 48 rows one after the other, 32 when
        the stream has all three in hand (16, then 11 padded to 16), in
        no program that a lone request would not run."""
        images = [self._image(3) for _ in range(3)]
        alone = self._engine(tile_batch=16)
        for x in images:
            alone.predict(x)
        assert (alone.pipeline_stats.rows_executed,
                alone.pipeline_stats.chunks_shared) == (48, 0)

        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        try:
            futures = eng.served_together(images, plug=self._image(5))
            before = eng.pipeline_stats.as_dict()   # the plug's two chunks
            replies = [f.result(timeout=60) for f in futures]
        finally:
            eng.gate.set()
            eng.close()
        after = eng.pipeline_stats.as_dict()
        assert [after[k] - before[k] for k in
                ("chunks", "rows_executed", "rows_useful", "chunks_shared")
                ] == [2, 32, 27, 2]
        assert _program_shapes(eng) <= _program_shapes(alone)
        assert eng.describe()["pipeline"]["chunks_shared"] == 2
        for reply, x in zip(replies, images):
            np.testing.assert_allclose(
                reply, alone.predict_serial(x), rtol=0, atol=0
            )

    def test_a_chunk_runs_at_a_row_count_one_of_its_requests_runs_alone(self):
        """6 tiles alone run 4 + 2 rows, 9 tiles 4 + 4 + 1; together
        they fill chunks of 4 (a tail of 3 runs at 4): no chunk of 2 or
        of 1, and no program that the two would not have built alone
        (each request builds its own on its own thread as it arrives,
        so the issuing thread never compiles)."""
        six = np.random.rand(1, 112, 80, 1).astype(np.float32)   # 3 x 2 tiles
        nine = self._image(3)
        eng = self._engine(cls=_GatedEngine, tile_batch=4)
        built_on, lookup = [], eng.cache.get_or_compile

        def noting(key, build):
            def noted():
                built_on.append(threading.current_thread().name)
                return build()

            return lookup(key, noted)

        eng.cache.get_or_compile = noting
        try:
            futures = eng.served_together([six, nine], plug=self._image(3))
            before, since = eng.pipeline_stats.rows_executed, time.time_ns()
            got = [f.result(timeout=60) for f in futures]
        finally:
            eng.gate.set()
            eng.close()
        # 15 tiles: 4 | 2 + 2 | 4 | 3 -> 4
        assert eng.pipeline_stats.rows_executed - before == 16
        puts = [
            s for s in tracing.get_stages(since)
            if s["name"] == "engine.put" and s["thread"] == "dispatch-pipe-device"
        ]
        assert [s["attrs"]["bytes"] for s in puts] == [4 * 64 * 64 * 4] * 4
        np.testing.assert_allclose(got[0], eng.predict_serial(six), rtol=0, atol=0)
        np.testing.assert_allclose(got[1], eng.predict_serial(nine), rtol=0, atol=0)
        alone = self._engine(tile_batch=4)
        alone.predict(six), alone.predict(nine)
        assert _program_shapes(eng) <= _program_shapes(alone)
        # built where the request is, not where the device is fed
        assert len(built_on) == 3       # 4, 2 and 1 rows
        assert all(name.startswith("dispatch-pipe_") for name in built_on)

    def test_a_lone_request_runs_the_chunks_it_always_did(self):
        eng = self._engine(tile_batch=16)
        x = self._image(5)                  # 25 tiles: 16, then 9 padded to 16
        try:
            reply = eng.predict(x)
        finally:
            eng.close()
        stats = eng.pipeline_stats
        assert (stats.chunks, stats.chunks_shared) == (2, 0)
        assert (stats.rows_executed, stats.rows_useful) == (32, 25)
        np.testing.assert_allclose(reply, eng.predict_serial(x), rtol=0, atol=0)
        # 9 tiles in chunks of 4: 4, 4 and a tail of 1, each at its own
        # rung of the batch ladder, as the serial path runs them
        small = self._engine(tile_batch=4)
        small.predict(self._image(3))
        assert (small.pipeline_stats.chunks, small.pipeline_stats.rows_executed) == (3, 9)
        serial = self._engine(tile_batch=4)
        serial.predict_serial(self._image(3))
        assert _program_shapes(small) == _program_shapes(serial)

    def test_the_open_chunk_is_closed_late(self):
        """With two chunks in flight (a gated read-back keeps them
        there) a 9-tile request opens a chunk, and a second one that
        arrives later still joins it: 9 + 7 go as one full chunk."""
        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        first, second, third = self._image(5), self._image(3), self._image(3)
        try:
            eng.predict(self._image(3))     # compile the one program
            before = eng.pipeline_stats.as_dict()

            def issued():
                return eng.pipeline_stats.chunks - before["chunks"]

            eng.gate.clear()
            futures = [eng.submit(eng.predict, first)]   # 16 + 9, held in flight
            eng.wait_for(lambda: issued() == 2)
            futures.append(eng.submit(eng.predict, second))
            eng.wait_for(lambda: len(eng._stream._pending) == 2)
            time.sleep(0.05)                # its 9 rows wait in the open chunk
            assert eng._stream._open.rows == 9
            futures.append(eng.submit(eng.predict, third))
            eng.wait_for(lambda: len(eng._stream._pending) == 3)
            eng.wait_for(lambda: len(eng._stream._staged) == 1)
            assert issued() == 2            # nothing went meanwhile
            eng.gate.set()
            replies = [f.result(timeout=60) for f in futures]
        finally:
            eng.gate.set()
            eng.close()
        after = eng.pipeline_stats.as_dict()
        # 16, 9 -> 16, 9 + 7, 2 -> 16; one after the other it would be 5 chunks
        assert after["chunks"] - before["chunks"] == 4
        assert after["chunks_shared"] - before["chunks_shared"] == 1
        assert after["rows_useful"] - before["rows_useful"] == 25 + 9 + 9
        for reply, x in zip(replies, (first, second, third)):
            np.testing.assert_allclose(
                reply, eng.predict_serial(x), rtol=0, atol=0
            )

    # the program of the tests' 1-channel tiles (48 px, bucketed to 64)
    # at 16 rows, as the stream keys its readings
    PROGRAM = (((64, 64, 1), "<f4"), 16)

    @staticmethod
    def _timed(*readings):
        """A program's kept (device time, put + dispatch) readings."""
        return deque(readings, maxlen=pipeline.TIMED_CHUNKS)

    def _held_behind_its_first_chunk(self, eng, first):
        """``first`` (25 tiles) on an engine whose program is timed at a
        minute: its first 16 rows go at once and stay in flight (a gated
        read-back keeps them there), its other 9 are held in the open
        chunk for their deadline. Returns the stats before and the time
        since."""
        stream = eng._stream
        eng.predict(self._image(3))     # compile the one program
        before, since = TestEngineStages._sums(eng), time.time_ns()
        stream._timed[self.PROGRAM] = self._timed((60 * 10**9, 0))
        eng.gate.clear()
        future = eng.submit(eng.predict, first)
        eng.wait_for(lambda: stream._open is not None and stream._open.held_ns)
        assert eng.pipeline_stats.chunks - before["chunks"] == 1
        assert stream._open.rows == 9
        return before, since, future

    def test_a_held_chunk_takes_the_rows_of_a_request_that_enrols_meanwhile(self):
        """One chunk in flight and a place free in the window: the 9-row
        open chunk is held for the chunk in flight's deadline, and a
        7-tile request that enrols during the hold fills it: 16 | 9 + 7,
        both full, where the late rule ran 16 | 9 | 7."""
        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        first = self._image(5)
        seven = np.random.rand(1, 240, 48, 1).astype(np.float32)   # 7 x 1 tiles
        try:
            before, since, future = self._held_behind_its_first_chunk(eng, first)
            futures = [future, eng.submit(eng.predict, seven)]
            eng.wait_for(lambda: eng.pipeline_stats.chunks - before["chunks"] == 2)
            eng.gate.set()
            replies = [f.result(timeout=60) for f in futures]
        finally:
            eng.gate.set()
            eng.close()
        after = TestEngineStages._sums(eng)
        chunks = self._dispatches(since)
        assert [(d["closed"], d["rows"], d["capacity"]) for d in chunks] == [
            ("full", 16, 16), ("full", 16, 16)
        ]
        assert [len(d["requests"]) for d in chunks] == [1, 2]
        assert chunks[0]["held_ms"] == 0 and chunks[1]["held_ms"] > 0
        assert {k: after[k] - before[k] for k in (
            "chunks", "chunks_held", "chunks_held_filled", "chunks_closed_late",
            "rows_executed", "rows_useful",
        )} == {
            "chunks": 2, "chunks_held": 1, "chunks_held_filled": 1,
            "chunks_closed_late": 0, "rows_executed": 32, "rows_useful": 32,
        }
        assert after["hold_seconds"] - before["hold_seconds"] == pytest.approx(
            chunks[1]["held_ms"] / 1e3, abs=1e-9
        )
        for reply, x in zip(replies, (first, seven)):
            np.testing.assert_allclose(
                reply, eng.predict_serial(x), rtol=0, atol=0
            )

    def test_a_held_chunk_that_nothing_joins_goes_late_at_its_deadline(self):
        """Held behind its first chunk, a 25-tile request's 9-row tail
        goes ``late`` when its deadline comes (brought forward here: the
        program read anew at nothing), having taken no rows meanwhile."""
        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        first = self._image(5)
        stream = eng._stream
        try:
            before, since, future = self._held_behind_its_first_chunk(eng, first)
            with stream._cond:
                stream._timed[self.PROGRAM].append((0, 0))
                stream._cond.notify_all()
            eng.wait_for(lambda: eng.pipeline_stats.chunks - before["chunks"] == 2)
            eng.gate.set()
            reply = future.result(timeout=60)
        finally:
            eng.gate.set()
            eng.close()
        after = TestEngineStages._sums(eng)
        chunks = self._dispatches(since)
        assert [(d["closed"], d["rows"]) for d in chunks] == [
            ("full", 16), ("late", 9)
        ]
        assert chunks[0]["held_ms"] == 0 and chunks[1]["held_ms"] > 0
        assert [after[k] - before[k] for k in (
            "chunks_held", "chunks_held_filled", "chunks_closed_late"
        )] == [1, 0, 1]
        assert after["hold_seconds"] - before["hold_seconds"] == pytest.approx(
            chunks[1]["held_ms"] / 1e3, abs=1e-9
        )
        np.testing.assert_allclose(reply, eng.predict_serial(first), rtol=0, atol=0)

    @pytest.mark.parametrize("case", ["dry", "untimed"])
    def test_nothing_is_held_where_the_device_is_dry_or_the_program_untimed(
        self, case
    ):
        """``dry``: a lone 9-tile request on an idle engine, its program
        timed at a minute, goes at once (nothing is in flight).
        ``untimed``: a 25-tile request's 9-row tail, behind its first 16
        in flight, whose program no read-back has timed yet, goes at
        once."""
        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        x = self._image(3) if case == "dry" else self._image(5)
        try:
            if case == "dry":
                eng.predict(x)
                before, since = TestEngineStages._sums(eng), time.time_ns()
                eng._stream._timed[self.PROGRAM] = self._timed((60 * 10**9, 0))
                reply = eng.submit(eng.predict, x).result(timeout=30)
            else:
                before, since = TestEngineStages._sums(eng), time.time_ns()
                eng.gate.clear()
                future = eng.submit(eng.predict, x)
                eng.wait_for(lambda: eng.pipeline_stats.chunks == 2)
                assert self.PROGRAM not in eng._stream._timed
                eng.gate.set()
                reply = future.result(timeout=60)
        finally:
            eng.gate.set()
            eng.close()
        after = TestEngineStages._sums(eng)
        chunks = self._dispatches(since)
        assert [(d["closed"], d["rows"], d["held_ms"]) for d in chunks][-1] == (
            "late", 9, 0.0
        )
        assert [after[k] - before[k] for k in (
            "chunks_held", "chunks_held_filled", "hold_seconds"
        )] == [0, 0, 0]
        np.testing.assert_allclose(reply, eng.predict_serial(x), rtol=0, atol=0)

    def test_a_read_back_that_finds_its_chunk_done_forgets_the_program(self):
        """A held chunk filled and sent, its read-backs find their chunks
        done: they saw no end, so the program's readings are forgotten,
        and the next request's 9-row tail, behind its first 16 in
        flight, goes at once, late and unheld."""
        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        first, third = self._image(5), self._image(5)
        seven = np.random.rand(1, 240, 48, 1).astype(np.float32)   # 7 x 1 tiles
        stream = eng._stream
        try:
            before, since, future = self._held_behind_its_first_chunk(eng, first)
            eng.found_done = True
            futures = [future, eng.submit(eng.predict, seven)]
            eng.wait_for(lambda: eng.pipeline_stats.chunks - before["chunks"] == 2)
            eng.gate.set()
            for f in futures:
                f.result(timeout=60)
            assert self.PROGRAM not in stream._timed
            eng.gate.clear()
            futures.append(eng.submit(eng.predict, third))
            eng.wait_for(lambda: eng.pipeline_stats.chunks - before["chunks"] == 4)
            eng.gate.set()
            replies = [f.result(timeout=60) for f in futures]
        finally:
            eng.gate.set()
            eng.close()
        after = TestEngineStages._sums(eng)
        chunks = self._dispatches(since)
        assert [(d["closed"], d["rows"], d["held_ms"] > 0) for d in chunks] == [
            ("full", 16, False), ("full", 16, True),
            ("full", 16, False), ("late", 9, False),
        ]
        assert [after[k] - before[k] for k in (
            "chunks_held", "chunks_held_filled", "chunks_closed_late"
        )] == [1, 1, 1]
        for reply, x in zip(replies, (first, seven, third)):
            np.testing.assert_allclose(
                reply, eng.predict_serial(x), rtol=0, atol=0
            )

    def test_the_deadline_is_the_end_in_flight_less_the_lead(self):
        """Known answers of ``TileStream._deadline``: the chunks in
        flight end one after another, each after the smallest device
        time its program kept, and the open chunk goes the smallest put
        + dispatch kept for the newest one's program and its bytes' copy
        earlier. A long reading (a host pause in the wait, a compile in
        the dispatch) beside shorter ones is never used."""
        eng = self._engine(tile_batch=16)
        stream = eng._stream
        ms = 10**6
        key = self.PROGRAM[0]

        def chunk(size=16, dispatched_ns=0):
            c = pipeline._Chunk(key, np.zeros((16, 64, 64, 1), np.float32))
            c.size = size
            c.flight = pipeline.InFlight(None, size, dispatched_ns)
            return c

        opened = chunk()
        copy = int(16 * 64 * 64 * 4 * pipeline.H2D_NS_PER_BYTE)
        try:
            stream._last_ready_ns = 3 * ms
            stream._timed[(key, 16)] = self._timed(
                (10 * ms, 2 * ms), (50 * ms, 1_500_000), (12 * ms, 30 * ms)
            )
            one = deque([chunk(dispatched_ns=1 * ms)])
            assert stream._deadline(one, opened) == 3 * ms + 10 * ms - 1_500_000 - copy
            one[0].flight.dispatched_ns = 5 * ms    # dispatched to a dry device
            assert stream._deadline(one, opened) == 5 * ms + 10 * ms - 1_500_000 - copy
            stream._timed[(key, 8)] = self._timed((5 * ms, 1 * ms))
            two = deque([chunk(dispatched_ns=1 * ms), chunk(8, 4 * ms)])
            assert stream._deadline(two, opened) == 13 * ms + 5 * ms - 1 * ms - copy
            assert stream._deadline(deque(), opened) is None    # the device is dry
            two.append(chunk(4, 6 * ms))                        # never timed
            assert stream._deadline(two, opened) is None
        finally:
            eng.close()

    @staticmethod
    def _dispatches(since):
        """The stream's chunks since then, as their ``engine.dispatch``
        stages' attrs, in the order they went."""
        return [
            s["attrs"] for s in tracing.get_stages(since, name="engine.dispatch")
            if s["thread"] == "dispatch-pipe-device"
        ]

    @staticmethod
    def _closed(before, after):
        return {
            reason: after[f"chunks_closed_{reason}"] - before[f"chunks_closed_{reason}"]
            for reason in ("full", "key", "direct", "late")
        }

    def test_the_stream_accounts_for_every_chunk_and_every_request(self):
        """The scenario of ``test_the_open_chunk_is_closed_late``: why
        each of the four chunks closed, which requests rode each, how
        many chunks each request rode, and its time in the stream, all
        inside its ``engine.request``."""
        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        first, second, third = self._image(5), self._image(3), self._image(3)
        # the sums as kept, not as ``as_dict`` rounds them
        sums = TestEngineStages._sums
        try:
            eng.predict(self._image(3))     # compile the one program
            before, since = sums(eng), time.time_ns()

            def issued():
                return eng.pipeline_stats.chunks - before["chunks"]

            eng.gate.clear()
            futures = [eng.submit(eng.predict, first)]
            eng.wait_for(lambda: issued() == 2)
            futures.append(eng.submit(eng.predict, second))
            eng.wait_for(lambda: len(eng._stream._pending) == 2)
            time.sleep(0.05)
            futures.append(eng.submit(eng.predict, third))
            eng.wait_for(lambda: len(eng._stream._pending) == 3)
            eng.wait_for(lambda: len(eng._stream._staged) == 1)
            eng.gate.set()
            for f in futures:
                f.result(timeout=60)
        finally:
            eng.gate.set()
            eng.close()
        after = sums(eng)
        stages = tracing.get_stages(since)
        seq = {
            s["start_ns"]: s["request_seq"]
            for s in stages if s["name"] == "engine.request"
        }
        a, b, c = (seq[t] for t in sorted(seq))   # first, second, third
        chunks = self._dispatches(since)
        # 16 | 9 (the window has a place) | 9 + 7 | 2 (the same)
        assert [d["closed"] for d in chunks] == ["full", "late", "full", "late"]
        assert [d["requests"] for d in chunks] == [[a], [a], [b, c], [c]]
        assert [(d["rows"], d["capacity"]) for d in chunks] == [
            (16, 16), (9, 16), (16, 16), (2, 16)
        ]
        assert self._closed(before, after) == {
            "full": 2, "key": 0, "direct": 0, "late": 2
        }
        assert sum(self._closed(before, after).values()) == (
            after["chunks"] - before["chunks"]
        )
        # the hold's account: its time is its chunks' ``held_ms``
        held, filled = (
            after[k] - before[k] for k in ("chunks_held", "chunks_held_filled")
        )
        assert 0 <= filled <= held <= after["chunks"] - before["chunks"]
        assert held == sum(d["held_ms"] > 0 for d in chunks)
        assert after["hold_seconds"] - before["hold_seconds"] == pytest.approx(
            sum(d["held_ms"] for d in chunks) / 1e3, abs=1e-9
        )
        assert after["jobs"] - before["jobs"] == 3
        assert after["job_chunks"] - before["job_chunks"] == sum(
            len(d["requests"]) for d in chunks
        ) == 5
        predicted = {
            s["request_seq"]: s for s in stages if s["name"] == "engine.predict"
        }
        requests = {
            s["request_seq"]: s for s in stages if s["name"] == "engine.request"
        }
        assert [
            (predicted[r]["attrs"]["tiles"], predicted[r]["attrs"]["chunks"])
            for r in (a, b, c)
        ] == [(25, 2), (9, 1), (9, 2)]
        waits = runs = 0.0
        for r in (a, b, c):
            attrs = predicted[r]["attrs"]
            assert attrs["stream_wait_s"] >= 0 and attrs["stream_run_s"] > 0
            assert attrs["stream_wait_s"] + attrs["stream_run_s"] <= (
                requests[r]["duration_s"]
            )
            waits += attrs["stream_wait_s"]
            runs += attrs["stream_run_s"]
        # the second waited in the open chunk behind the held window
        assert predicted[b]["attrs"]["stream_wait_s"] > 0.04
        assert after["stream_wait_seconds"] - before["stream_wait_seconds"] == (
            pytest.approx(waits, abs=1e-9)
        )
        assert after["stream_run_seconds"] - before["stream_run_seconds"] == (
            pytest.approx(runs, abs=1e-9)
        )

    def test_a_chunk_closes_when_the_next_request_has_another_key(self):
        """Tiles of one channel and of two share no chunk: the open
        chunk goes when the cut thread reaches the other key."""
        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        one, two = self._image(3), self._image(3, channels=2)
        try:
            before, since = eng.pipeline_stats.as_dict(), time.time_ns()
            futures = eng.served_together([one, two], plug=self._image(5))
            replies = [f.result(timeout=60) for f in futures]
        finally:
            eng.gate.set()
            eng.close()
        after = eng.pipeline_stats.as_dict()
        # the plug's 16 | 9, then 9 of one channel | 9 of two
        assert [d["closed"] for d in self._dispatches(since)] == [
            "full", "late", "key", "late"
        ]
        assert self._closed(before, after) == {
            "full": 1, "key": 1, "direct": 0, "late": 2
        }
        assert after["chunks"] - before["chunks"] == 4
        assert after["job_chunks"] - before["job_chunks"] == 4
        for reply, x in zip(replies, (one, two)):
            np.testing.assert_allclose(
                reply, eng.predict_serial(x), rtol=0, atol=0
            )

    def test_a_chunk_closes_when_a_direct_prediction_overtakes_it(self):
        """An input that needs no tiling goes in arrival order: the
        partly filled chunk before it goes first, closed ``direct``.
        The direct run is a chunk of ``chunks`` with no close reason."""
        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        nine, small = self._image(3), np.ones((1, 40, 40, 1), np.float32)
        try:
            eng.predict(small)                  # compile the direct program
            before, since = eng.pipeline_stats.as_dict(), time.time_ns()
            eng.gate.clear()
            futures = [eng.submit(eng.predict, self._image(5))]
            eng.wait_for(lambda: eng.pipeline_stats.chunks - before["chunks"] == 2)
            futures.append(eng.submit(eng.predict, nine))
            eng.wait_for(lambda: len(eng._stream._pending) == 2)
            eng.wait_for(lambda: not eng._stream._cutter_busy)
            assert eng._stream._open.rows == 9
            futures.append(eng.submit(eng.predict, small))
            eng.wait_for(lambda: any(
                type(entry).__name__ == "_Direct" for entry in eng._stream._staged
            ))
            eng.gate.set()
            replies = [f.result(timeout=60) for f in futures]
        finally:
            eng.gate.set()
            eng.close()
        after = eng.pipeline_stats.as_dict()
        assert [d.get("closed") for d in self._dispatches(since)] == [
            "full", "late", "direct", None
        ]
        closed = self._closed(before, after)
        assert closed == {"full": 1, "key": 0, "direct": 1, "late": 1}
        assert after["chunks"] - before["chunks"] == sum(closed.values()) + 1
        assert after["jobs"] - before["jobs"] == 2      # the tiled two
        np.testing.assert_allclose(replies[1], eng.predict_serial(nine), rtol=0, atol=0)
        np.testing.assert_allclose(replies[2], small * 1.7 + 0.25, rtol=1e-6)

    def test_staging_reuse_after_direct_path_poisoning(self):
        """A direct (non-tiled) predict shares the staging pool; its
        stale content in a reused buffer's pad margins must never leak
        into tiled results (regression: margins between the clamped
        tile extent and the bucket extent)."""
        eng = self._engine()
        x = np.random.rand(2, 100, 90, 2).astype(np.float32)
        serial = eng.predict_serial(x)
        # direct predict of a (bb, 64, 64, 2)-bucketed batch writes
        # nonzero data beyond the 48-wide tile extent
        eng.predict(np.random.rand(3, 60, 60, 2).astype(np.float32) + 5.0)
        piped = eng.predict(x)
        np.testing.assert_allclose(piped, serial, rtol=0, atol=0)

    @pytest.mark.parametrize("depth", [0, 1, 2, 3])
    def test_in_flight_window_bounded(self, depth):
        # a depth below 1 is a window of one chunk: the serial order,
        # through the same stream (there is no second way to predict)
        eng = self._engine(pipeline_depth=depth, tile_batch=1)
        x = np.random.rand(1, 120, 120, 1).astype(np.float32)
        out = eng.predict(x)
        stats = eng.pipeline_stats
        assert stats.chunks >= 4  # enough chunks to fill any window
        assert stats.max_in_flight <= max(depth, 1), (depth, stats.as_dict())
        np.testing.assert_allclose(out, eng.predict_serial(x), rtol=0, atol=0)

    def test_staging_buffers_are_recycled(self):
        from bioengine_tpu.runtime.pipeline import PREFETCH

        eng = self._engine()
        x = np.random.rand(4, 150, 150, 1).astype(np.float32)
        for _ in range(3):
            eng.predict(x)
        # many chunks over many requests, but the pool only ever
        # allocated what was concurrently outstanding: one shape of
        # buffer, the window and the chunks cut ahead of it
        assert eng.pipeline_stats.chunks >= 12
        assert eng._staging_pool.allocated <= eng.config.pipeline_depth + PREFETCH

    def test_stats_accounting(self):
        eng = self._engine()
        x = np.random.rand(2, 100, 100, 1).astype(np.float32)
        eng.predict(x)
        d = eng.pipeline_stats.as_dict()
        assert d["items"] == 2 and d["chunks"] > 0 and d["chunks_shared"] == 0
        for stage in ("cut", "put", "dispatch", "device_wait", "d2h", "stitch"):
            assert d[f"{stage}_seconds"] >= 0.0
        assert d["wall_seconds"] > 0
        assert 0.0 <= d["overlap_efficiency"] <= 1.5  # clock-skew slack

    def test_error_in_model_fails_the_request_and_the_stream_goes_on(self):
        def bad_fn(params, x):
            if x.shape[-1] == 2:
                raise RuntimeError("trace-time boom")
            return x + 1.0

        eng = self._engine(apply_fn=bad_fn)
        with pytest.raises(RuntimeError, match="boom"):
            eng.predict(np.random.rand(1, 100, 100, 2).astype(np.float32))
        # the same stream serves the next request
        x = np.random.rand(1, 100, 100, 1).astype(np.float32)
        np.testing.assert_allclose(eng.predict(x), x + 1.0, rtol=1e-6)

    def test_a_failing_tile_fails_only_the_requests_that_rode_its_chunk(self):
        def check(x):
            if x.max() > 100:
                raise ValueError("poisoned tile")
            return x * 2.0

        def apply_fn(p, x):
            return jax.pure_callback(
                check, jax.ShapeDtypeStruct(x.shape, x.dtype), x
            )

        eng = self._engine(apply_fn=apply_fn, cls=_GatedEngine, tile_batch=16)
        poisoned, rider, clear = self._image(3), self._image(3), self._image(4)
        poisoned[0, 0, 0, 0] = 1000.0
        try:
            # chunks: 9 poisoned + 7 of the rider | 2 of the rider + 14 | 2
            futures = eng.served_together(
                [poisoned, rider, clear], plug=self._image(5)
            )
            for f in futures[:2]:
                with pytest.raises(Exception, match="poisoned tile"):
                    f.result(timeout=60)
            np.testing.assert_allclose(
                futures[2].result(timeout=60), clear * 2.0, rtol=1e-6
            )
            # and the stream serves the next
            np.testing.assert_allclose(eng.predict(rider), rider * 2.0, rtol=1e-6)
        finally:
            eng.gate.set()
            eng.close()

    def test_global_output_raises_in_the_stream(self):
        eng = self._engine(apply_fn=lambda p, x: jnp.mean(x, axis=(1, 2)))
        with pytest.raises(ValueError, match="dense spatial"):
            eng.predict(np.ones((1, 100, 100, 2), np.float32))

    def test_close_fails_the_requests_in_hand_and_ends_the_threads(self):
        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        eng.gate.clear()
        futures = [eng.submit(eng.predict, self._image(n)) for n in (5, 4, 3)]
        eng.wait_for(lambda: len(eng._stream._pending) == 3)
        eng.wait_for(lambda: eng.pipeline_stats.chunks == 2)  # held in flight
        # an input that needs no tiling, waiting for its turn behind them
        futures.append(eng.submit(eng.predict, np.ones((1, 40, 40, 1), np.float32)))
        eng.wait_for(lambda: any(
            type(entry).__name__ == "_Direct" for entry in eng._stream._staged
        ))
        threads = [
            t for t in threading.enumerate()
            if t.name.startswith(("dispatch-pipe", "pipeline-"))
            and t in eng._stream._threads + list(eng._dispatcher._pool._threads)
        ]
        assert {t.name for t in threads} >= {
            "pipeline-cut", "dispatch-pipe-device", "pipeline-stitch",
            "dispatch-pipe_0", "dispatch-pipe_3",
        }
        closer = threading.Thread(target=eng.close)
        closer.start()
        eng.wait_for(lambda: eng._stream._closed)
        eng.gate.set()      # the issuing thread was held in a read-back
        closer.join(timeout=30)
        assert not closer.is_alive()
        for f in futures:
            with pytest.raises(RuntimeError, match="dispatcher 'dispatch-pipe' is closed"):
                f.result(timeout=30)
        with pytest.raises(RuntimeError, match="is closed"):
            eng.submit(eng.predict, self._image(3))
        with pytest.raises(RuntimeError, match="is closed"):
            eng.predict(self._image(3))
        eng.close()  # idempotent
        for t in threads:
            t.join(timeout=10)
        assert not [t.name for t in threads if t.is_alive()]

    def test_chip_seconds_are_shares_of_the_device_time(self):
        """Requests served together are billed their rows' share of each
        chunk they rode: the bills sum to the engine's device time."""
        eng = self._engine(cls=_GatedEngine, tile_batch=16)
        accounts = []

        def accounted(x):
            acc, token = tracing.start_chip_accounting()
            try:
                return eng.predict(x)
            finally:
                tracing.stop_chip_accounting(token)
                accounts.append(acc)

        try:
            eng.predict(self._image(3))     # compile outside the bills
            busy = eng.pipeline_stats.compute_seconds
            eng.gate.clear()
            futures = [
                eng.submit(accounted, self._image(n)) for n in (5, 3, 3, 4)
            ]
            futures.append(
                eng.submit(accounted, np.ones((2, 40, 40, 1), np.float32))
            )
            eng.wait_for(lambda: len(eng._stream._pending) == 4)
            eng.gate.set()
            for f in futures:
                f.result(timeout=60)
        finally:
            eng.gate.set()
            eng.close()
        assert eng.pipeline_stats.chunks_shared >= 1
        assert len(accounts) == 5 and all(acc.seconds > 0 for acc in accounts)
        assert sum(acc.seconds for acc in accounts) == pytest.approx(
            eng.pipeline_stats.compute_seconds - busy, rel=1e-6
        )

    @pytest.mark.anyio
    async def test_predict_async_front_door(self):
        import asyncio

        eng = self._engine()
        try:
            x = np.random.rand(2, 100, 90, 1).astype(np.float32)
            serial = eng.predict_serial(x)
            # concurrent async callers join the one stream and all come
            # back correct
            outs = await asyncio.gather(
                *(eng.predict_async(x) for _ in range(3))
            )
            for out in outs:
                np.testing.assert_allclose(out, serial, rtol=0, atol=0)
        finally:
            eng.close()

    def test_only_the_issuing_thread_talks_to_the_device(self):
        """Tiled or not, from a request thread or a caller's own: the
        device's stages are all on the stream's issuing thread."""
        eng = self._engine()
        tiled = np.random.rand(1, 100, 90, 1).astype(np.float32)
        direct = np.random.rand(2, 60, 60, 1).astype(np.float32)
        since = time.time_ns()
        try:
            eng.predict(tiled), eng.predict(direct)
            eng.submit(eng.predict, tiled).result(timeout=60)
            eng.submit(eng.predict, direct).result(timeout=60)
        finally:
            eng.close()
        on_device = [
            s for s in tracing.get_stages(since)
            if s["name"] in ("engine.put", "engine.dispatch",
                             "engine.device_wait", "engine.d2h")
        ]
        assert len(on_device) >= 4 * 4
        assert {s["thread"] for s in on_device} == {"dispatch-pipe-device"}


class TestGlobalOutputGuard:
    def test_padded_global_output_raises(self):
        def embed_fn(params, x):
            return jnp.mean(x, axis=(1, 2))  # (B, C) global output

        eng = InferenceEngine(
            "emb", embed_fn, {}, cache=CompiledProgramCache()
        )
        # exact bucket size: fine
        out = eng.predict(np.ones((1, 64, 64, 3), np.float32))
        assert out.shape == (1, 3)
        # off-bucket: padding would corrupt the embedding -> raise
        with pytest.raises(ValueError, match="global output"):
            eng.predict(np.ones((1, 60, 60, 3), np.float32))


def test_predictions_to_masks_rescales_network_flows():
    from bioengine_tpu.ops.flows import (
        masks_to_flows,
        predictions_to_masks,
    )

    masks = np.zeros((48, 48), np.int32)
    masks[6:20, 6:20] = 1
    masks[28:44, 28:44] = 2
    flows = masks_to_flows(masks)
    # Simulate a perfectly-trained network: 5x-scaled flows + logits.
    pred = np.concatenate(
        [
            np.moveaxis(flows * 5.0, 0, -1),
            np.where(masks > 0, 5.0, -5.0)[..., None],
        ],
        axis=-1,
    ).astype(np.float32)
    rec = predictions_to_masks(pred, n_iter=100)
    assert rec.max() == 2


class TestCheckpointService:
    """Orbax-backed train-state checkpoints (runtime/checkpoints.py) —
    SURVEY §5's stretch goal beyond the reference's app-level files."""

    def _tiny_state(self, seed=0):
        import jax
        import jax.numpy as jnp
        import optax

        from bioengine_tpu.models.cellpose import CellposeNet, TrainState

        model = CellposeNet(features=(4, 8), in_channels=2)
        params = model.init(
            jax.random.key(seed), jnp.zeros((1, 16, 16, 2), jnp.float32)
        )["params"]
        return model, TrainState.create(
            model.apply, params, optax.adam(1e-3)
        )

    def test_save_restore_roundtrip(self, tmp_path):
        import jax
        import numpy as np

        from bioengine_tpu.runtime.checkpoints import CheckpointService

        model, state = self._tiny_state()
        with CheckpointService(tmp_path / "ckpt") as ckpt:
            assert ckpt.restore_latest(state) is None  # empty dir
            ckpt.save(0, state)
            ckpt.wait()
            restored = ckpt.restore_latest(state)
        for a, b in zip(
            jax.tree.leaves(state.params), jax.tree.leaves(restored.params)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(restored.step) == int(state.step)

    def test_retention_keeps_newest(self, tmp_path):
        from bioengine_tpu.runtime.checkpoints import CheckpointService

        _, state = self._tiny_state()
        with CheckpointService(tmp_path / "ckpt", max_to_keep=2) as ckpt:
            for step in range(5):
                ckpt.save(step, state)
            ckpt.wait()
            assert ckpt.steps() == [3, 4]
            assert ckpt.latest_step() == 4

    def test_restore_onto_mesh_shards(self, tmp_path):
        """Restore with a sharded template lands leaves on the mesh
        (dp-replicated here) without a host gather."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bioengine_tpu.parallel.mesh import make_mesh
        from bioengine_tpu.runtime.checkpoints import CheckpointService

        _, state = self._tiny_state()
        mesh = make_mesh({"dp": 4}, jax.devices("cpu")[:4])
        sharded_template = jax.device_put(state, NamedSharding(mesh, P()))
        with CheckpointService(tmp_path / "ckpt") as ckpt:
            ckpt.save(7, state)
            ckpt.wait()
            restored = ckpt.restore(7, sharded_template)
        leaf = jax.tree.leaves(restored.params)[0]
        assert len(leaf.sharding.device_set) == 4


class TestEngineStages:
    """The stage timeline (utils/tracing.py): one time_ns() pair per
    stage feeds the process-wide timeline and the PipelineStats sums."""

    # the request's own thread: its stages from submit to the reply
    ON_REQUEST = ("engine.queue", "engine.request", "engine.predict")
    # the stream's issuing thread: the device's stages, and nothing else
    ON_DEVICE = (
        "engine.put", "engine.dispatch", "engine.device_wait", "engine.d2h",
    )
    SUMMED = {
        "engine.cut": "cut_seconds", "engine.put": "put_seconds",
        "engine.dispatch": "dispatch_seconds",
        "engine.device_wait": "device_wait_seconds",
        "engine.d2h": "d2h_seconds", "engine.stitch": "stitch_seconds",
        "engine.queue": "queue_seconds",
    }

    def _engine(self, cls=InferenceEngine, **cfg_overrides):
        cfg_kw = dict(max_tile=64, tile=48, tile_overlap=16, tile_batch=16)
        cfg_kw.update(cfg_overrides)
        return cls(
            "staged",
            lambda p, x: x * p["scale"] + 0.25,
            {"scale": jnp.asarray(1.7)},
            config=EngineConfig(**cfg_kw),
            cache=CompiledProgramCache(),
        )

    @staticmethod
    def _sums(eng):
        stats = eng.pipeline_stats
        return {f: getattr(stats, f) for f in stats._FIELDS}

    @staticmethod
    def _stages_of(eng, since_ns, also=()):
        """The engine's stages since then, the issuing thread's idling
        (``engine.chunk_wait``, no request's own stage) left out."""
        stages = [
            s for s in tracing.get_stages(since_ns)
            if s["thread"].startswith(("dispatch-staged", "pipeline-", *also))
        ]
        idling = [s for s in stages if s["name"] == "engine.chunk_wait"]
        assert {s["thread"] for s in idling} <= {"dispatch-staged-device"}
        return [s for s in stages if s not in idling]

    @pytest.mark.parametrize("path", ["tiled", "direct", "serial"])
    def test_a_prediction_leaves_its_stages(self, path):
        eng = self._engine()
        size = 40 if path == "direct" else 112
        x = np.random.rand(1, size, size, 1).astype(np.float32)
        me = threading.current_thread().name
        if path == "serial":
            def predict(x):
                return eng.predict_serial(x)
        else:
            def predict(x):
                return eng.submit(eng.predict, x).result(timeout=60)
        try:
            predict(x)  # compile outside the count
            before, since = self._sums(eng), time.time_ns()
            out = predict(x)
            after = self._sums(eng)
        finally:
            eng.close()
        np.testing.assert_allclose(out, x * 1.7 + 0.25, rtol=1e-4, atol=1e-5)
        stages = self._stages_of(eng, since, also=(me,))
        by_name = {}
        for s in stages:
            by_name.setdefault(s["name"], []).append(s)
        streamed = path != "serial"
        assert set(by_name) == set(self.ON_DEVICE) | {
            "engine.cut", "engine.stitch"
        } | (set(self.ON_REQUEST) if streamed else set())
        if streamed:
            (request,) = by_name["engine.request"]
            (queue,) = by_name["engine.queue"]
            assert request["request_seq"] > 0
            for s in stages:
                assert s["end_ns"] >= s["start_ns"]
                assert s["request_seq"] == request["request_seq"]
                if s is not queue:  # the wait ends where the request begins
                    assert request["start_ns"] <= s["start_ns"]
                    assert s["end_ns"] <= request["end_ns"]
            assert queue["end_ns"] <= request["start_ns"]
            for name in self.ON_REQUEST:
                assert {s["thread"] for s in by_name[name]} == {
                    "dispatch-staged_0"
                }
            assert after["requests"] - before["requests"] == 1
        device = "dispatch-staged-device" if streamed else me
        for name in self.ON_DEVICE:
            assert {s["thread"] for s in by_name[name]} == {device}
        # a tiled request's tiles are cut and blended beside the device
        # (its own thread divides); a direct batch is filled and cropped
        # where it runs
        assert {s["thread"] for s in by_name["engine.cut"]} == {
            "pipeline-cut" if path == "tiled" else device
        }
        assert {s["thread"] for s in by_name["engine.stitch"]} == (
            {"pipeline-stitch", "dispatch-staged_0"} if path == "tiled"
            else {device}
        )
        # one measurement: the timeline's durations ARE the sums' deltas
        for name, field in self.SUMMED.items():
            assert sum(
                s["duration_s"] for s in by_name.get(name, ())
            ) == pytest.approx(after[field] - before[field], abs=1e-9), name
        assert after["items"] - before["items"] == 1
        assert after["chunks"] - before["chunks"] == len(by_name["engine.put"])
        assert after["chunks_shared"] == 0
        (put,) = by_name["engine.put"]
        assert put["attrs"]["bytes"] == after["h2d_bytes"] - before["h2d_bytes"]

    def test_requests_in_hand_overlap(self):
        """Two requests in one stream: their ``engine.request`` stages
        overlap, the chunk they share is recorded once (under its first
        request), and each keeps its own cut and stitch stages."""
        eng = self._engine(cls=_GatedEngine)
        images = [np.random.rand(1, 112, 112, 1).astype(np.float32) for _ in "ab"]
        plug = np.random.rand(1, 176, 176, 1).astype(np.float32)
        try:
            eng.predict(images[0])  # compile
            futures = eng.served_together(images, plug=plug)
            since = time.time_ns()  # the plug's chunks are on the device
            for f in futures:
                f.result(timeout=60)
        finally:
            eng.gate.set()
            eng.close()
        stages = self._stages_of(eng, since)
        plugged, first, second = sorted(
            (s for s in stages if s["name"] == "engine.request"),
            key=lambda s: s["start_ns"],
        )
        assert first["request_seq"] != second["request_seq"]
        assert second["start_ns"] < first["end_ns"]  # both in hand at once
        assert {first["thread"], second["thread"]} == {
            "dispatch-staged_1", "dispatch-staged_2"
        }
        # 9 + 7 rows in one chunk, 2 in the next: two program calls
        for name in ("engine.put", "engine.dispatch"):
            calls = [s for s in stages if s["name"] == name]
            assert [s["request_seq"] for s in calls] == [
                first["request_seq"], second["request_seq"]
            ]
            assert {s["thread"] for s in calls} == {"dispatch-staged-device"}
        blends = [
            s for s in stages
            if s["name"] == "engine.stitch" and s["thread"] == "pipeline-stitch"
            and s["request_seq"] != plugged["request_seq"]
        ]
        assert [s["request_seq"] for s in blends] == [
            first["request_seq"], second["request_seq"], second["request_seq"]
        ]
        assert eng.pipeline_stats.chunks_shared == 1

    def test_nine_tiles_in_a_chunk_of_sixteen(self):
        eng = self._engine()
        # stride 32 over 112 px: tile starts 0, 32, 64 on each axis
        eng.predict(np.random.rand(1, 112, 112, 1).astype(np.float32))
        stats = eng.pipeline_stats
        assert (stats.rows_useful, stats.rows_executed) == (9, 16)
        assert stats.d2h_bytes == stats.h2d_bytes == 16 * 64 * 64 * 4

    def test_engine_queue_counts_the_wait_for_a_request_thread(self, monkeypatch):
        from bioengine_tpu.runtime import pipeline

        monkeypatch.setattr(pipeline, "REQUEST_THREADS", 1)
        eng = self._engine()
        since = time.time_ns()
        try:
            slow = eng.submit(time.sleep, 0.05)
            fast = eng.submit(lambda: None)
            slow.result(), fast.result()
        finally:
            eng.close()
        queues = [
            s for s in self._stages_of(eng, since) if s["name"] == "engine.queue"
        ]
        assert len(queues) == 2
        assert queues[0]["request_seq"] != queues[1]["request_seq"]
        # the second task waited for the one request thread while the
        # first ran
        assert queues[1]["duration_s"] >= 0.04
        stats = eng.pipeline_stats
        assert stats.requests == 2
        assert stats.queue_seconds == pytest.approx(
            sum(s["duration_s"] for s in queues), abs=1e-9
        )

    def test_requests_do_not_wait_for_each_other_to_be_taken_up(self):
        eng = self._engine()
        since = time.time_ns()
        try:
            slow = eng.submit(time.sleep, 0.2)
            fast = eng.submit(lambda: threading.current_thread().name)
            assert fast.result(timeout=0.15) == "dispatch-staged_1"
            slow.result()
        finally:
            eng.close()
        queues = [
            s for s in self._stages_of(eng, since) if s["name"] == "engine.queue"
        ]
        assert max(s["duration_s"] for s in queues) < 0.1

    def test_timeline_is_bounded(self):
        now = time.time_ns()
        for i in range(tracing.MAX_STAGES + 50):
            tracing.record_stage("bound.test", now + i, now + i + 1)
        stages = tracing.get_stages()
        assert len(stages) == tracing.MAX_STAGES
        # the newest survive, the oldest roll off
        assert stages[-1]["start_ns"] == now + tracing.MAX_STAGES + 49
        assert tracing.get_stages(name="bound.test", max_stages=3) == stages[-3:]
        tracing.clear_stages()

    def test_programs_are_named_after_model_and_shape(self):
        eng = self._engine()
        program = eng._program((2, 64, 64, 1), np.float32)
        assert program.__name__ == "engine_staged_2x64x64x1"
        lowered = program.lower(eng.params, jnp.zeros((2, 64, 64, 1)))
        assert "jit_engine_staged_2x64x64x1" in lowered.as_text()[:400]
