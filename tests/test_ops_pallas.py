"""Pallas kernel correctness vs. plain-XLA reference implementations.

Runs in interpreter mode because conftest names the CPU platform
(JAX_PLATFORMS=cpu) — the same kernel code compiles via Mosaic on TPU,
which chip_smoke.py checks on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bioengine_tpu.ops.attention import (
    attention,
    packed_attention,
    reference_attention,
    traced_paths,
    unpacked_attention,
)
from bioengine_tpu.ops.pallas import attention as kernel_module
from bioengine_tpu.ops.pallas.attention import (
    _block_sizes,
    flash_attention,
    make_attn_fn,
    packed_flash_attention,
    packs,
)


def ref_attention(q, k, v, causal=False):
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhnd,bhmd->bhnm", qf * scale, kf)
    if causal:
        n = q.shape[2]
        mask = np.tril(np.ones((n, n), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhnm,bhmd->bhnd", p, vf).astype(q.dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("n", [128, 200, 257])
    def test_matches_reference(self, n):
        rng = np.random.default_rng(0)
        q, k, v = (
            jnp.asarray(rng.normal(size=(2, 3, n, 64)), jnp.float32)
            for _ in range(3)
        )
        out = flash_attention(q, k, v)
        ref = ref_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_causal(self):
        rng = np.random.default_rng(1)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 2, 200, 32)), jnp.float32)
            for _ in range(3)
        )
        out = flash_attention(q, k, v, causal=True)
        ref = ref_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_interprets_only_under_explicit_cpu(self, cpu_not_asked_for):
        """Every other test here runs the interpreter because the suite
        names the CPU platform; a CPU backend nobody asked for is a
        failed accelerator, and the kernel refuses to stand in for it."""
        from bioengine_tpu.utils.devices import NoAcceleratorError

        q = jnp.zeros((1, 1, 136, 64), jnp.float32)  # a shape no test traced
        with pytest.raises(NoAcceleratorError, match="flash_attention"):
            flash_attention(q, q, q)

    def test_bf16(self):
        rng = np.random.default_rng(2)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 2, 130, 64)), jnp.bfloat16)
            for _ in range(3)
        )
        out = flash_attention(q, k, v)
        ref = ref_attention(q, k, v)
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), atol=2e-2
        )

    def test_non_dividing_blocks_pad_to_lcm(self):
        """block sizes where neither divides the other's max: padding
        must go to lcm so no key block is dropped from the grid."""
        rng = np.random.default_rng(6)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 1, 100, 64)), jnp.float32)
            for _ in range(3)
        )
        out = flash_attention(q, k, v, block_q=128, block_k=96)
        ref = ref_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_nonsquare_blocks(self):
        rng = np.random.default_rng(3)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 1, 300, 64)), jnp.float32)
            for _ in range(3)
        )
        out = flash_attention(q, k, v, block_q=128, block_k=256)
        ref = ref_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_vit_integration(self):
        """The kernel drops into ViT's attn_fn slot and preserves output."""
        from bioengine_tpu.models.vit import ViT

        rng = np.random.default_rng(4)
        images = jnp.asarray(rng.normal(size=(1, 56, 56, 3)), jnp.float32)
        base = ViT(patch_size=14, dim=64, depth=2, num_heads=2)
        params = base.init(jax.random.key(0), images)["params"]
        out_base = base.apply({"params": params}, images)
        flash = ViT(
            patch_size=14, dim=64, depth=2, num_heads=2,
            attn_fn=make_attn_fn(),
        )
        out_flash = flash.apply({"params": params}, images)
        np.testing.assert_allclose(
            np.asarray(out_base), np.asarray(out_flash), atol=5e-2
        )

    def test_grad_flows(self):
        """Interpret-mode kernel is differentiable end-to-end (XLA autodiff
        through the pallas primal) — enough for fine-tune paths on CPU."""
        rng = np.random.default_rng(5)
        q, k, v = (
            jnp.asarray(rng.normal(size=(1, 1, 128, 64)), jnp.float32)
            for _ in range(3)
        )

        def loss(q):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        g = jax.grad(loss)(q)
        assert np.isfinite(np.asarray(g)).all()


class TestFoldedShapes:
    """What cpsam's folded attention asks of the kernel: a q/k depth
    that is not v's, an explicit scale, N off the block grid."""

    @staticmethod
    def _qkv(n, d_qk, d_v, dtype=jnp.float32, seed=7):
        rng = np.random.default_rng(seed)
        return tuple(
            jnp.asarray(rng.normal(size=(2, 2, n, d)), dtype)
            for d in (d_qk, d_qk, d_v)
        )

    # (N, blocks): one kv step with padding, one without, the online
    # path over two kv steps, and over four with two of them all padding
    @pytest.mark.parametrize(
        "n,blocks",
        [(200, {}), (256, {}), (300, dict(block_q=128, block_k=256)),
         (100, dict(block_q=128, block_k=96))],
    )
    def test_depths_differ_and_scale_is_explicit(self, n, blocks):
        q, k, v = self._qkv(n, 92, 64)
        out = flash_attention(q, k * 0.3, v, scale=1.0, **blocks)
        ref = reference_attention(q, k * 0.3, v, scale=1.0)
        assert out.shape == (2, 2, n, 64)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_default_scale_is_the_qk_depth_s(self):
        q, k, v = self._qkv(130, 48, 16)
        np.testing.assert_allclose(
            flash_attention(q, k, v),
            flash_attention(q, k, v, scale=48**-0.5),
            atol=1e-6,
        )
        np.testing.assert_allclose(
            flash_attention(q, k, v), reference_attention(q, k, v),
            atol=2e-5, rtol=2e-5,
        )

    def test_causal_with_two_depths(self):
        q, k, v = self._qkv(200, 96, 32)
        out = flash_attention(q, k, v, causal=True, scale=0.2)
        ref = reference_attention(q, k, v, True, 0.2)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_bf16_operands_stay_bf16(self):
        """bf16 in, bf16 out, within bf16 rounding of the f32 reference."""
        q, k, v = self._qkv(196, 92, 64, jnp.bfloat16)
        out = flash_attention(q, k, v, scale=1.0)
        assert out.dtype == jnp.bfloat16
        ref = reference_attention(q, k, v, scale=1.0)
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), atol=3e-2
        )

    def test_gradients_flow_to_the_wider_depth(self):
        q, k, v = self._qkv(136, 80, 16)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v, scale=1.0) ** 2)

        got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            assert g.shape == w.shape
            np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize(
        "n,expected",
        [
            (196, (256, 256)),      # a SAM window
            (1024, (512, 1024)),    # cpsam's tile: one kv step
            (1025, (384, 1152)),    # ViT-B/14 at 448 px with its CLS token
            (2304, (384, 1152)),    # 18 lane widths: both caps bite
            (4096, (512, 2048)),    # two kv steps
            (896, (128, 896)),      # 7 lane widths: no divisor under the cap
        ],
    )
    def test_block_sizes_come_from_n(self, n, expected):
        block_q, block_k = _block_sizes(n)
        assert (block_q, block_k) == expected
        padded = -(-n // 128) * 128
        assert padded % block_q == 0 and padded % block_k == 0


class TestAttentionDispatch:
    """``ops.attention.attention``: the reference off the TPU, the kernel
    on it, and a counter that says which."""

    def test_cpu_takes_the_reference_and_counts_xla(self):
        q, k, v = TestFoldedShapes._qkv(72, 40, 8)
        before = traced_paths()
        out = attention(q, k, v, scale=1.0)
        assert traced_paths(since=before) == {"xla:72": 1}
        np.testing.assert_array_equal(
            out, reference_attention(q, k, v, scale=1.0)
        )

    def test_tpu_backend_takes_the_kernel_and_counts_fused(self, monkeypatch):
        """The backend pretended, the kernel interpreted: the choice is
        ``jax.default_backend()``'s and nothing else's."""
        calls = []

        def interpreted(q, k, v, **kwargs):
            calls.append(kwargs)
            return flash_attention(q, k, v, interpret=True, **kwargs)

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(kernel_module, "flash_attention", interpreted)
        q, k, v = TestFoldedShapes._qkv(72, 40, 8)
        before = traced_paths()
        out = attention(q, k, v, scale=1.0)
        assert traced_paths(since=before) == {"fused:72": 1}
        assert calls == [{"scale": 1.0}]
        np.testing.assert_allclose(
            out, reference_attention(q, k, v, scale=1.0), atol=2e-5, rtol=2e-5
        )

    def test_program_cache_keeps_the_rise_of_a_build(self):
        from bioengine_tpu.runtime.program_cache import CompiledProgramCache

        q, k, v = TestFoldedShapes._qkv(40, 24, 8)
        cache = CompiledProgramCache()

        def build():
            fn = jax.jit(
                lambda q, k, v: attention(attention(q, k, q), k, v)
            )
            fn(q, k, v)
            return fn

        cache.get_or_compile(("two-attentions", 40), build)
        cache.get_or_compile(("none", 0), lambda: None)
        info = cache.compile_info_snapshot()
        assert info[str(("two-attentions", 40))]["attention_paths"] == {
            "xla:40": 2
        }
        assert info[str(("none", 0))]["attention_paths"] == {}
        cache.evict(lambda key: True)
        assert cache.stats.attention_paths == {}


class TestPartitionedUnderGspmd:
    """A Mosaic call cannot be partitioned automatically: lowering one
    inside a multi-device jit raises. Where its operands belong to a
    mesh the kernel wraps itself in a ``shard_map`` over the batch, so
    the engine's dp-sharded batch and the dp fine-tune step keep
    working, each device on its own shard."""

    @staticmethod
    def _operands(devices, spec, axes):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(devices).reshape(*axes.values()), tuple(axes))
        q, k, v = TestFoldedShapes._qkv(100, 48, 16, seed=11)
        q, k, v = (jnp.concatenate([a] * 4) for a in (q, k, v))  # batch 8
        sharded = tuple(
            jax.device_put(a, NamedSharding(mesh, P(*spec))) for a in (q, k, v)
        )
        return (q, k, v), sharded

    def test_batch_sharded_runs_per_shard_with_no_collective(self, devices):
        plain, sharded = self._operands(devices[:4], ("dp",), {"dp": 4})
        fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, scale=1.0))
        out = fn(*sharded)
        assert out.sharding.spec[0] == "dp"
        np.testing.assert_allclose(
            out, reference_attention(*plain, scale=1.0), atol=2e-5, rtol=2e-5
        )
        hlo = fn.lower(*sharded).compile().as_text()
        assert "all-gather" not in hlo and "all-reduce" not in hlo

    def test_batch_that_does_not_divide_takes_the_reference(
        self, devices, monkeypatch
    ):
        """Six items over four devices: the kernel refuses, and
        ``attention`` does not ask it."""
        _, sharded = self._operands(devices[:4], (), {"dp": 4})
        six = tuple(a[:6] for a in sharded)
        with pytest.raises(ValueError, match="does not divide"):
            jax.jit(lambda q, k, v: flash_attention(q, k, v, scale=1.0))(*six)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = traced_paths()
        jax.jit(lambda q, k, v: attention(q, k, v, scale=1.0))(*six)
        assert traced_paths(since=before) == {"xla:100": 1}

    def test_sequence_sharded_operands_are_gathered_not_miscomputed(
        self, devices
    ):
        plain, sharded = self._operands(
            devices[:4], ("dp", None, "sp"), {"dp": 2, "sp": 2}
        )
        out = jax.jit(lambda q, k, v: flash_attention(q, k, v, scale=1.0))(
            *sharded
        )
        np.testing.assert_allclose(
            out, reference_attention(*plain, scale=1.0), atol=2e-5, rtol=2e-5
        )

    def test_gradients_under_a_dp_mesh(self, devices):
        plain, sharded = self._operands(devices[:4], ("dp",), {"dp": 4})

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v, scale=1.0) ** 2)

        got = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))(
            *sharded
        )
        want = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(*plain)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)


def _packed_operands(grid, heads, hd=64, batch=2, dtype=jnp.float32, seed=13):
    """What ``SAMAttention`` hands ``packed_attention``: the projection's
    output and the two relative-position tables at the grid's extent."""
    H, W = grid
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.normal(size=(batch, H * W, 3 * heads * hd)), dtype),
        jnp.asarray(0.3 * rng.normal(size=(2 * H - 1, hd)), dtype),
        jnp.asarray(0.3 * rng.normal(size=(2 * W - 1, hd)), dtype),
    )


def _unpacked_reference(qkv, rel_h, rel_w, grid, heads):
    return unpacked_attention(
        reference_attention, qkv, rel_h, rel_w, grid, heads
    )


class TestPackedAttention:
    """The packed entry, interpreted, at a toy width with hd 64: 128-lane
    head pairs cut out of the projection's output, the bias rows, q' and
    k' formed in the kernel. The reference is the plain one over the
    unpacked ``(B, heads, N, .)`` operands."""

    @pytest.mark.parametrize(
        "grid,dtype,atol",
        [
            ((32, 32), jnp.float32, 5e-5),
            ((32, 32), jnp.bfloat16, 3e-2),
            # rows and columns of unequal extent: the two tables' lanes
            # and both rotations differ
            ((16, 48), jnp.float32, 5e-5),
            ((48, 16), jnp.bfloat16, 3e-2),
        ],
    )
    def test_matches_the_reference_over_unpacked_operands(
        self, grid, dtype, atol
    ):
        operands = _packed_operands(grid, heads=4, dtype=dtype)
        out = packed_flash_attention(*operands, grid=grid, heads=4)
        ref = _unpacked_reference(*operands, grid, 4)
        assert out.shape == ref.shape == (2, grid[0] * grid[1], 256)
        assert out.dtype == dtype
        assert float(jnp.abs(ref.astype(jnp.float32)).max()) > 1.0
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), atol=atol
        )

    def test_the_bias_is_seen(self):
        """The comparison has teeth: with the two tables swapped the
        result moves far beyond the tolerance."""
        qkv, rel_h, rel_w = _packed_operands((32, 32), heads=2)
        out = packed_flash_attention(qkv, rel_h, rel_w, grid=(32, 32), heads=2)
        wrong = _unpacked_reference(qkv, rel_w, rel_h, (32, 32), 2)
        assert float(jnp.abs(out - wrong).max()) > 1e-2

    def test_gradients_are_the_reference_s(self):
        grid, heads = (32, 32), 2
        operands = _packed_operands(grid, heads, batch=1)
        weights = jnp.asarray(
            np.random.default_rng(5).normal(size=(1, 1024, 128)), jnp.float32
        )

        def packed(*operands):
            out = packed_flash_attention(*operands, grid=grid, heads=heads)
            return jnp.sum(out * weights)

        def plain(*operands):
            return jnp.sum(_unpacked_reference(*operands, grid, heads) * weights)

        got = jax.grad(packed, argnums=(0, 1, 2))(*operands)
        want = jax.grad(plain, argnums=(0, 1, 2))(*operands)
        for g, w, name in zip(got, want, ("qkv", "rel_h", "rel_w")):
            assert g.shape == w.shape
            assert float(jnp.abs(w).max()) > 1e-3, name
            np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4, err_msg=name)

    def test_refuses_what_does_not_pack(self):
        operands = _packed_operands((14, 14), heads=2)
        with pytest.raises(ValueError, match="does not pack"):
            packed_flash_attention(*operands, grid=(14, 14), heads=2)

    # (grid, heads, hd) -> whether two heads and their bias rows fill the
    # lanes, the sequence is one unpadded kv step, and a q block holds
    # whole rows of the grid
    @pytest.mark.parametrize(
        "grid,heads,hd,expected",
        [
            ((32, 32), 16, 64, True),    # cpsam's global block
            ((16, 48), 4, 64, True),
            ((14, 14), 16, 64, False),   # SAM's window: 64 + 28 lanes
            ((64, 64), 16, 64, False),   # 64 + 128 lanes, two kv steps
            ((16, 16), 4, 32, False),    # four heads to the lane width
            ((32, 32), 3, 64, False),    # a head without its pair
            ((30, 34), 2, 64, False),    # 1020 tokens: padding
            ((60, 4), 2, 64, False),     # rows of half a sublane tile
        ],
    )
    def test_which_shapes_pack(self, grid, heads, hd, expected):
        n = grid[0] * grid[1]
        assert packs(n, heads * hd, grid, heads) is expected


class TestPackedDispatch:
    """``ops.attention.packed_attention``: the packed kernel where the
    backend is a TPU and the lanes line up, everything else unpacked to
    ``attention``; the counter says which. Tracing alone counts, so the
    shapes are traced (``eval_shape``), not run."""

    @staticmethod
    def _trace(grid, heads, hd=64, batch=2):
        H, W = grid
        shapes = (
            jax.ShapeDtypeStruct((batch, H * W, 3 * heads * hd), jnp.bfloat16),
            jax.ShapeDtypeStruct((2 * H - 1, hd), jnp.bfloat16),
            jax.ShapeDtypeStruct((2 * W - 1, hd), jnp.bfloat16),
        )
        before = traced_paths()
        out = jax.eval_shape(
            lambda *a: packed_attention(*a, grid=grid, heads=heads), *shapes
        )
        assert out.shape == (batch, H * W, heads * hd)
        return traced_paths(since=before)

    def test_cpu_unpacks_to_the_reference(self):
        assert self._trace((32, 32), 4) == {"xla:1024": 1}

    @pytest.mark.parametrize(
        "grid,heads,hd,expected",
        [
            ((32, 32), 16, 64, {"packed:1024": 1}),
            ((16, 48), 4, 64, {"packed:768": 1}),
            ((14, 14), 16, 64, {"fused:196": 1}),
            ((64, 64), 16, 64, {"fused:4096": 1}),
            ((16, 16), 4, 32, {"fused:256": 1}),
            ((32, 32), 3, 64, {"fused:1024": 1}),
        ],
    )
    def test_tpu_backend_packs_where_the_lanes_line_up(
        self, monkeypatch, grid, heads, hd, expected
    ):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert self._trace(grid, heads, hd) == expected

    def test_tpu_backend_runs_the_packed_kernel(self, monkeypatch):
        """The backend pretended, the kernel interpreted: same result
        as the CPU's unpacked reference."""
        calls = []

        def interpreted(*operands, **kwargs):
            calls.append(kwargs)
            return packed_flash_attention(*operands, interpret=True, **kwargs)

        operands = _packed_operands((32, 32), heads=2)
        want = packed_attention(*operands, grid=(32, 32), heads=2)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            kernel_module, "packed_flash_attention", interpreted
        )
        got = packed_attention(*operands, grid=(32, 32), heads=2)
        assert calls == [{"grid": (32, 32), "heads": 2}]
        np.testing.assert_allclose(got, want, atol=5e-5)

    def test_program_cache_records_the_packed_depth(self, monkeypatch):
        from bioengine_tpu.runtime.program_cache import CompiledProgramCache

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cache = CompiledProgramCache()

        def build():
            self._trace((32, 32), 4)
            self._trace((32, 32), 4)
            self._trace((14, 14), 4)

        cache.get_or_compile(("three-blocks", 1024), build)
        info = cache.compile_info_snapshot()
        assert info[str(("three-blocks", 1024))]["attention_paths"] == {
            "packed:1024": 2, "fused:196": 1,
        }


class TestPackedUnderGspmd:
    """The packed call under a CPU ``dp`` mesh: per shard, the tables
    whole on every device, no collective."""

    @staticmethod
    def _operands(devices):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(devices), ("dp",))
        qkv, rel_h, rel_w = _packed_operands((32, 32), heads=2, batch=8)
        sharded = (
            jax.device_put(qkv, NamedSharding(mesh, P("dp"))),
            jax.device_put(rel_h, NamedSharding(mesh, P())),
            jax.device_put(rel_w, NamedSharding(mesh, P())),
        )
        return (qkv, rel_h, rel_w), sharded

    def test_batch_sharded_runs_per_shard_with_no_collective(self, devices):
        plain, sharded = self._operands(devices[:4])
        fn = jax.jit(
            lambda *a: packed_flash_attention(*a, grid=(32, 32), heads=2)
        )
        out = fn(*sharded)
        assert out.sharding.spec[0] == "dp"
        np.testing.assert_allclose(
            out, _unpacked_reference(*plain, (32, 32), 2), atol=5e-5
        )
        hlo = fn.lower(*sharded).compile().as_text()
        assert "all-gather" not in hlo and "all-reduce" not in hlo

    def test_batch_that_does_not_divide_takes_the_reference(
        self, devices, monkeypatch
    ):
        _, (qkv, rel_h, rel_w) = self._operands(devices[:4])
        six = (qkv[:6], rel_h, rel_w)
        with pytest.raises(ValueError, match="does not divide"):
            jax.jit(
                lambda *a: packed_flash_attention(*a, grid=(32, 32), heads=2)
            )(*six)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = traced_paths()
        jax.jit(lambda *a: packed_attention(*a, grid=(32, 32), heads=2))(*six)
        assert traced_paths(since=before) == {"xla:1024": 1}

    def test_gradients_under_a_dp_mesh(self, devices):
        plain, sharded = self._operands(devices[:4])

        def loss(fn):
            return lambda *a: jnp.sum(fn(*a) ** 2)

        got = jax.jit(
            jax.grad(
                loss(
                    lambda *a: packed_flash_attention(
                        *a, grid=(32, 32), heads=2
                    )
                ),
                argnums=(0, 1, 2),
            )
        )(*sharded)
        want = jax.grad(
            loss(lambda *a: _unpacked_reference(*a, (32, 32), 2)),
            argnums=(0, 1, 2),
        )(*plain)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-3, rtol=1e-4)


@pytest.fixture(scope="module")
def one_chip():
    """One described (not attached) v5e chip: the TPU compiler is
    installed here and refuses what the chip's would refuse. Described
    inside a fixture, never at import (only one process may load the
    TPU library; a worker that cannot skips these tests)."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


class TestMosaicAcceptsTheServedShapes:
    """The kernel compiled by Mosaic for a v5e at the widths the repo
    serves. Nothing runs and nothing is measured; what is refused here
    would have cost a chip call."""

    @pytest.mark.parametrize(
        "shape_qk,d_v,kwargs",
        [
            # cpsam-vitl's served program: 16 tiles x 16 heads, folded
            ((16, 16, 1024, 128), 64, dict(scale=1.0)),
            # CpSAM's default windows under fine-tuning: 14 x 14 tokens
            ((48, 16, 196, 92), 64, dict(scale=1.0)),
            # the embedder, ViT-B/14 at 448 px
            ((2, 12, 1025, 64), 64, dict()),
            ((2, 12, 1025, 64), 64, dict(causal=True)),
            # beyond MAX_BLOCK_K: the online path over two kv steps
            ((1, 16, 4096, 128), 64, dict(scale=1.0)),
        ],
    )
    def test_compiles_for_v5e(self, one_chip, no_compile_cache, shape_qk, d_v, kwargs):
        def spec(shape):
            return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

        compiled = (
            jax.jit(
                lambda q, k, v: flash_attention(
                    q, k, v, interpret=False, **kwargs
                )
            )
            .trace(spec(shape_qk), spec(shape_qk), spec(shape_qk[:3] + (d_v,)))
            .lower(lowering_platforms=("tpu",))
            .compile()
        )
        assert "tpu_custom_call" in compiled.as_text()


class TestTheServedBlockHasNoRelayout:
    """One cpsam block as the benchmark's configuration serves it,
    compiled for a described v5e with the backend pretended a TPU: the
    packed kernel is in it, and between the qkv projection and the
    output projection nothing relays an attention operand. These passes
    were 42 % of the served step (PERF.md section 6, PR 31); an edit that
    brings one back fails here before it costs a chip run."""

    RELAYOUTS = (
        "copy bf16[16,1024,3,16,64]",
        "reshape bf16[16,1024,3,16,64]",
        "copy bf16[16,16,1024,64]",
        "copy bf16[16,16,1024,128]",
        "pad_maximum_fusion bf16[256,1024,128]",
        "broadcast bf16[16,16,1024,64]",
        "copy bf16[16,16,32,32,32]",
        "copy bf16[16,1024,16,64]",
    )

    def test_compiles_for_v5e_without_them(
        self, one_chip, no_compile_cache, monkeypatch
    ):
        import re

        from bioengine_tpu.models.sam import SAMBlock

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        block = SAMBlock(1024, 16, 4.0, 0, 32)
        params = jax.eval_shape(
            block.init, jax.random.key(0),
            jax.ShapeDtypeStruct((1, 32, 32, 1024), jnp.bfloat16),
        )
        on_chip = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            (params, jax.ShapeDtypeStruct((16, 32, 32, 1024), jnp.bfloat16)),
        )
        before = traced_paths()
        text = (
            jax.jit(block.apply)
            .trace(*on_chip)
            .lower(lowering_platforms=("tpu",))
            .compile()
            .as_text()
        )
        assert traced_paths(since=before) == {"packed:1024": 1}
        assert "tpu_custom_call" in text and "packed_attention" in text
        entry = text[text.index("ENTRY"):]
        # "%name = type[shape]{layout} opcode(" -> "opcode-or-fusion-kind type[shape]"
        produced = {
            f"{re.sub(r'[.][0-9]+$', '', name) if op == 'fusion' else op} {shape}"
            for name, shape, op in re.findall(
                r"%([\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(", entry
            )
        }
        # the pattern reads this dump: the qkv projection is in it
        assert any(p.endswith("bf16[16,32,32,3072]") for p in produced)
        assert not produced & set(self.RELAYOUTS)
        # nothing at all the size of an attention operand but the
        # projection's output, the kernel's and the block's own
        assert not {
            p for p in produced
            if re.search(r"bf16\[16,(16,1024|1024,16|1024,3,16),", p)
        }
