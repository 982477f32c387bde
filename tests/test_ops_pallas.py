"""Pallas kernel correctness vs. plain-XLA reference implementations.

Runs in interpreter mode because conftest names the CPU platform
(JAX_PLATFORMS=cpu) — the same kernel code compiles via Mosaic on TPU,
which chip_smoke.py checks on the chip.
"""

import contextlib
import re

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
from bioengine_tpu.ops import mlp as mlp_ops
from bioengine_tpu.ops.mlp import gelu, mlp, reference_mlp
from bioengine_tpu.ops.pallas import attention as kernel_module
from bioengine_tpu.ops.pallas import mlp as mlp_kernel
from bioengine_tpu.ops.pallas.attention import (
    _block_sizes,
    flash_attention,
    make_attn_fn,
    packed_flash_attention,
    packs,
)
from bioengine_tpu.ops.pallas.mlp import Tiles, fused_mlp, tiles


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


def _mlp_operands(shape, hidden, dtype=jnp.float32, seed=17):
    """What ``SAMBlock`` hands ``ops.mlp.mlp``: the normalised tokens,
    the two layers as the parameter tree holds them (f32) and the
    shortcut. Biases away from zero, so a dropped one is seen."""
    dim = shape[-1]
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.normal(size=shape), dtype),
        jnp.asarray(rng.normal(size=(dim, hidden)) * dim**-0.5, jnp.float32),
        jnp.asarray(rng.normal(size=(hidden,)), jnp.float32),
        jnp.asarray(rng.normal(size=(hidden, dim)) * hidden**-0.5, jnp.float32),
        jnp.asarray(rng.normal(size=(dim,)), jnp.float32),
        jnp.asarray(rng.normal(size=shape), dtype),
    )


MLP_OPERANDS = ("y", "w1", "b1", "w2", "b2", "shortcut")


class TestGelu:
    """``ops.mlp.gelu``: the one statement of the SAM block's exact GELU,
    in its erf form, which the reference and the kernel both evaluate."""

    @staticmethod
    def _every_bf16():
        bits = np.arange(65536, dtype=np.uint32) << 16
        return bits.view(np.float32)

    @pytest.mark.parametrize("jitted", [False, True])
    def test_every_bf16_input_is_within_one_ulp_of_exact(self, jitted):
        """All 65,536 bf16 values: the finite ones, rounded to bf16 as
        the second product takes them, within one bf16 ulp of float64
        exact GELU or 2**-20 absolute, whichever is larger; the far
        negative tail is exactly -0."""
        import math

        x = self._every_bf16()
        finite = np.isfinite(x)
        fn = jax.jit(gelu) if jitted else gelu
        got = fn(jnp.asarray(x).astype(jnp.bfloat16))
        assert got.dtype == jnp.float32
        got = np.asarray(
            got.astype(jnp.bfloat16).astype(jnp.float32), np.float64
        )[finite]
        exact = np.array(
            [0.5 * v * math.erfc(-v / math.sqrt(2)) for v in x[finite].astype(np.float64)]
        )
        with np.errstate(divide="ignore"):
            ulp = np.where(
                exact != 0, 2.0 ** (np.floor(np.log2(np.abs(exact))) - 7), 0
            )
        worst = np.abs(got - exact) / np.maximum(ulp, 2.0**-20)
        assert worst.max() <= 1.0, x[finite][np.argmax(worst)]
        far = x[finite] < -6
        assert np.array_equal(got[far], np.zeros(far.sum()))
        assert np.signbit(got[far]).all()

    def test_non_finite_inputs_read_as_the_erfc_form_has_them(self):
        """+inf -> +inf, -inf and NaN -> NaN, exactly what
        ``nn.gelu(approximate=False)`` (0.5 x erfc(-x / sqrt 2)) gives."""
        x = self._every_bf16()
        odd = jnp.asarray(x[~np.isfinite(x)])
        assert odd.shape == (256,)
        np.testing.assert_array_equal(
            gelu(odd), jax.nn.gelu(odd, approximate=False)
        )

    def test_equals_the_erfc_form_where_it_is_not_a_rounding(self):
        """Against jax's own exact GELU in f32 over the range a network
        visits: the same function, another way of writing it."""
        x = jnp.linspace(-9.0, 9.0, 200001, dtype=jnp.float32)
        np.testing.assert_allclose(
            gelu(x), jax.nn.gelu(x, approximate=False), atol=1e-6, rtol=2e-6
        )


class TestFusedMlp:
    """The MLP kernel, interpreted, against ``reference_mlp``. The f32
    tolerance is the interpreter's: it evaluates the reciprocal estimate
    in bf16, where the chip's is good to 1.6e-5 and the Newton step
    takes it to 1.4e-7 (chip run of PR 36), so the kernel's erf is
    within 2e-5 here and within an f32 ulp there."""

    # shape of y, hidden -> the tiles in the comment
    SHAPES = {
        "one-tile": ((2, 64, 128), 256),            # (128, 256)
        "three-row-tiles": ((3, 8, 16, 128), 384),  # (128, 128) x 3 x 3
        "two-hidden-blocks": ((256, 256), 2048),    # (256, 1024) x 1 x 2
    }

    @pytest.mark.parametrize("case", list(SHAPES))
    @pytest.mark.parametrize(
        "dtype,atol", [(jnp.float32, 1e-4), (jnp.bfloat16, 6e-2)]
    )
    def test_matches_the_reference(self, case, dtype, atol):
        shape, hidden = self.SHAPES[case]
        operands = _mlp_operands(shape, hidden, dtype)
        out = fused_mlp(*operands)
        ref = reference_mlp(*operands)
        assert out.shape == ref.shape == shape and out.dtype == dtype
        assert float(jnp.abs(ref.astype(jnp.float32)).max()) > 3.0
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), atol=atol
        )

    def test_the_grid_is_what_the_case_says(self):
        assert tiles(128, 128, 256, jnp.float32) == Tiles(128, 256)
        assert tiles(384, 128, 384, jnp.float32) == Tiles(128, 128)
        assert tiles(256, 256, 2048, jnp.float32) == Tiles(256, 1024)

    @pytest.mark.parametrize("dropped", ["b1", "b2", "shortcut"])
    def test_every_operand_is_seen(self, dropped):
        """The comparison has teeth: with one bias or the shortcut
        zeroed the reference moves far beyond the tolerance."""
        operands = dict(zip(MLP_OPERANDS, _mlp_operands((2, 64, 128), 256)))
        out = fused_mlp(*operands.values())
        operands[dropped] = jnp.zeros_like(operands[dropped])
        wrong = reference_mlp(*operands.values())
        assert float(jnp.abs(out - wrong).max()) > 0.1

    def test_gradients_are_the_reference_s(self):
        operands = _mlp_operands((256, 128), 384)
        weights = jnp.asarray(
            np.random.default_rng(5).normal(size=(256, 128)), jnp.float32
        )

        def loss(fn):
            return lambda *a: jnp.sum(fn(*a) * weights)

        argnums = tuple(range(6))
        got = jax.grad(loss(fused_mlp), argnums)(*operands)
        want = jax.grad(loss(reference_mlp), argnums)(*operands)
        for g, w, name in zip(got, want, MLP_OPERANDS):
            assert g.shape == w.shape
            assert float(jnp.abs(w).max()) > 1e-3, name
            np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5, err_msg=name)

    def test_refuses_what_has_no_tiles(self):
        operands = _mlp_operands((200, 128), 256)
        with pytest.raises(ValueError, match="has no tiles"):
            fused_mlp(*operands)

    def test_interprets_only_under_explicit_cpu(self, cpu_not_asked_for):
        from bioengine_tpu.utils.devices import NoAcceleratorError

        operands = _mlp_operands((128, 256), 128)  # a shape no test traced
        with pytest.raises(NoAcceleratorError, match="fused_mlp"):
            fused_mlp(*operands)

    # (rows, dim, hidden) -> tiles or None: whole lane widths, the rows a
    # multiple of a row tile, the largest tiles that fit the VMEM asked for
    @pytest.mark.parametrize(
        "rows,dim,hidden,expected",
        [
            (16384, 1024, 4096, Tiles(1024, 1024)),  # the served program
            (8192, 1024, 4096, Tiles(1024, 1024)),   # fine-tuning, batch 8
            (1536, 1024, 4096, Tiles(512, 1024)),    # three row tiles
            (1024, 768, 3072, Tiles(1024, 1024)),    # ViT-B widths
            (1024, 1024, 4224, Tiles(1024, 128)),     # 33 lane widths
            (16384, 1024, 4000, None),    # hidden off the lanes
            (16384, 1000, 4096, None),    # dim off the lanes
            (16384 + 64, 1024, 4096, None),  # rows no tile divides
            (200, 128, 256, None),
            (16384, 64, 256, None),       # a toy width
        ],
    )
    def test_which_shapes_have_tiles(self, rows, dim, hidden, expected):
        assert tiles(rows, dim, hidden, jnp.bfloat16) == expected
        if expected is not None:
            assert mlp_kernel._vmem_bytes(expected, dim, 2) <= mlp_kernel.VMEM_LIMIT


class TestMlpDispatch:
    """``ops.mlp.mlp``: the kernel where the backend is a TPU and the
    shapes have tiles, the reference anywhere else; the counter says
    which. Tracing alone counts, so most shapes are traced
    (``eval_shape``), not run."""

    @staticmethod
    def _trace(shape, hidden, dtype=jnp.bfloat16):
        dim = shape[-1]
        shapes = (
            jax.ShapeDtypeStruct(shape, dtype),
            jax.ShapeDtypeStruct((dim, hidden), jnp.float32),
            jax.ShapeDtypeStruct((hidden,), jnp.float32),
            jax.ShapeDtypeStruct((hidden, dim), jnp.float32),
            jax.ShapeDtypeStruct((dim,), jnp.float32),
            jax.ShapeDtypeStruct(shape, dtype),
        )
        before = mlp_ops.traced_paths()
        # a new function each time: ``eval_shape`` keeps its traces
        out = jax.eval_shape(lambda *a: mlp(*a), *shapes)
        assert out.shape == shape and out.dtype == dtype
        return mlp_ops.traced_paths(since=before)

    def test_cpu_takes_the_reference_and_counts_xla(self):
        operands = _mlp_operands((2, 64, 128), 256)
        before = mlp_ops.traced_paths()
        out = mlp(*operands)
        assert mlp_ops.traced_paths(since=before) == {"xla:128": 1}
        np.testing.assert_array_equal(out, reference_mlp(*operands))
        assert self._trace((16, 32, 32, 1024), 4096) == {"xla:16384": 1}

    @pytest.mark.parametrize(
        "shape,hidden,expected",
        [
            ((16, 32, 32, 1024), 4096, {"fused:16384": 1}),  # served
            ((8, 32, 32, 1024), 4096, {"fused:8192": 1}),    # fine-tuning
            ((2, 8, 8, 128), 512, {"fused:128": 1}),
            ((16, 32, 32, 1024), 4000, {"xla:16384": 1}),    # hidden % 128
            ((2, 10, 10, 128), 512, {"xla:200": 1}),         # no row tile
            ((2, 32, 32, 32), 128, {"xla:2048": 1}),         # tier-1's widths
        ],
    )
    def test_tpu_backend_fuses_where_the_shapes_have_tiles(
        self, monkeypatch, shape, hidden, expected
    ):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert self._trace(shape, hidden) == expected

    def test_tpu_backend_runs_the_kernel(self, monkeypatch):
        """The backend pretended, the kernel interpreted: the choice is
        ``jax.default_backend()``'s and the shapes', nothing else's."""
        calls = []

        def interpreted(*operands, **kwargs):
            calls.append(kwargs)
            return fused_mlp(*operands, interpret=True, **kwargs)

        operands = _mlp_operands((2, 64, 128), 256)
        want = mlp(*operands)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(mlp_kernel, "fused_mlp", interpreted)
        before = mlp_ops.traced_paths()
        got = mlp(*operands)
        assert mlp_ops.traced_paths(since=before) == {"fused:128": 1}
        assert calls == [{}]
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_program_cache_keeps_the_rise_of_a_build(self, monkeypatch):
        from bioengine_tpu.runtime.program_cache import CompiledProgramCache

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cache = CompiledProgramCache()

        def build():
            self._trace((16, 32, 32, 1024), 4096)
            self._trace((16, 32, 32, 1024), 4096)
            self._trace((2, 10, 10, 128), 512)

        cache.get_or_compile(("three-blocks", 16384), build)
        cache.get_or_compile(("none", 0), lambda: None)
        info = cache.compile_info_snapshot()
        assert info[str(("three-blocks", 16384))]["mlp_paths"] == {
            "fused:16384": 2, "xla:200": 1,
        }
        assert info[str(("three-blocks", 16384))]["attention_paths"] == {}
        assert info[str(("none", 0))]["mlp_paths"] == {}
        cache.evict(lambda key: True)
        assert cache.stats.mlp_paths == {}


class TestMlpUnderGspmd:
    """The MLP kernel under a CPU ``dp`` mesh: per shard, the weights
    whole on every device, no collective."""

    @staticmethod
    def _operands(devices, batch=8):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(devices), ("dp",))
        plain = _mlp_operands((batch, 4, 32, 128), 256)
        sharded = tuple(
            jax.device_put(
                a, NamedSharding(mesh, P("dp") if a.ndim == 4 else P())
            )
            for a in plain
        )
        return plain, sharded

    def test_batch_sharded_runs_per_shard_with_no_collective(self, devices):
        plain, sharded = self._operands(devices[:4])
        fn = jax.jit(fused_mlp)
        out = fn(*sharded)
        assert out.sharding.spec[0] == "dp"
        np.testing.assert_allclose(out, reference_mlp(*plain), atol=1e-4)
        hlo = fn.lower(*sharded).compile().as_text()
        assert "all-gather" not in hlo and "all-reduce" not in hlo

    def test_batch_that_does_not_divide_takes_the_reference(
        self, devices, monkeypatch
    ):
        """Six items over four devices: the kernel refuses, and ``mlp``
        does not ask it, though a row tile divides the rows."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(devices[:4]), ("dp",))
        six = tuple(
            jax.device_put(a, NamedSharding(mesh, P()))
            for a in _mlp_operands((6, 4, 32, 128), 256)
        )
        assert tiles(6 * 4 * 32, 128, 256, jnp.float32) is not None
        with pytest.raises(ValueError, match="does not divide"):
            jax.jit(fused_mlp)(*six)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = mlp_ops.traced_paths()
        jax.jit(mlp)(*six)
        assert mlp_ops.traced_paths(since=before) == {"xla:768": 1}

    def test_gradients_under_a_dp_mesh(self, devices):
        plain, sharded = self._operands(devices[:4])

        def loss(fn):
            return lambda *a: jnp.sum(fn(*a) ** 2)

        argnums = tuple(range(6))
        got = jax.jit(jax.grad(loss(fused_mlp), argnums))(*sharded)
        want = jax.grad(loss(reference_mlp), argnums)(*plain)
        for g, w, name in zip(got, want, MLP_OPERANDS):
            # sums over four shards round in another order than over one
            np.testing.assert_allclose(
                g, w, atol=2e-5 * float(jnp.abs(w).max()), rtol=1e-4,
                err_msg=name,
            )


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


@contextlib.contextmanager
def _compile_cache_off():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture()
def no_compile_cache():
    with _compile_cache_off():
        yield


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

    @pytest.mark.parametrize(
        "shape",
        [
            (16, 32, 32, 1024),  # cpsam-vitl's served program: 16 tiles
            (8, 32, 32, 1024),   # CpSAM under fine-tuning: batch 8 of 256 px
        ],
    )
    def test_mlp_compiles_for_v5e(self, one_chip, no_compile_cache, shape):
        """bf16 tokens, the f32 weights of the parameter tree, the VMEM
        the tiles ask for: what Mosaic refuses here (a tile it cannot
        lay out, more VMEM than the chip has) would have cost a chip
        call."""
        dim, hidden = shape[-1], 4 * shape[-1]

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        compiled = (
            jax.jit(lambda *a: fused_mlp(*a, interpret=False))
            .trace(
                spec(shape, jnp.bfloat16),
                spec((dim, hidden), jnp.float32),
                spec((hidden,), jnp.float32),
                spec((hidden, dim), jnp.float32),
                spec((dim,), jnp.float32),
                spec(shape, jnp.bfloat16),
            )
            .lower(lowering_platforms=("tpu",))
            .compile()
        )
        text = compiled.as_text()
        assert "tpu_custom_call" in text and "fused_mlp" in text


class TestTheServedBlockIsItsKernels:
    """One cpsam block as the benchmark's configuration serves it,
    compiled for a described v5e with the backend pretended a TPU (the
    model code asks it). Read from the optimised program: the packed
    attention kernel is in it and between the qkv projection and the
    output projection nothing relays an attention operand (those passes
    were 42 % of the served step; PERF.md section 6, PR 31); the MLP is
    the one fused kernel, its hidden activation is nowhere in HBM, and
    no matmul fusion carries an ``exponential`` (the erfc chain in
    ``mlp_lin2``'s operand prologue and the bitmask beside ``mlp_lin1``
    were a fifth of the step; PR 36). An edit that brings either back
    fails here before it costs a chip run."""

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

    @pytest.fixture(scope="class")
    def served_block(self, one_chip):
        """(optimised HLO text, attention paths, MLP paths) of one
        compile, shared by the tests below."""
        from bioengine_tpu.models.sam import SAMBlock

        block = SAMBlock(1024, 16, 4.0, 0, 32)
        params = jax.eval_shape(
            block.init, jax.random.key(0),
            jax.ShapeDtypeStruct((1, 32, 32, 1024), jnp.bfloat16),
        )
        on_chip = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            (params, jax.ShapeDtypeStruct((16, 32, 32, 1024), jnp.bfloat16)),
        )
        before = traced_paths(), mlp_ops.traced_paths()
        with pytest.MonkeyPatch.context() as patch, _compile_cache_off():
            patch.setattr(jax, "default_backend", lambda: "tpu")
            text = (
                jax.jit(block.apply)
                .trace(*on_chip)
                .lower(lowering_platforms=("tpu",))
                .compile()
                .as_text()
            )
        return (
            text,
            traced_paths(since=before[0]),
            mlp_ops.traced_paths(since=before[1]),
        )

    @staticmethod
    def _produced(entry):
        """"%name = type[shape]{layout} opcode(" of every instruction of
        the entry computation -> "opcode-or-fusion-kind type[shape]"."""
        return {
            f"{re.sub(r'[.][0-9]+$', '', name) if op == 'fusion' else op} {shape}"
            for name, shape, op in re.findall(
                r"%([\w.\-]+) = (\(?\w+\[[\d,]*\])\S* ([\w\-]+)\(", entry
            )
        }

    def test_compiles_for_v5e_without_a_relayout(self, served_block):
        text, attention_paths, _ = served_block
        assert attention_paths == {"packed:1024": 1}
        assert "tpu_custom_call" in text and "packed_attention" in text
        produced = self._produced(text[text.index("ENTRY"):])
        # the pattern reads this dump: the qkv projection is in it
        assert any(p.endswith("bf16[16,32,32,3072]") for p in produced)
        assert not produced & set(self.RELAYOUTS)
        # nothing at all the size of an attention operand but the
        # projection's output, the kernel's and the block's own
        assert not {
            p for p in produced
            if re.search(r"bf16\[16,(16,1024|1024,16|1024,3,16),", p)
        }

    def test_the_mlp_is_one_kernel(self, served_block):
        text, _, mlp_paths = served_block
        assert mlp_paths == {"fused:16384": 1}
        assert "fused_mlp" in text
        entry = text[text.index("ENTRY"):]
        calls = re.findall(r'custom_call_target="tpu_custom_call"', entry)
        assert len(calls) == 2  # the packed attention and the MLP
        produced = self._produced(entry)
        assert any(p.endswith("bf16[16,32,32,3072]") for p in produced)
        # the pre-activation, the packed sign bits beside it, the hidden
        # activation: none of them exists outside the kernel
        assert not {p for p in produced if "4096]" in p and "f32[" not in p}, produced
        assert not {p for p in produced if "u32[16,32,4096]" in p}
        # and no matmul fusion evaluates an erfc (its exponential)
        for computation in text[:text.index("ENTRY")].split("\n\n"):
            if " convolution(" in computation:
                assert " exponential(" not in computation, computation[:200]
