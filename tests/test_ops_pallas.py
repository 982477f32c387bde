"""Pallas kernel correctness vs. plain-XLA reference implementations.

Runs in interpreter mode because conftest names the CPU platform
(JAX_PLATFORMS=cpu) — the same kernel code compiles via Mosaic on TPU,
which chip_smoke.py checks on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bioengine_tpu.ops.pallas.attention import flash_attention, make_attn_fn


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
