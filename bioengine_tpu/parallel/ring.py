"""Ring attention — sequence/context parallelism over the mesh.

The reference has no sequence models and no sequence parallelism
(SURVEY.md §2.3, §5.7); this is a first-class new capability so the
framework scales transformer workloads (ViT embedders over giant token
counts, future sequence models) past one chip's HBM.

Design: shard the token axis over the ``sp`` mesh axis. Q blocks stay
resident; K/V blocks rotate around the ring with ``ppermute`` (ICI
neighbour hops) while a streaming-softmax accumulator (running max,
normalizer, weighted sum — the flash-attention recurrence) folds in one
block per step. Memory per chip is O(N/n) and the ICI transfer fully
overlaps with the block matmuls under XLA's scheduler.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _block_attn(q, k, v, m_prev, l_prev, o_prev, scale):
    """One streaming-softmax update. q/k/v: (B, H, Nq, d)/(B, H, Nk, d)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale          # (B,H,Nq,Nk)
    m_cur = jnp.max(s, axis=-1)                               # (B,H,Nq)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    l_new = l_prev * jnp.exp(m_prev - m_new) + p.sum(-1)
    o_new = o_prev * jnp.exp(m_prev - m_new)[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v
    )
    return m_new, l_new, o_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
) -> jax.Array:
    """Exact attention with K/V sharded over ``axis_name``.

    Called INSIDE shard_map; q, k, v: (B, H, N_local, d) per-shard
    blocks. Returns (B, H, N_local, d). Non-causal (bidirectional —
    images/embedding workloads); a causal variant can mask per-step.
    """
    n = jax.lax.axis_size(axis_name)
    scale = q.shape[-1] ** -0.5
    B, H, Nq, d = q.shape
    m0 = jnp.full((B, H, Nq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Nq), jnp.float32)
    o0 = jnp.zeros((B, H, Nq, d), jnp.float32)
    # Accumulators must carry the same device-varying type as the loop
    # body's outputs (which derive from the sp-sharded q/k/v blocks).
    m0, l0, o0 = jax.lax.pcast((m0, l0, o0), axis_name, to="varying")

    qf = q.astype(jnp.float32)

    def fold(m, l, o, k_blk, v_blk):
        return _block_attn(
            qf,
            k_blk.astype(jnp.float32),
            v_blk.astype(jnp.float32),
            m,
            l,
            o,
            scale,
        )

    def step(i, carry):
        m, l, o, kv = carry
        k_blk, v_blk = kv
        m, l, o = fold(m, l, o, k_blk, v_blk)
        # Rotate K/V one hop around the ring for the next step.
        perm = [(j, (j + 1) % n) for j in range(n)]
        kv = jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), (k_blk, v_blk)
        )
        return m, l, o, kv

    # Loop n-1 fold+rotate steps, then fold the final block outside the
    # loop — saves one full K/V ICI hop per attention call.
    m, l, o, (k_last, v_last) = jax.lax.fori_loop(
        0, n - 1, step, (m0, l0, o0, (k, v))
    )
    m, l, o = fold(m, l, o, k_last, v_last)
    return (o / l[..., None]).astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis: str = "sp"):
    """Build a jitted full-sequence attention fn with tokens sharded
    over ``axis``: (B, H, N, d) x3 -> (B, H, N, d).

    Drop-in for ``bioengine_tpu.models.vit.Attention(attn_fn=...)`` when
    a replica owns a multi-chip sub-mesh and sequences exceed one chip.
    """
    spec = P(None, None, axis, None)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def sharded(q, k, v):
        return ring_attention(q, k, v, axis)

    return jax.jit(sharded)


def reference_attention(q, k, v):
    """Unsharded reference for tests: same math, one device."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
