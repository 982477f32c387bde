"""Flash attention as a Pallas TPU kernel.

The hot op of the ViT embedder (cell-image-search) and any future
sequence model. The reference runs torch scaled-dot-product attention
through CUDA (ref apps/cell-image-search/embedder.py:40-70); here the
whole softmax(QK^T)V is one fused Mosaic kernel: K/V blocks stream
through VMEM while an online-softmax accumulator (running max m,
normalizer l, weighted sum acc) lives in f32 scratch — attention
probabilities never round-trip to HBM, so the op is bounded by the MXU,
not HBM bandwidth.

Layout: grid = (batch*heads, num_q_blocks, num_kv_blocks); the kv axis
is innermost so scratch carries across kv steps for one q block.
Accumulators init at kv==0 and the normalized output is written at the
last kv step. Sequence padding (to the block size) and the causal
option are handled with ``broadcasted_iota`` masks; fully-masked
causal blocks skip their matmuls via ``pl.when``.

The kernel runs in interpreter mode only where the CPU platform was
asked for by name (the hermetic tests); any other non-TPU backend is an
error, not a quiet interpreter run.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bioengine_tpu.utils.devices import require_accelerator

NEG_INF = -1e30


def _attn_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scratch,
    l_scratch,
    acc_scratch,
    *,
    scale: float,
    seq_len: int,
    block_q: int,
    block_k: int,
    causal: bool,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    last_k = pl.num_programs(2) - 1

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    k_start = ki * block_k

    # Row/col token ids of this tile, for padding + causal masks.
    row_ids = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    col_ids = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    def compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)  # (block_k, d)

        s = jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)

        mask = col_ids < seq_len
        if causal:
            mask = jnp.logical_and(mask, col_ids <= row_ids)
        s = jnp.where(mask, s, NEG_INF)

        # m/l scratch are (block_q, 128) with the value broadcast across
        # lanes (keeps buffers tile-aligned); column 0 is authoritative.
        m_prev = m_scratch[:, :1]  # (block_q, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scratch[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scratch[:] * alpha + jax.lax.dot_general(
            p,
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)
        acc_scratch[:] = acc

    if causal:
        # Dynamic skip: whole tile above the diagonal → no contribution.
        @pl.when(k_start <= q_start + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == last_k)
    def _finish():
        l = l_scratch[:, :1]
        # Fully-padded q rows have l == 0; emit zeros, not NaN.
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[:] / safe_l).astype(o_ref.dtype)


def _pad_to(x, size, axis):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _reference_attention(q, k, v, causal):
    """Plain-XLA attention — the custom-VJP backward recomputes through
    this (flash forward + XLA backward: correct grads everywhere; a
    fused Pallas backward kernel is a later optimization)."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhnd,bhmd->bhnm", qf * scale, kf)
    if causal:
        n = q.shape[2]
        row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        s = jnp.where((col <= row)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhnm,bhmd->bhnd", p, vf).astype(q.dtype)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def _flash_attention(q, k, v, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    return _flash_attention(q, k, v, causal, block_q, block_k, interpret), (
        q,
        k,
        v,
    )


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: _reference_attention(q, k, v, causal), q, k, v
    )
    return vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused attention. q, k, v: (B, H, N, d) → (B, H, N, d).

    Self-attention shapes only (same N for q and kv). N and d are
    padded to tile boundaries internally (d to a multiple of 128 —
    lane width; zero-padded d contributes nothing to QK^T and the
    extra output columns are sliced off). Differentiable via custom
    VJP (XLA-recompute backward).
    """
    if interpret is None:
        backend = jax.default_backend()
        require_accelerator(backend, "flash_attention")
        interpret = backend == "cpu"
    return _flash_attention(q, k, v, causal, block_q, block_k, interpret)


def _flash_forward(q, k, v, causal, block_q, block_k, interpret):
    B, H, N, d = q.shape
    scale = d**-0.5

    import math

    n_pad = math.lcm(block_q, block_k)
    N_p = ((N + n_pad - 1) // n_pad) * n_pad
    d_p = ((d + 127) // 128) * 128

    qp = _pad_to(_pad_to(q, N_p, 2), d_p, 3).reshape(B * H, N_p, d_p)
    kp = _pad_to(_pad_to(k, N_p, 2), d_p, 3).reshape(B * H, N_p, d_p)
    vp = _pad_to(_pad_to(v, N_p, 2), d_p, 3).reshape(B * H, N_p, d_p)

    grid = (B * H, N_p // block_q, N_p // block_k)

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        seq_len=N,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
    )

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, block_q, d_p),
                lambda b, i, j: (b, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_k, d_p),
                lambda b, i, j: (b, j, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_k, d_p),
                lambda b, i, j: (b, j, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, d_p),
            lambda b, i, j: (b, i, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, N_p, d_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d_p), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * N_p * N_p * d_p,
            bytes_accessed=(3 * B * H * N_p * d_p + B * H * N_p * d_p)
            * q.dtype.itemsize,
            transcendentals=B * H * N_p * N_p,
        ),
        interpret=interpret,
    )(qp, kp, vp)

    return out.reshape(B, H, N_p, d_p)[:, :, :N, :d]


def make_attn_fn(**kwargs):
    """Adapter for ``models.vit.Attention(attn_fn=...)``: (q,k,v)→out."""

    def attn_fn(q, k, v):
        return flash_attention(q, k, v, **kwargs)

    return attn_fn
