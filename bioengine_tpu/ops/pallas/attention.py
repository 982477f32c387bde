"""Fused attention as a Pallas TPU kernel.

``softmax(q k^T * scale) v`` in one Mosaic kernel: the scores of one
block of queries against a block of keys are formed, exponentiated and
contracted with the values in VMEM, so neither the scores nor the
probabilities ever reach HBM. Users: cpsam's ``SAMAttention``
(models/sam.py, through ``ops.attention.attention``, with the decomposed
relative-position bias folded into the contraction) and the ViT
embedder's ``attn_fn`` slot (cell-image-search).

What the kernel is given is what it multiplies: q, k and v go into both
``dot_general``s in the dtype they arrive in (bf16 on the serving path:
one MXU pass), accumulation and the softmax statistics are f32, and the
probabilities are cast to the value dtype before ``P V``. q and k share
a depth, v may have another (cpsam: 64 + 32 + 32 = 128 against 64); the
q/k depth is zero-padded to the 128-lane width, the value depth is left
as it is. ``scale`` is explicit (``None`` = depth**-0.5).

Layout: grid = (batch*heads, q blocks, kv blocks), kv innermost and
"arbitrary", the other two "parallel". Block sizes come from N
(:func:`_block_sizes`): the kv block is the whole padded sequence up to
``MAX_BLOCK_K`` tokens, so at N <= 2048 there is ONE kv step and the
kernel is a plain softmax in VMEM with no scratch at all. Longer
sequences take several kv steps with an online-softmax accumulator
(running max m, normalizer l, weighted sum acc) in f32 scratch across
the kv steps of one q block. On the v5e at (256 heads, N 1024, depth
128 / 64) the one-step form takes 1.11 ms, the online form over two
512-token kv steps 2.6 ms and over 128-token blocks 9.4 ms (chip runs
of PR 27, PERF.md section 6). Sequence padding and the causal option
are ``broadcasted_iota`` masks, applied only where the shapes call for
them; with several kv steps fully-masked causal blocks skip their
matmuls via ``pl.when``.

Models call ``ops.attention.attention``, which picks this kernel on a
TPU backend and the plain-XLA reference elsewhere; the backward pass of
the kernel recomputes through that same reference (custom VJP).
:func:`flash_attention` is the kernel itself; it runs in interpreter
mode only where the CPU platform was asked for by name (the hermetic
tests); any other non-TPU backend is an error, not a quiet interpreter
run.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from bioengine_tpu.ops.attention import (
    NEG_INF,
    mesh_axes,
    reference_attention,
)
from bioengine_tpu.utils.devices import require_accelerator

LANES = 128
# Block caps, set from the sweep on the v5e (PERF.md section 6, PR 27)
MAX_BLOCK_Q = 512
MAX_BLOCK_K = 2048


def _attn_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    *scratch,
    scale: float,
    seq_len: int,
    block_q: int,
    block_k: int,
    padded: bool,
    causal: bool,
):
    q_start = pl.program_id(1) * block_q
    k_start = pl.program_id(2) * block_k

    def scores():
        s = jax.lax.dot_general(
            q_ref[0],  # (block_q, d_qk)
            k_ref[0],  # (block_k, d_qk)
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k) f32
        if scale != 1.0:
            s = s * scale
        if padded or causal:
            col_ids = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            mask = col_ids < seq_len
            if causal:
                row_ids = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                mask = jnp.logical_and(mask, col_ids <= row_ids)
            # key 0 is visible to every row in kv block 0, so the row
            # max is finite from the first step on and a masked score
            # exponentiates to 0
            s = jnp.where(mask, s, NEG_INF)
        return s

    def weighted(p):
        v = v_ref[0]  # (block_k, d_v)
        return jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if not scratch:
        # one kv step holds the whole sequence: a plain softmax in VMEM
        s = scores()
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        l = jnp.sum(p, axis=-1, keepdims=True)
        o_ref[0] = (weighted(p) / l).astype(o_ref.dtype)
        return

    m_scratch, l_scratch, acc_scratch = scratch
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    def accumulate():
        s = scores()
        # m/l scratch are (block_q, 128) with the value broadcast across
        # lanes (keeps buffers tile-aligned); column 0 is authoritative.
        m_prev = m_scratch[:, :1]  # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scratch[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * alpha + weighted(p)
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    if causal:
        # Dynamic skip: whole tile above the diagonal → no contribution.
        pl.when(k_start <= q_start + block_q - 1)(accumulate)
    else:
        accumulate()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_scratch[:] / l_scratch[:, :1]).astype(o_ref.dtype)


def _pad_to(x, size, axis):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _largest_block(n_lanes: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``n_lanes`` (itself one)
    and does not exceed ``cap``."""
    units = n_lanes // LANES
    best = max(
        u for u in range(1, units + 1) if units % u == 0 and u * LANES <= cap
    )
    return best * LANES


def _block_sizes(n: int) -> tuple[int, int]:
    """(block_q, block_k) for a sequence of ``n`` tokens: both divide n
    rounded up to the lane width, so the padding stays under 128 tokens;
    the kv block is the whole sequence where that fits ``MAX_BLOCK_K``."""
    n_lanes = -(-n // LANES) * LANES
    return _largest_block(n_lanes, MAX_BLOCK_Q), _largest_block(
        n_lanes, MAX_BLOCK_K
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out = _flash_attention(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    _, vjp = jax.vjp(
        lambda q, k, v: reference_attention(q, k, v, causal, scale), *res
    )
    return vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused attention. q, k: (B, H, N, d_qk), v: (B, H, N, d_v) →
    (B, H, N, d_v).

    Self-attention shapes only (same N for q and kv). N is padded to
    the blocks and d_qk to a multiple of 128 internally (zero-padded
    depth contributes nothing to QK^T). ``scale`` multiplies the scores
    (``None`` = d_qk**-0.5). ``block_q``/``block_k`` are for tests and
    sweeps; callers leave them to :func:`_block_sizes`. Differentiable
    via custom VJP (XLA-recompute backward).
    """
    if interpret is None:
        backend = jax.default_backend()
        require_accelerator(backend, "flash_attention")
        interpret = backend == "cpu"
    if scale is None:
        scale = q.shape[-1] ** -0.5
    derived = _block_sizes(q.shape[2])
    return _flash_attention(
        q, k, v, causal, float(scale),
        block_q or derived[0], block_k or derived[1], interpret,
    )


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret):
    """The kernel, per device. Mosaic calls cannot be partitioned
    automatically (lowering one inside a multi-device jit raises), so
    where the operands belong to a mesh (the engine's dp-sharded batch,
    the dp fine-tune step) the call is wrapped in a ``shard_map`` that
    splits the batch over every axis of that mesh: each (batch, head)
    pair is its own problem, so no collective is needed. The mesh is
    read off the operands' types; a program whose mesh exists only in
    ``jit(in_shardings=...)`` over uncommitted arrays shows none, and
    Mosaic's own error then says what to do."""
    run = functools.partial(
        _pallas_forward,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    axes, devices = mesh_axes(q)
    if devices == 1:
        return run(q, k, v)
    if q.shape[0] % devices:
        raise ValueError(
            f"flash_attention: batch {q.shape[0]} does not divide over the "
            f"{devices} devices of mesh axes {axes}"
        )
    spec = P(axes)
    return jax.shard_map(
        run,
        mesh=jax.typeof(q).sharding.mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _pallas_forward(q, k, v, *, causal, scale, block_q, block_k, interpret):
    B, H, N, d = q.shape
    d_v = v.shape[-1]
    n_pad = math.lcm(block_q, block_k)
    N_p = -(-N // n_pad) * n_pad
    d_p = -(-d // LANES) * LANES

    qp = _pad_to(_pad_to(q, N_p, 2), d_p, 3).reshape(B * H, N_p, d_p)
    kp = _pad_to(_pad_to(k, N_p, 2), d_p, 3).reshape(B * H, N_p, d_p)
    vp = _pad_to(v, N_p, 2).reshape(B * H, N_p, d_v)
    n_kv = N_p // block_k

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        seq_len=N,
        block_q=block_q,
        block_k=block_k,
        padded=N_p != N,
        causal=causal,
    )

    out = pl.pallas_call(
        kernel,
        grid=(B * H, N_p // block_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d_p), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_p), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_v), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, N_p, d_v), q.dtype),
        # the online-softmax state; one kv step needs none
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ] if n_kv > 1 else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * N_p * N_p * (d_p + d_v),
            bytes_accessed=B * H * N_p * (2 * d_p + 2 * d_v)
            * q.dtype.itemsize,
            transcendentals=B * H * N_p * N_p,
        ),
        name="fused_attention",
        interpret=interpret,
    )(qp, kp, vp)

    return out.reshape(B, H, N_p, d_v)[:, :, :N]


def make_attn_fn(**kwargs):
    """Adapter for ``models.vit.Attention(attn_fn=...)``: (q,k,v)→out."""

    def attn_fn(q, k, v):
        return flash_attention(q, k, v, **kwargs)

    return attn_fn
