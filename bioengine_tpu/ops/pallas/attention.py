"""Fused attention as a Pallas TPU kernel.

``softmax(q k^T * scale) v`` in one Mosaic kernel: the scores of one
block of queries against a block of keys are formed, exponentiated and
contracted with the values in VMEM, so neither the scores nor the
probabilities ever reach HBM. Users: cpsam's ``SAMAttention``
(models/sam.py, through ``ops.attention.packed_attention``, with the
decomposed relative-position bias folded into the contraction) and the
ViT embedder's ``attn_fn`` slot (cell-image-search).

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

Models call ``ops.attention``, which picks a kernel of this module on a
TPU backend and the plain-XLA reference elsewhere; the backward pass of
both kernels recomputes through that same reference (custom VJP).
:func:`flash_attention` is the kernel itself; it runs in interpreter
mode only where the CPU platform was asked for by name (the hermetic
tests); any other non-TPU backend is an error, not a quiet interpreter
run.

:func:`packed_flash_attention` is the same one-step softmax over
operands nobody relaid (cpsam's global blocks; ``packs`` says which
shapes). ``flash_attention`` wants ``(B * heads, N, d)``: for a 64-wide
head XLA will not hand a custom call a 64-lane-minor array for free, so
between the qkv projection and the kernel stood a 5-D reshape and copy,
a copy per operand, the bias einsums written at 32 of 128 lanes, a
concatenate each for q' and k' and a transpose of the result, 83 of the
served step's 195 ms (PERF.md section 6, PR 31). The packed entry's
grid is (batch, head pairs, q blocks) and its ``BlockSpec``s cut
128-lane blocks, two heads, out of the projection's ``(B, N, 3 * dim)``
output as it lies; the bias rows are one more matmul of the q block
against both relative-position tables and a lane rotation per row of
the token grid; q' and k' are lane selects in VMEM; the result is
written as the ``(B, N, dim)`` the output projection reads. On the v5e
at the served shape 0.91 ms a call against 3.27 ms for everything it
replaces (chip run of PR 31).
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
    key_positions,
    mesh_axes,
    reference_attention,
    unpacked_attention,
)
from bioengine_tpu.utils.devices import require_accelerator

LANES = 128
# Block caps, set from the sweep on the v5e (PERF.md section 6, PR 27)
MAX_BLOCK_Q = 512
MAX_BLOCK_K = 2048


def _plain_softmax(s, v):
    """``softmax(s) v`` where one kv step holds the whole sequence: no
    running statistics. s: (block_q, N) f32 scores, v: (N, d_v) in the
    dtype it arrived in; the probabilities are cast to it, the result is
    f32."""
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(p, axis=-1, keepdims=True)
    weighted = jax.lax.dot_general(
        p.astype(v.dtype),
        v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return weighted / l


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
        o_ref[0] = _plain_softmax(scores(), v_ref[0]).astype(o_ref.dtype)
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
    run = functools.partial(
        _pallas_forward,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return per_device(run, "flash_attention", q, k, v)


def per_device(run, name, *operands, replicated=0):
    """``run(*operands)``, per device. Mosaic calls cannot be partitioned
    automatically (lowering one inside a multi-device jit raises), so
    where the operands belong to a mesh (the engine's dp-sharded batch,
    the dp fine-tune step) the call is wrapped in a ``shard_map`` that
    splits the batch over every axis of that mesh (the last
    ``replicated`` operands have no batch and go to every device whole):
    each (batch, head) pair is its own problem, so no collective is
    needed. The mesh is read off the operands' types; a program whose
    mesh exists only in ``jit(in_shardings=...)`` over uncommitted
    arrays shows none, and Mosaic's own error then says what to do."""
    first = operands[0]
    axes, devices = mesh_axes(first)
    if devices == 1:
        return run(*operands)
    if first.shape[0] % devices:
        raise ValueError(
            f"{name}: batch {first.shape[0]} does not divide over the "
            f"{devices} devices of mesh axes {axes}"
        )
    spec = P(axes)
    return jax.shard_map(
        run,
        mesh=jax.typeof(first).sharding.mesh,
        in_specs=(spec,) * (len(operands) - replicated) + (P(),) * replicated,
        out_specs=spec,
        check_vma=False,
    )(*operands)


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


# ---- the packed entry ------------------------------------------------------


def packs(n: int, dim: int, grid: tuple[int, int], heads: int) -> bool:
    """Whether :func:`packed_flash_attention` can take a ``(B, n,
    3 * dim)`` projection of ``heads`` heads over a ``grid`` of tokens:
    two heads fill the 128 lanes, the two bias rows fill one head's
    depth (so q' is exactly 128 lanes), the whole sequence is one kv
    step that needs no padding, and a q block holds whole rows of the
    grid, each a whole number of sublane tiles."""
    hd = dim // heads
    block_q, block_k = _block_sizes(n)
    return (
        2 * hd == LANES
        and heads % 2 == 0
        and sum(grid) == hd
        and grid[0] * grid[1] == n
        and n % LANES == 0
        and block_k == n
        and grid[1] % 8 == 0
        and block_q % grid[1] == 0
    )


def _packed_kernel(
    q_ref, k_ref, v_ref, rel_ref, pos_ref, o_ref, *, scale, grid
):
    """One q block of one pair of heads ``(a, b)``. q, k, v are 128
    lanes ``[x_a | x_b]`` as the projection wrote them, pos the keys'
    one-hot positions ``[pos | pos]``. Head a is ``[q_a | bias_a]
    [k_a | pos]^T`` and head b ``[bias_b | q_b] [pos | k_b]^T``:
    full-width lane selects, and the same depth-128 contraction as the
    unpacked fold.

    The bias rows are formed here. ``q @ rel`` is, per head, the q
    row's product with every row of both tables, highest offset first:
    lanes ``[0, 2H - 1)`` for the key rows, ``[2H, 2H + 2W - 1)`` for
    the key columns. Token (h, w)'s bias against key row k sits at lane
    ``H - 1 - h + k``, against key column k at ``2H + W - 1 - w + k``:
    one lane rotation per row of the grid (h is the same for its W
    tokens; w is the token's index in it, a strided rotation) puts both
    where q' wants them."""
    H, W = grid
    half = LANES // 2
    rows_here = q_ref.shape[1] // W  # rows of the grid in this q block
    q = q_ref[0]  # (block_q, 128)
    k, v, pos = k_ref[0], v_ref[0], pos_ref[...]  # (N, 128)
    rel = jax.lax.dot_general(
        q,
        rel_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (block_q, 256): head a's offsets, then head b's
    first_row = pl.program_id(2) * rows_here
    lane = jax.lax.broadcasted_iota(jnp.int32, (W, LANES), 1)

    def bias_rows(rel_head, at):
        """``[bias_h | bias_w]`` at lanes ``[at, at + H + W)``; what the
        rotations wrap into the other lanes is not selected below."""
        rows = []
        for r in range(rows_here):
            x = rel_head[r * W:(r + 1) * W]
            to_rows = jax.lax.rem(at + LANES - (H - 1) + first_row + r, LANES)
            by_row = pltpu.roll(x, to_rows, 1)
            by_column = pltpu.roll(
                x, (at - H - (W - 1)) % LANES, 1, stride=1, stride_axis=0
            )
            rows.append(jnp.where(lane < at + H, by_row, by_column))
        return jnp.concatenate(rows, axis=0).astype(q.dtype)

    q = q * scale
    q_low = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1) < half
    k_low = jax.lax.broadcasted_iota(jnp.int32, k.shape, 1) < half

    def head(q_fold, k_fold):
        s = jax.lax.dot_general(
            q_fold,
            k_fold,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, N) f32
        # against the pair's v: 128 lanes is one MXU pass, as 64 is, and
        # the other head's half of the result is dropped below
        return _plain_softmax(s, v)

    out_a = head(
        jnp.where(q_low, q, bias_rows(rel[:, :LANES], half)),
        jnp.where(k_low, k, pos),
    )
    out_b = head(
        jnp.where(q_low, bias_rows(rel[:, LANES:], 0), q),
        jnp.where(k_low, pos, k),
    )
    o_ref[0] = jnp.where(q_low, out_a, out_b).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _packed_attention(qkv, rel_h, rel_w, grid, heads, interpret):
    run = functools.partial(
        _packed_forward, grid=grid, heads=heads, interpret=interpret
    )
    return per_device(
        run, "packed_flash_attention", qkv, rel_h, rel_w, replicated=2
    )


def _packed_fwd(qkv, rel_h, rel_w, grid, heads, interpret):
    out = _packed_attention(qkv, rel_h, rel_w, grid, heads, interpret)
    return out, (qkv, rel_h, rel_w)


def _packed_bwd(grid, heads, interpret, res, g):
    _, vjp = jax.vjp(
        lambda qkv, rel_h, rel_w: unpacked_attention(
            reference_attention, qkv, rel_h, rel_w, grid, heads
        ),
        *res,
    )
    return vjp(g)


_packed_attention.defvjp(_packed_fwd, _packed_bwd)


@functools.partial(jax.jit, static_argnames=("grid", "heads", "interpret"))
def packed_flash_attention(
    qkv: jax.Array,
    rel_h: jax.Array,
    rel_w: jax.Array,
    *,
    grid: tuple[int, int],
    heads: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused attention with SAM's decomposed relative-position bias,
    over operands in the layout their producer writes. qkv: (B, N,
    3 * dim), the projection's output; rel_h: (2H - 1, hd), rel_w:
    (2W - 1, hd), the tables at the extent of ``grid = (H, W)``,
    ``H * W == N`` -> (B, N, dim), the layout the output projection
    reads. Shapes have to satisfy :func:`packs`;
    ``ops.attention.packed_attention`` asks it and unpacks everything
    else to :func:`flash_attention`'s operands.

    Grid ``(B, heads / 2, N / block_q)``: the ``BlockSpec``s cut one
    pair of 64-wide heads, 128 lanes, straight out of ``qkv`` (q at
    column block p, k at ``heads / 2 + p``, v at ``heads + p``), so no
    operand is relaid in HBM; the bias rows, q' and k' are formed in
    VMEM (:func:`_packed_kernel`). Differentiable via custom VJP (XLA
    recompute through the unpacked reference)."""
    n, dim = qkv.shape[1], qkv.shape[2] // 3
    if not packs(n, dim, grid, heads):
        raise ValueError(
            f"packed_flash_attention: qkv {qkv.shape} over grid {grid} with "
            f"{heads} heads does not pack into {LANES}-lane head pairs"
        )
    if interpret is None:
        backend = jax.default_backend()
        require_accelerator(backend, "packed_flash_attention")
        interpret = backend == "cpu"
    return _packed_attention(qkv, rel_h, rel_w, grid, heads, interpret)


def _packed_forward(qkv, rel_h, rel_w, *, grid, heads, interpret):
    B, N, _ = qkv.shape
    H, W = grid
    pairs, hd = heads // 2, LANES // 2
    block_q = _block_sizes(N)[0]
    pos = key_positions(H, W, qkv.dtype)
    pos = jnp.concatenate([pos, pos], axis=-1)  # (N, 128), a constant
    # (hd, 128): both tables side by side, highest offset first, a zero
    # column after each; then one copy per head of the pair, (128, 256)
    gap = jnp.zeros((hd, 1), rel_h.dtype)
    rel = jnp.concatenate([rel_h[::-1].T, gap, rel_w[::-1].T, gap], axis=1)
    rel = jnp.kron(jnp.eye(2, dtype=rel.dtype), rel)

    def rows(column_block):
        return pl.BlockSpec(
            (1, N, LANES), lambda b, p, i: (b, 0, column_block + p)
        )

    def whole(shape):
        # the same block at every step: fetched once
        return pl.BlockSpec(shape, lambda b, p, i: (0, 0))

    q_rows = pl.BlockSpec((1, block_q, LANES), lambda b, p, i: (b, i, p))
    return pl.pallas_call(
        functools.partial(_packed_kernel, scale=hd**-0.5, grid=grid),
        grid=(B, pairs, N // block_q),
        in_specs=[
            q_rows,
            rows(pairs),
            rows(2 * pairs),
            whole((LANES, 2 * LANES)),
            whole((N, LANES)),
        ],
        out_specs=q_rows,
        out_shape=jax.ShapeDtypeStruct((B, N, heads * hd), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * heads * N * (2 * N + LANES) * LANES,
            bytes_accessed=B * N * 4 * heads * hd * qkv.dtype.itemsize,
            transcendentals=B * heads * N * N,
        ),
        name="packed_attention",
        interpret=interpret,
    )(qkv, qkv, qkv, rel, pos)


def make_attn_fn(**kwargs):
    """Adapter for ``models.vit.Attention(attn_fn=...)``: (q,k,v)→out."""

    def attn_fn(q, k, v):
        return flash_attention(q, k, v, **kwargs)

    return attn_fn
