"""A transformer block's MLP as one Pallas TPU kernel.

``shortcut + (gelu(y w1 + b1) w2 + b2)`` with the hidden activation
kept in VMEM: grid (row tiles, hidden blocks), hidden innermost and
"arbitrary", an f32 ``(tm, dim)`` accumulator in scratch. Per step the
row tile is multiplied with a block of ``w1``'s columns, the f32 result
takes its bias and the exact GELU (``ops.mlp.gelu``, the one statement
of it), is rounded to the compute dtype and multiplied with the same
block of ``w2``'s rows into the accumulator, which starts as the shortcut
tile plus the second bias and is rounded out at the last hidden block. What
XLA does with the same arithmetic is two fusions with the ``(rows,
hidden)`` activation written to HBM between them and read back (134 MB
a block of the served cpsam program); here it never leaves the chip.

The body is straight-line code that Mosaic unrolls and bundles: the
VPU's work on the activation (about 35 operations an element, the erf's
one divide taken as the hardware's reciprocal estimate and a Newton
step) sits beside the MXU's in the same bundles where the schedule
finds room. On the v5e at the served cpsam shape (16384 rows, 1024,
4096) the call takes 1.58 ms against 1.395 ms of MXU time (chip runs of
PR 36, PERF.md section 6).

The weights arrive as the parameter tree holds them (f32 for cpsam:
``param_dtype``) and are rounded to the compute dtype tile by tile in
VMEM, 1 / ``tm`` of the tile's multiply-adds; no second copy of them
exists anywhere. A row tile of ``tm`` rows reads both weight matrices
once, so the weights cross HBM ``rows / tm`` times a call; :func:`tiles`
picks the largest row tile that divides the rows and fits the VMEM the
call asks for (``vmem_limit_bytes``; the v5e has 128 MiB).

Models call ``ops.mlp.mlp``, which takes this kernel on a TPU backend
where :func:`tiles` finds tiles and the plain-XLA reference elsewhere;
the backward pass recomputes through that same reference (custom VJP).
Like the attention kernels, it runs interpreted only where the CPU
platform was asked for by name.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bioengine_tpu.ops.mlp import gelu, reference_mlp
from bioengine_tpu.ops.pallas.attention import LANES, per_device
from bioengine_tpu.utils.devices import require_accelerator

# what one call may ask of the v5e's 128 MiB of VMEM, the pipeline's
# double buffers and the body's temporaries included
VMEM_LIMIT = 64 * 2**20
# largest first. Set from sweeps on the v5e at (16384, 1024, 4096)
# (PERF.md section 6, PR 36): 1024 x 1024 runs in 1.58 ms, 1024 x 2048 in
# 1.56 (and compiles twice as long), 1024 x 512 in 1.63, 512 x 1024 in
# 1.71; a 2048-row tile is no faster at 512 hidden columns and a quarter
# slower at 1024
ROW_TILES = (1024, 512, 256, 128)
HIDDEN_BLOCKS = (1024, 512, 256, 128)


class Tiles(NamedTuple):
    rows: int    # tm: rows of y, shortcut and the result per grid step
    hidden: int  # columns of w1 / rows of w2 per grid step


def _vmem_bytes(t: Tiles, dim: int, itemsize: int) -> int:
    """What the pipeline and the body hold for these tiles: y, shortcut
    and the result double-buffered, both weight blocks (counted as f32,
    the widest a parameter tree holds) double-buffered, the
    accumulator, and in the body the f32 pre-activation, the GELU's
    temporaries, the rounded activation and the rounded weight blocks."""
    row_tiles = 3 * 2 * t.rows * dim * itemsize
    weights = 2 * 2 * dim * t.hidden * 4
    accumulator = t.rows * dim * 4
    body = t.rows * t.hidden * (3 * 4 + itemsize) + 2 * dim * t.hidden * itemsize
    return row_tiles + weights + accumulator + body


def tiles(rows: int, dim: int, hidden: int, dtype) -> Optional[Tiles]:
    """The tiles :func:`fused_mlp` runs ``(rows, dim) -> (rows, hidden)
    -> (rows, dim)`` at, or None where it cannot: ``dim`` and ``hidden``
    whole lane widths, the rows a multiple of a row tile, everything
    inside ``VMEM_LIMIT``. The largest row tile that
    fits (the weights cross HBM once a row tile), then the largest
    hidden block."""
    if dim % LANES or hidden % LANES:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    for tm in ROW_TILES:
        if rows % tm:
            continue
        for tg in HIDDEN_BLOCKS:
            if hidden % tg:
                continue
            t = Tiles(tm, tg)
            if _vmem_bytes(t, dim, itemsize) <= VMEM_LIMIT:
                return t
    return None


def _reciprocal(q):
    """``1 / q`` for the erf's denominator, q in [1, 140]: the
    hardware's estimate (good to 1.6e-5 on the v5e) and one Newton step
    (1.4e-7: an f32 ulp; chip run of PR 36), a third of the VALU work of
    a general f32 divide, whose special cases cannot arise here."""
    r = pl.reciprocal(q, approx=True)
    return r * (2.0 - q * r)


def _mlp_kernel(y_ref, s_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, acc_ref):
    j = pl.program_id(1)
    y = y_ref[...]  # (tm, dim)

    @pl.when(j == 0)
    def _init():
        # the second bias and the shortcut go in first: the last hidden
        # block then only rounds the accumulator and writes it out
        acc_ref[...] = s_ref[...].astype(jnp.float32) + b2_ref[...]

    h = jax.lax.dot_general(
        y,
        w1_ref[...].astype(y.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b1_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        gelu(h, _reciprocal).astype(y.dtype),
        w2_ref[...].astype(y.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pallas_mlp(y, shortcut, w1, b1, w2, b2, *, t: Tiles, interpret):
    """One device's rows. y, shortcut: (B, ..., dim) -> the same."""
    shape = y.shape
    dim, hidden = w1.shape
    rows = math.prod(shape[:-1])
    row_block = pl.BlockSpec((t.rows, dim), lambda i, j: (i, 0))
    out = pl.pallas_call(
        _mlp_kernel,
        grid=(rows // t.rows, hidden // t.hidden),
        in_specs=[
            row_block,
            row_block,
            pl.BlockSpec((dim, t.hidden), lambda i, j: (0, j)),
            pl.BlockSpec((1, t.hidden), lambda i, j: (0, j)),
            pl.BlockSpec((t.hidden, dim), lambda i, j: (j, 0)),
            pl.BlockSpec((1, dim), lambda i, j: (0, 0)),
        ],
        out_specs=row_block,
        out_shape=jax.ShapeDtypeStruct((rows, dim), y.dtype),
        scratch_shapes=[pltpu.VMEM((t.rows, dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * dim * hidden,
            bytes_accessed=3 * rows * dim * y.dtype.itemsize
            + (rows // t.rows) * 2 * dim * hidden * w1.dtype.itemsize,
            transcendentals=0,
        ),
        name="fused_mlp",
        interpret=interpret,
    )(
        y.reshape(rows, dim),
        shortcut.reshape(rows, dim),
        w1,
        b1.astype(jnp.float32).reshape(1, hidden),
        w2,
        b2.astype(jnp.float32).reshape(1, dim),
    )
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _fused_mlp(y, w1, b1, w2, b2, shortcut, interpret):
    def run(y, shortcut, w1, b1, w2, b2):
        t = tiles(math.prod(y.shape[:-1]), *w1.shape, y.dtype)
        if t is None:
            raise ValueError(
                f"fused_mlp: {y.shape} through {w1.shape} has no tiles "
                f"(whole {LANES}-lane widths, rows a multiple of a row tile)"
            )
        return _pallas_mlp(
            y, shortcut, w1, b1, w2, b2, t=t, interpret=interpret
        )

    return per_device(
        run, "fused_mlp", y, shortcut, w1, b1, w2, b2, replicated=4
    )


def _fused_fwd(y, w1, b1, w2, b2, shortcut, interpret):
    out = _fused_mlp(y, w1, b1, w2, b2, shortcut, interpret)
    return out, (y, w1, b1, w2, b2, shortcut)


def _fused_bwd(interpret, res, g):
    _, vjp = jax.vjp(reference_mlp, *res)
    return vjp(g)


_fused_mlp.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_mlp(
    y: jax.Array,
    w1: jax.Array,
    b1: jax.Array,
    w2: jax.Array,
    b2: jax.Array,
    shortcut: jax.Array,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``shortcut + (gelu(y w1 + b1) w2 + b2)`` in one kernel. y,
    shortcut: (B, ..., dim), the leading axis the one a mesh splits;
    w1: (dim, hidden), b1: (hidden,), w2: (hidden, dim), b2: (dim,), in
    the dtype the parameters are stored in -> (B, ..., dim) in ``y``'s
    dtype. The rows of one device have to satisfy :func:`tiles`
    (``ValueError`` otherwise);
    ``ops.mlp.mlp`` asks it and runs everything else through the
    reference. Differentiable via custom VJP (XLA recompute through
    ``ops.mlp.reference_mlp``)."""
    if interpret is None:
        backend = jax.default_backend()
        require_accelerator(backend, "fused_mlp")
        interpret = backend == "cpu"
    return _fused_mlp(y, w1, b1, w2, b2, shortcut, interpret)
