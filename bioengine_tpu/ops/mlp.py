"""``shortcut + lin2(gelu(lin1(y)))`` for model code, and which program
ran it.

The MLP half of a transformer block as one call. cpsam's ``SAMBlock``
calls :func:`mlp`; this module is the one place that chooses the
program behind it, from what the code can observe while a program is
traced: on a TPU backend, where the shapes fit the kernel's tiles
(``ops/pallas/mlp.py`` ``tiles``) and the mesh the operands belong to
divides the batch, the fused Pallas kernel, which keeps the hidden
activation in VMEM; anywhere else :func:`reference_mlp`, the plain-XLA
statement of the same arithmetic (tier-1 on the CPU, the f32 golden
tests, and what the kernel's custom VJP recomputes through).

The arithmetic, whoever runs it: both products take operands in ``y``'s
dtype (bf16 on the serving path: one MXU pass) and accumulate in f32;
the first bias and the activation are applied to that f32 accumulator,
which is rounded to ``y``'s dtype once, before the second product; the
second bias and the shortcut are added in f32 and the sum is rounded
once.

:func:`gelu` is *the* exact GELU of the repo's SAM block, in the form
the published model states it (torch's ``nn.GELU()``):
``0.5 h (1 + erf(h / sqrt 2))``. :func:`erf` is written out as the
clamped rational polynomial XLA itself expands an f32 ``erf`` to, in
plain multiplies and adds, because Mosaic has no lowering for
``lax.erf``: written once here, it is what the kernel and the reference
both evaluate. jax's ``nn.gelu(approximate=False)`` writes the same
function as ``0.5 x erfc(-x / sqrt 2)``, which XLA expands to a
two-branch chain of 72 operations an element with an ``exponential``;
that chain, in the operand prologue of the second matmul, cost the
served cpsam step a fifth of its time (PERF.md section 6, PR 36). The
two forms differ only in the far negative tail (x < -4, where |gelu| <
2e-4), below a bf16 ulp of anything the second product sums with.

Every call counts itself in ``mlp_traced_total{path, rows}``; the
program cache takes the counter's rise over a build, so ``describe()``
shows for each compiled program how many MLPs it holds on which path
(``{"fused:16384": 24}`` for a served cpsam program).

This module imports no Pallas: a CPU process never loads the kernel.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from bioengine_tpu.ops.attention import counted, mesh_axes
from bioengine_tpu.utils import metrics

MLP_TRACED = metrics.counter(
    "mlp_traced_total",
    "transformer-block MLPs traced into a program through ops.mlp, by "
    "the path taken (fused = the Pallas kernel that keeps the hidden "
    "activation in VMEM, xla = the plain reference) and the rows of one "
    "call",
    ("path", "rows"),
)

# erf(x) = x P(x^2) / Q(x^2) on |x| <= _ERF_CLAMP, where f32 erf has
# reached +-1 to half an ulp: the coefficients XLA's own f32 expansion
# uses (highest power first), about 1 ulp of f32 over the range
_ERF_CLAMP = 3.832506856900711
_ERF_P = (
    0.00022905065861350646,
    0.0034082910107109506,
    0.050955695062380861,
    0.18520832239976145,
    1.128379143519084,
)
_ERF_Q = (
    -1.1791602954361697e-7,
    0.000023547966471313185,
    0.0010179625278914885,
    0.014070470171167667,
    0.11098505178285362,
    0.49746925110067538,
    1.0,
)


def _horner(x, coefficients):
    total = jnp.full_like(x, coefficients[0])
    for c in coefficients[1:]:
        total = total * x + c
    return total


def erf(x: jax.Array, reciprocal=None) -> jax.Array:
    """f32 ``erf`` in multiplies, adds and one divide (no ``lax.erf``,
    which Mosaic cannot lower; no ``exp``). NaN stays NaN.
    ``reciprocal``, where given, is the caller's ``1 / q`` for q in
    [1, 72] (the kernel's: the hardware's estimate and a Newton step,
    with none of a general divide's special cases)."""
    clamped = jnp.clip(x, -_ERF_CLAMP, _ERF_CLAMP)
    x2 = clamped * clamped
    p, q = clamped * _horner(x2, _ERF_P), _horner(x2, _ERF_Q)
    return p / q if reciprocal is None else p * reciprocal(q)


def gelu(h: jax.Array, reciprocal=None) -> jax.Array:
    """Exact GELU, ``0.5 h (1 + erf(h / sqrt 2))``, in f32 whatever
    ``h``'s dtype; the result is f32 and the caller rounds it. Where
    f32 ``erf`` has reached -1 the factor is 0 itself, not what the
    polynomial's last bit leaves of it, so the far negative tail is -0
    (and -inf gives NaN) as the ``erfc`` form has them."""
    h = h.astype(jnp.float32)
    x = h * math.sqrt(0.5)
    return 0.5 * h * jnp.where(
        x <= -_ERF_CLAMP, 0.0, 1.0 + erf(x, reciprocal)
    )


def reference_mlp(y, w1, b1, w2, b2, shortcut) -> jax.Array:
    """Plain XLA. y, shortcut: (..., dim); w1: (dim, hidden), b1:
    (hidden,), w2: (hidden, dim), b2: (dim,) -> (..., dim) in ``y``'s
    dtype. Also what the kernel's custom VJP recomputes through."""
    dtype = y.dtype
    h = jnp.dot(
        y, w1.astype(dtype), preferred_element_type=jnp.float32
    ) + b1.astype(jnp.float32)
    out = jnp.dot(
        gelu(h).astype(dtype), w2.astype(dtype),
        preferred_element_type=jnp.float32,
    ) + b2.astype(jnp.float32)
    return (shortcut.astype(jnp.float32) + out).astype(dtype)


def mlp(y, w1, b1, w2, b2, shortcut) -> jax.Array:
    """``shortcut + (gelu(y w1 + b1) w2 + b2)``. y, shortcut: (B, ...,
    dim), the leading axis the one a mesh may split; the weights as the
    parameter tree holds them (any float dtype; they are used in
    ``y``'s) -> (B, ..., dim) in ``y``'s dtype. The fused kernel where
    the default backend is a TPU, the mesh the operands belong to
    divides the batch and ``pallas.mlp.tiles`` finds tiles for the rows
    of one device; the reference anywhere else."""
    rows = math.prod(y.shape[:-1])
    if jax.default_backend() == "tpu":
        devices = mesh_axes(y)[1]
        if y.shape[0] % devices == 0:
            from bioengine_tpu.ops.pallas import mlp as kernels

            if kernels.tiles(rows // devices, *w1.shape, y.dtype) is not None:
                MLP_TRACED.labels("fused", rows).inc()
                return kernels.fused_mlp(y, w1, b1, w2, b2, shortcut)
    MLP_TRACED.labels("xla", rows).inc()
    return reference_mlp(y, w1, b1, w2, b2, shortcut)


def traced_paths(since: Optional[dict[str, int]] = None) -> dict[str, int]:
    """``{"fused:16384": 24, ...}``: ``mlp_traced_total``'s series as
    one dict, or with ``since`` (an earlier reading) only what rose."""
    return counted(MLP_TRACED, since)
