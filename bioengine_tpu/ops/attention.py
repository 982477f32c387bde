"""``softmax(q k^T * scale) v`` for model code, and which program ran it.

:func:`attention` is what a model calls (cpsam's ``SAMAttention``): on a
TPU backend it is the fused Pallas kernel (``ops/pallas/attention.py``:
the scores never reach HBM), anywhere else :func:`reference_attention`,
the plain-XLA statement of the same arithmetic in f32 (tier-1 on the
CPU, the f32 golden tests). The choice is made from what the code can
observe while a program is traced, and every call counts itself in
``attention_traced_total{path, tokens}``; the program cache takes the
counter's rise over a build, so ``describe()`` shows for each compiled
program how many attention calls it holds on which path
(``{"fused:1024": 24}`` for a served cpsam program).

This module imports no Pallas: a CPU process never loads the kernel.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bioengine_tpu.utils import metrics

NEG_INF = -1e30

ATTENTION_TRACED = metrics.counter(
    "attention_traced_total",
    "attention calls traced into a program, by the path taken "
    "(fused = the Pallas kernel, xla = the plain reference) and N",
    ("path", "tokens"),
)


def reference_attention(q, k, v, causal=False, scale=None):
    """Plain-XLA attention in f32. q, k: (B, H, N, d_qk), v: (B, H, N,
    d_v); ``scale=None`` is d_qk**-0.5. Also what the kernel's custom
    VJP recomputes through (fused forward + XLA backward: correct grads
    everywhere; a fused backward kernel is a later optimization)."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhnd,bhmd->bhnm", qf * scale, kf)
    if causal:
        n = q.shape[2]
        row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        s = jnp.where((col <= row)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhnm,bhmd->bhnd", p, vf).astype(q.dtype)


def mesh_axes(x) -> tuple[tuple[str, ...], int]:
    """The axes of the mesh that ``x`` belongs to which a ``shard_map``
    may still split (not already manual, more than one device), and the
    number of devices they span: ``((), 1)`` for an array of a
    one-device program. Read off the array's type, so it works on
    tracers inside ``jit``."""
    mesh = jax.typeof(x).sharding.mesh
    axes = tuple(
        name
        for name, kind in zip(mesh.axis_names, mesh.axis_types)
        if kind != jax.sharding.AxisType.Manual and mesh.shape[name] > 1
    )
    devices = 1
    for name in axes:
        devices *= mesh.shape[name]
    return axes, devices


def attention(q, k, v, *, scale: Optional[float] = None) -> jax.Array:
    """q, k: (B, H, N, d_qk), v: (B, H, N, d_v) → (B, H, N, d_v). The
    fused kernel where the default backend is a TPU (split over the
    batch where the operands belong to a mesh whose devices divide it),
    the reference over the same operands anywhere else."""
    fused = (
        jax.default_backend() == "tpu"
        and q.shape[0] % mesh_axes(q)[1] == 0
    )
    ATTENTION_TRACED.labels("fused" if fused else "xla", q.shape[2]).inc()
    if fused:
        from bioengine_tpu.ops.pallas.attention import flash_attention

        return flash_attention(q, k, v, scale=scale)
    return reference_attention(q, k, v, scale=scale)


def traced_paths(since: Optional[dict[str, int]] = None) -> dict[str, int]:
    """``{"fused:1024": 24, ...}``: the counter's series as one dict, or
    with ``since`` (an earlier reading) only what rose, by how much: the
    attention calls of whatever was traced in between."""
    now = {
        f"{path}:{tokens}": int(child.value)
        for (path, tokens), child in ATTENTION_TRACED.items()
    }
    if since is None:
        return now
    return {k: n - since.get(k, 0) for k, n in now.items() if n > since.get(k, 0)}
