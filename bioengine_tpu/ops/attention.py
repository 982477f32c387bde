"""``softmax(q k^T * scale) v`` for model code, and which program ran it.

Two entries, and this module is the one place that chooses between the
programs behind them, from what the code can observe while a program is
traced:

:func:`attention` takes ``(B, heads, N, d)`` operands. On a TPU backend
it is the fused Pallas kernel (``ops/pallas/attention.py``: the scores
never reach HBM), anywhere else :func:`reference_attention`, the
plain-XLA statement of the same arithmetic in f32 (tier-1 on the CPU,
the f32 golden tests).

:func:`packed_attention` is what cpsam's ``SAMAttention`` calls. It takes
the operands as their producer writes them: the qkv projection's output
``(B, N, 3 * dim)`` and the two relative-position tables. Where the
lanes line up on a TPU (two 64-wide heads to the 128 lanes, H + W = 64:
cpsam's global blocks) the packed kernel cuts head pairs straight out of
that array and writes the layout the output projection reads, so no
attention operand is relaid in HBM; every other shape and backend goes
through :func:`unpacked_attention`, the one place the head transposes
and concatenations live, into :func:`attention`.

Every call counts itself in ``attention_traced_total{path, tokens}``;
the program cache takes the counter's rise over a build, so
``describe()`` shows for each compiled program how many attention calls
it holds on which path (``{"packed:1024": 24}`` for a served cpsam
program).

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
    "attention calls traced into a program, by the path taken (packed = "
    "the Pallas kernel over the projection's own layout, fused = the "
    "Pallas kernel over (B, heads, N, d) operands, xla = the plain "
    "reference) and N",
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


def key_positions(H: int, W: int, dtype) -> jax.Array:
    """(H * W, H + W): key n = (n // W, n % W) as the one-hot of its row
    beside the one-hot of its column. Exact in any dtype."""
    return jnp.concatenate(
        [
            jnp.repeat(jnp.eye(H, dtype=dtype), W, axis=0),
            jnp.tile(jnp.eye(W, dtype=dtype), (H, 1)),
        ],
        axis=-1,
    )


def relative_rows(table: jax.Array, size: int) -> jax.Array:
    """(2 * size - 1, hd) -> (size, size, hd): ``[i, j] = table[i - j +
    size - 1]``, the table's row for query coordinate i against key
    coordinate j (SAM's ``get_rel_pos`` at equal extents)."""
    at = jnp.arange(size)
    return table[at[:, None] - at[None, :] + size - 1]


def unpacked_attention(attend, qkv, rel_h, rel_w, grid, heads) -> jax.Array:
    """:func:`packed_attention`'s operands relaid to ``(B, heads, N, .)``
    and run through ``attend(q', k', v, scale=1.0)``. The bias of query
    n = (h, w) against key (k_h, k_w) is ``q[n] . rel_h[h - k_h + H - 1]
    + q[n] . rel_w[w - k_w + W - 1]`` (SAM's ``add_decomposed_rel_pos``,
    unscaled q); with ``q' = [q hd^-1/2, bias_h[n, :], bias_w[n, :]]``
    and ``k' = [k, onehot(k_h, H), onehot(k_w, W)]`` the contraction
    ``q' . k'`` is the biased score term for term, at depth hd + H + W.
    The only place these relayouts live: the path of every shape the
    packed kernel does not take, and what its backward pass recomputes
    through."""
    B, N, _ = qkv.shape
    H, W = grid
    q, k, v = (
        jnp.moveaxis(x, 2, 1)  # (B, heads, N, hd)
        for x in jnp.moveaxis(qkv.reshape(B, N, 3, heads, -1), 2, 0)
    )
    q_grid = q.reshape(B, heads, H, W, -1)
    bias_h = jnp.einsum("bnhwc,hkc->bnhwk", q_grid, relative_rows(rel_h, H))
    bias_w = jnp.einsum("bnhwc,wkc->bnhwk", q_grid, relative_rows(rel_w, W))
    q_fold = jnp.concatenate(
        [
            q * (q.shape[-1] ** -0.5),
            bias_h.reshape(B, heads, N, H),
            bias_w.reshape(B, heads, N, W),
        ],
        axis=-1,
    )
    pos = key_positions(H, W, qkv.dtype)
    k_fold = jnp.concatenate(
        [k, jnp.broadcast_to(pos, (B, heads) + pos.shape)], axis=-1
    )
    out = attend(q_fold, k_fold, v, scale=1.0)  # (B, heads, N, hd)
    return jnp.moveaxis(out, 1, 2).reshape(B, N, -1)


def packed_attention(
    qkv, rel_h, rel_w, *, grid: tuple[int, int], heads: int
) -> jax.Array:
    """Attention with SAM's decomposed relative-position bias over the
    operands as their producer writes them. qkv: (B, N, 3 * dim), the
    projection's output (q, k, v side by side, each head by head);
    rel_h: (2H - 1, hd), rel_w: (2W - 1, hd), the relative-position
    tables at the extent of ``grid = (H, W)``, ``H * W == N``, in the
    dtype of qkv -> (B, N, dim), the layout the output projection
    reads.

    On a TPU backend, where two heads fill the 128 lanes and the bias
    rows fill one head's depth (``pallas.attention.packs``; cpsam's
    global blocks: hd 64 on a 32 x 32 grid), the packed kernel reads
    these arrays as they are, forms the bias rows itself, and the call
    counts ``packed``. Every other shape and backend unpacks to
    :func:`attention`'s operands, which counts ``fused`` or ``xla``."""
    B, N, width = qkv.shape
    if jax.default_backend() == "tpu" and B % mesh_axes(qkv)[1] == 0:
        from bioengine_tpu.ops.pallas import attention as kernels

        if kernels.packs(N, width // 3, grid, heads):
            ATTENTION_TRACED.labels("packed", N).inc()
            return kernels.packed_flash_attention(
                qkv, rel_h, rel_w, grid=grid, heads=heads
            )
    return unpacked_attention(attention, qkv, rel_h, rel_w, grid, heads)


def counted(counter, since: Optional[dict[str, int]] = None) -> dict[str, int]:
    """A two-label counter's series as one dict, ``{"<a>:<b>": n}``, or
    with ``since`` (an earlier reading) only what rose, by how much:
    the calls of whatever was traced in between."""
    now = {f"{a}:{b}": int(child.value) for (a, b), child in counter.items()}
    if since is None:
        return now
    return {k: n - since.get(k, 0) for k, n in now.items() if n > since.get(k, 0)}


def traced_paths(since: Optional[dict[str, int]] = None) -> dict[str, int]:
    """``{"fused:1024": 24, ...}``: ``attention_traced_total``'s series
    as one dict, or with ``since`` (an earlier reading) only what rose:
    the attention calls of whatever was traced in between."""
    return counted(ATTENTION_TRACED, since)
