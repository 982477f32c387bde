"""Spatial (image-domain) parallelism with halo exchange.

The reference's closest analog is *serial* tiling: fibsem-mito-analysis
cuts a large EM image into 512^2 tiles and calls the model per tile over
RPC (ref apps/fibsem-mito-analysis/analysis_deployment.py:10-14), and
bioimageio blockwise prediction does the same in-process. Neither is
parallel. Here the first spatial axis — image height, or stack depth
for volumetric (B, D, H, W, C) models — is sharded over the mesh's
``sp`` axis and convolutional halos are exchanged with ``ppermute``
over ICI: one jitted program, N chips, no stitching artifacts. With
halo >= receptive radius the interior is bit-exact vs the unsharded
model; multi-layer models differ only within the receptive radius of
the GLOBAL borders, where block-level zero padding stands in for the
unsharded model's per-layer padding (see ``spatial_shard_apply``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def halo_exchange(x: jax.Array, halo: int, axis_name: str) -> jax.Array:
    """Pad a block sharded on array axis 1 with ``halo`` slices from
    ring neighbours.

    x: (B, H_local, W, C) — or (B, D_local, H, W, C) for volumes —
    inside shard_map; only axis 1 is touched, so any rank works.
    Returns the block grown by 2*halo along axis 1. Edge shards receive
    zeros (same as a zero-padded unsharded conv).
    """
    if halo == 0:
        return x
    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    top_rows = x[:, :halo]          # my first rows -> neighbour below...
    bot_rows = x[:, -halo:]         # my last rows -> neighbour above
    # Send my bottom rows DOWN the ring (shard i -> i+1) so each shard
    # receives its upper neighbour's bottom rows.
    from_above = jax.lax.ppermute(
        bot_rows, axis_name, [(i, (i + 1) % n) for i in range(n)]
    )
    # Send my top rows UP the ring (i -> i-1): receive lower neighbour's top.
    from_below = jax.lax.ppermute(
        top_rows, axis_name, [(i, (i - 1) % n) for i in range(n)]
    )
    # Zero out wrap-around contributions at the edges.
    from_above = jnp.where(idx == 0, jnp.zeros_like(from_above), from_above)
    from_below = jnp.where(
        idx == n - 1, jnp.zeros_like(from_below), from_below
    )
    return jnp.concatenate([from_above, x, from_below], axis=1)


def spatial_shard_apply(
    apply_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    halo: int,
    axis: str = "sp",
    rank: int = 4,
) -> Callable[[Any, jax.Array], jax.Array]:
    """Lift ``apply_fn`` to an SPMD program sharded on its first
    spatial axis: (B,H,W,C) height-sharded at ``rank=4``, volumetric
    (B,D,H,W,C) depth-sharded at ``rank=5``.

    The wrapped fn takes the FULL array; jit + shard_map split axis 1
    over ``axis``, exchange halos, run the model per-shard on the
    haloed block, and crop the halo off the output. Exact for models
    whose receptive-field radius <= halo and whose output stride is 1,
    with one caveat: within the receptive radius of the GLOBAL top and
    bottom borders, a multi-layer model sees block-level zero padding
    instead of the unsharded model's per-layer zero padding, so border
    slices can differ (a boundary-condition approximation of the same
    order as tiled/blended inference; interiors are bit-exact). A
    single conv layer matches everywhere.

    ``halo`` must not exceed the local shard extent (global size /
    n_shards): ppermute reaches immediate ring neighbours only.
    """
    spec = _axis1_spec(axis, rank)
    n_shards = mesh.shape[axis]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), spec),
        out_specs=spec,
    )
    def sharded(params, block):
        if halo > block.shape[1]:
            raise ValueError(
                f"halo {halo} exceeds the local shard extent "
                f"{block.shape[1]} (axis '{axis}' split {n_shards} ways) — "
                f"halo exchange reaches immediate neighbours only; use "
                f"fewer shards or a smaller halo"
            )
        haloed = halo_exchange(block, halo, axis)
        out = apply_fn(params, haloed)
        return out[:, halo:-halo] if halo else out

    jitted = jax.jit(sharded)

    def wrapper(params, x):
        if np.ndim(x) != rank:
            raise ValueError(
                f"spatial_shard_apply was built with rank={rank} but got a "
                f"rank-{np.ndim(x)} input — pass rank={np.ndim(x)} (4 for "
                f"(B,H,W,C) images, 5 for (B,D,H,W,C) volumes)"
            )
        return jitted(params, x)

    return wrapper


def _axis1_spec(axis: str, rank: int) -> P:
    """PartitionSpec sharding array axis 1 over ``axis``."""
    return P(None, axis, *([None] * (rank - 2)))


def shard_image(mesh: Mesh, image, axis: str = "sp"):
    """Place (B, H, W, C) or (B, D, H, W, C) with axis 1 (height /
    depth) sharded over ``axis``."""
    return jax.device_put(
        image,
        NamedSharding(mesh, _axis1_spec(axis, np.ndim(image))),
    )
