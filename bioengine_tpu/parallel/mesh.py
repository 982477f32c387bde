"""Device-mesh construction for BioEngine-TPU.

The framework's parallelism axes:

- ``dp`` — data parallel (batch sharding; gradients all-reduced over ICI)
- ``sp`` — spatial/sequence parallel (image tiles with halo exchange, or
  token-sequence shards for ring attention)
- ``tp`` — tensor parallel (Megatron-style weight sharding,
  parallel/tensor_parallel.py)

The reference has no device-mesh concept at all — its unit of parallelism
is a whole Ray Serve replica (ref apps/proxy_deployment.py:36-44). Here a
replica *owns* a mesh, and scaling happens in units of replicas, each with
a fixed sub-mesh, so XLA programs never need recompiling on scale events
(see SURVEY.md §7 "Replica elasticity vs. XLA's static world").
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh description, serializable into app manifests."""

    axes: Mapping[str, int]  # ordered axis name -> size; -1 = fill

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = dict(self.axes)
        fill_axes = [k for k, v in sizes.items() if v == -1]
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if n_devices % fixed:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed axes {sizes}"
            )
        remaining = n_devices // fixed
        if not fill_axes:
            if fixed != n_devices:
                raise ValueError(
                    f"Mesh {sizes} needs {fixed} devices, have {n_devices}"
                )
            return sizes
        if len(fill_axes) > 1:
            raise ValueError("At most one axis may be -1")
        sizes[fill_axes[0]] = remaining
        return sizes


@dataclasses.dataclass(frozen=True)
class VirtualMeshSpec:
    """Hardware-neutral mesh description for a whole DEPLOYMENT.

    The virtual-device layer (VirtualFlow's decoupling, PAPERS.md): a
    deployment declares ``stages`` (the cross-host pipeline axis — each
    stage placeable on a different host's chip lease) and per-stage
    ``axes`` (dp/tp over whatever chips that stage's lease resolves to,
    ``-1`` = fill). The SAME spec then maps onto a v5e-1, a v5e-8, a
    two-host mesh, or a forced-host-device CPU mesh: the planner
    (serving/mesh_plan.py) picks hosts, and each shard's engine resolves
    ``axes`` over its concrete lease via :meth:`stage_axes` — app code
    never names a topology.
    """

    stages: int = 1
    axes: Mapping[str, int] = dataclasses.field(
        default_factory=lambda: {"dp": -1}
    )

    def stage_axes(self, n_devices: int) -> dict[str, int]:
        """Resolve the per-stage axes over one stage's concrete lease."""
        return MeshSpec(dict(self.axes)).resolve(n_devices)

    def shape(self, n_devices_per_stage: int) -> dict[str, int]:
        """The logical mesh shape this spec yields on a concrete
        topology — ``pp`` (pipeline/stage axis) first, then the
        resolved per-stage axes."""
        out: dict[str, int] = {}
        if self.stages > 1:
            out["pp"] = self.stages
        out.update(self.stage_axes(n_devices_per_stage))
        return out


def make_mesh(
    axes: Mapping[str, int],
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named Mesh over ``devices`` (default: all local devices).

    Device ordering follows JAX's enumeration, which on TPU follows the
    physical torus — adjacent mesh coordinates land on ICI neighbours, so
    ``psum`` over the innermost axis rides the fastest links.
    """
    devices = list(devices if devices is not None else jax.devices())
    sizes = MeshSpec(axes).resolve(len(devices))
    arr = np.array(devices).reshape(tuple(sizes.values()))
    return Mesh(arr, tuple(sizes.keys()))


def sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_device_mesh(n: int = 1, axis: str = "dp") -> Mesh:
    """A mesh over the first ``n`` local devices (single-replica case)."""
    return make_mesh({axis: n}, jax.devices()[:n])

