"""TPU topology discovery.

The reference discovers compute through Ray's GCS (nodes, GPUs,
ray.cluster_resources — ref bioengine/cluster/proxy_actor.py:332-350).
Here the source of truth is JAX's device enumeration: chips, their
generation, per-chip HBM, the host (process) each chip belongs to, and
sensible default mesh shapes for a replica's sub-mesh.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipInfo:
    device_id: int
    platform: str              # "tpu" | "cpu" | ...
    kind: str                  # e.g. "TPU v5 lite"
    process_index: int
    hbm_bytes: Optional[int] = None
    hbm_used_bytes: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class TpuTopology:
    chips: tuple[ChipInfo, ...]
    n_hosts: int
    platform: str

    @property
    def n_chips(self) -> int:
        return len(self.chips)

    @property
    def chips_per_host(self) -> int:
        return max(1, self.n_chips // max(1, self.n_hosts))

    def local_chips(self, process_index: Optional[int] = None) -> list[ChipInfo]:
        pi = (
            process_index
            if process_index is not None
            else int(os.environ.get("TPU_PROCESS_INDEX", 0))
        )
        return [c for c in self.chips if c.process_index == pi]

    def default_mesh_axes(self) -> dict[str, int]:
        """dp-major default: all chips data-parallel. Apps override via
        their manifest's mesh spec."""
        return {"dp": self.n_chips}

    def as_dict(self) -> dict:
        return {
            "platform": self.platform,
            "n_chips": self.n_chips,
            "n_hosts": self.n_hosts,
            "chips": [dataclasses.asdict(c) for c in self.chips],
        }


def detect_topology() -> TpuTopology:
    """Enumerate the visible accelerator topology via JAX. A CPU
    topology is returned only when CPU was asked for by name
    (utils/devices.py); a silent fallback raises NoAcceleratorError."""
    import jax

    from bioengine_tpu.utils.devices import require_accelerator

    devices = jax.devices()
    require_accelerator(devices[0].platform, "detect_topology")
    chips = []
    for d in devices:
        hbm = used = None
        try:
            stats = d.memory_stats()
            if stats:
                hbm = stats.get("bytes_limit")
                used = stats.get("bytes_in_use")
        except Exception:
            pass
        chips.append(
            ChipInfo(
                device_id=d.id,
                platform=d.platform,
                kind=getattr(d, "device_kind", d.platform),
                process_index=d.process_index,
                hbm_bytes=hbm,
                hbm_used_bytes=used,
            )
        )
    n_hosts = len({c.process_index for c in chips}) or 1
    platform = chips[0].platform if chips else "none"
    return TpuTopology(chips=tuple(chips), n_hosts=n_hosts, platform=platform)
