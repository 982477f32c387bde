"""Pallas TPU kernels for the compute hot path.

Kernels run compiled via Mosaic on TPU. They run in interpreter mode
only where the CPU platform was asked for by name (JAX_PLATFORMS=cpu,
as the hermetic test suite sets it) — never as a quiet fallback.
"""

from bioengine_tpu.ops.pallas.attention import flash_attention, make_attn_fn

__all__ = ["flash_attention", "make_attn_fn"]
