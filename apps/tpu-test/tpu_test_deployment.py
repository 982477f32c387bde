"""Minimal TPU sanity-check deployment — the tpu-native analog of the
reference's gpu-test app (ref apps/gpu-test/gpu_test_deployment.py:34-77:
ping + `nvidia-smi -L` + CUDA_VISIBLE_DEVICES). Here the device probe is
`jax.devices()` plus a tiny jitted matmul that proves the XLA backend is
alive, and the env report covers the TPU/JAX variables instead of CUDA.
Stdlib + jax only so the deployment is cheap to schedule.
"""

import os
import time

from bioengine_tpu.rpc import schema_method

_TPU_ENV_KEYS = (
    "JAX_PLATFORMS",
    "TPU_CHIPS_PER_HOST_BOUNDS",
    "TPU_HOST_BOUNDS",
    "TPU_WORKER_ID",
    "TPU_ACCELERATOR_TYPE",
    "XLA_FLAGS",
)


class TpuTest:
    def __init__(self) -> None:
        self.start_time = time.time()

    @schema_method
    async def ping(self, context=None):
        """Cheap liveness probe; does not touch the XLA backend."""
        return {
            "status": "ok",
            "uptime": time.time() - self.start_time,
            "jax_platforms": os.environ.get("JAX_PLATFORMS"),
        }

    @schema_method
    async def tpu_info(self, context=None):
        """Enumerate visible XLA devices and run one jitted matmul.

        Returns platform, device list (kind/id/process), and the result
        norm of a 128x128 bf16 matmul as proof the backend executes.
        """
        import jax
        import jax.numpy as jnp

        devices = [
            {
                "id": d.id,
                "platform": d.platform,
                "device_kind": d.device_kind,
                "process_index": d.process_index,
            }
            for d in jax.devices()
        ]
        x = jnp.ones((128, 128), jnp.bfloat16)
        y = jax.jit(lambda a: a @ a)(x)
        norm = float(jnp.linalg.norm(y.astype(jnp.float32)))
        return {
            "backend": jax.default_backend(),
            "device_count": len(devices),
            "devices": devices,
            "matmul_norm": norm,
            "env": {k: os.environ.get(k) for k in _TPU_ENV_KEYS},
        }

    @schema_method
    async def memory_info(self, context=None):
        """Per-device memory stats where the backend exposes them."""
        import jax

        stats = []
        for d in jax.devices():
            try:
                s = d.memory_stats() or {}
            except Exception:
                s = {}
            stats.append(
                {
                    "id": d.id,
                    "bytes_in_use": s.get("bytes_in_use"),
                    "bytes_limit": s.get("bytes_limit"),
                    "peak_bytes_in_use": s.get("peak_bytes_in_use"),
                }
            )
        return {"devices": stats}
