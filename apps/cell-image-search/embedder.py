"""TPU-native ViT embedder for cell crops.

Replaces the reference's torch-hub DINOv2 wrapper
(ref apps/cell-image-search/embedder.py:23-101: lazy CUDA load, fp16,
batch 64, ~500 img/s on one A100) with the framework's Flax ViT:

- bf16 matmuls on the MXU, flash-attention Pallas kernel on TPU;
- one jitted program per batch *bucket* (batches pad up to the bucket
  so arbitrary request sizes never trigger recompiles);
- data-parallel sharding over every local chip via the dp mesh — corpus
  embedding scales across a slice with zero code change (the reference's
  multi-GPU path was aspirational, SURVEY.md §6).

Pretrained DINOv2 weights convert from the torch checkpoint via
``bioengine_tpu.runtime.convert`` — one-time:
``bioengine models convert dinov2_vitb14.pth weights.npz --arch dinov2``
— then pass the npz as ``weights_path``. Without one the model runs
randomly initialized (deterministic seed), which preserves the full
pipeline shape for tests and benchmarks.
"""

from __future__ import annotations

import logging
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)


class ViTEmbedder:
    MODEL_NAME = "dinov2_vitb14"
    EMBED_DIM = 768
    INPUT_SIZE = 224

    def __init__(
        self,
        weights_path: Optional[str] = None,
        batch_bucket: int = 128,
        use_flash_attention: Optional[bool] = None,
    ) -> None:
        # the owning replica's chip lease (``bioengine_device_ids``),
        # set by the deployment before the first load; None = every
        # local device (stand-alone use)
        self.device_ids: Optional[list[int]] = None
        self.weights_path = weights_path
        self.batch_bucket = batch_bucket
        self.use_flash_attention = use_flash_attention
        self.pretrained = weights_path is not None
        self._model = None
        self._params = None
        self._embed_fn = None
        self._mesh = None
        import threading

        self._load_lock = threading.Lock()

    @property
    def loaded(self) -> bool:
        return self._model is not None

    def load(self) -> None:
        with self._load_lock:
            if self._embed_fn is None:
                self._load()

    def _load(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bioengine_tpu.models.vit import ViT
        from bioengine_tpu.parallel.mesh import make_mesh

        # Flash attention only pays off on LONG token sequences: at this
        # model's N=257 (224/14 patches + cls) the blocked Pallas kernel
        # measured ~3x SLOWER than XLA's fused attention on v5e (block
        # padding + f32 accumulation dominate short rows), so auto mode
        # keeps XLA attention below 1024 tokens.
        n_tokens = (self.INPUT_SIZE // 14) ** 2 + 1
        use_flash = self.use_flash_attention
        if use_flash is None:
            use_flash = jax.default_backend() == "tpu" and n_tokens >= 1024
        attn_fn = None
        if use_flash:
            from bioengine_tpu.ops.pallas import make_attn_fn

            attn_fn = make_attn_fn()

        model = ViT(
            patch_size=14, dim=768, depth=12, num_heads=12, attn_fn=attn_fn
        )
        if self.weights_path:
            from bioengine_tpu.runtime.convert import load_params_npz

            params = load_params_npz(self.weights_path)
            logger.info("loaded ViT weights from %s", self.weights_path)
        else:
            params = model.init(
                jax.random.key(0),
                jnp.zeros((1, self.INPUT_SIZE, self.INPUT_SIZE, 3)),
            )["params"]
            logger.warning(
                "no weights_path — running randomly-initialized ViT "
                "(pipeline-shape mode, embeddings are not DINOv2)"
            )

        # the mesh is built from the replica's lease, never from
        # jax.devices()[:dp]: two replicas on one host would otherwise
        # share the first chips while the controller books them apart
        if self.device_ids:
            from bioengine_tpu.runtime.engine import resolve_devices

            devices = resolve_devices(self.device_ids)
        else:
            devices = jax.local_devices()
        # dp over the largest power of two that divides the bucket
        dp = 1
        while dp * 2 <= len(devices) and self.batch_bucket % (dp * 2) == 0:
            dp *= 2
        mesh = make_mesh({"dp": dp}, devices[:dp])
        repl = NamedSharding(mesh, P())
        data_sh = NamedSharding(mesh, P("dp"))
        params = jax.device_put(params, repl)

        def fwd(params, images):
            emb = model.apply({"params": params}, images)  # (B, 768) f32
            norms = jnp.linalg.norm(emb, axis=-1, keepdims=True)
            return emb / jnp.maximum(norms, 1e-9)

        embed = jax.jit(fwd, in_shardings=(repl, data_sh), out_shardings=repl)

        self._model, self._params = model, params
        self._embed_fn, self._mesh = embed, mesh
        logger.info(
            "ViT embedder ready: backend=%s dp=%d flash_attention=%s "
            "pretrained=%s",
            jax.default_backend(), dp, use_flash, self.pretrained,
        )

    def embed_batch(
        self, images_rgb: list[np.ndarray], batch_size: Optional[int] = None
    ) -> np.ndarray:
        """List of (H, W, 3)-ish microscopy arrays → (N, 768) float32
        L2-normalised. Batches pad to ``batch_bucket`` so every call
        reuses one compiled program."""
        from normalizer import to_model_input

        if self._embed_fn is None:
            self.load()
        import jax.numpy as jnp

        bucket = batch_size or self.batch_bucket
        prepped = np.stack(
            [to_model_input(img, self.INPUT_SIZE) for img in images_rgb]
        )
        out = []
        for i in range(0, len(prepped), bucket):
            chunk = prepped[i : i + bucket]
            n = len(chunk)
            if n < bucket:
                chunk = np.pad(chunk, ((0, bucket - n), (0, 0), (0, 0), (0, 0)))
            emb = self._embed_fn(self._params, jnp.asarray(chunk))
            out.append(np.asarray(emb, np.float32)[:n])
        return np.vstack(out)

    def embed_single(self, image_rgb: np.ndarray) -> np.ndarray:
        return self.embed_batch([image_rgb])[0]
