"""Host-torch fallback for Model Zoo models that don't convert to JAX.

SURVEY.md §7 "Hard parts": weight conversion for *arbitrary* zoo
architectures can't be guaranteed; the pragmatic fallback keeps those
models runnable behind the same engine interface. It executes on the
HOST CPU (torch here is CPU-only) while the replica holds a chip lease
it does not use — correct, just not accelerated, and the caller says so
loudly when it picks this path (model-runner ``Pipeline``).
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def torch_available() -> bool:
    try:
        import torch  # noqa: F401

        return True
    except ImportError:
        return False


class TorchFallbackRunner:
    """predict(NHWC numpy) -> NHWC numpy via a torchscript/state-dict model."""

    def __init__(self, module=None, torchscript_path: Optional[str] = None):
        import torch

        self._torch = torch
        if module is None:
            if torchscript_path is None:
                raise ValueError("need a module or a torchscript path")
            module = torch.jit.load(torchscript_path, map_location="cpu")
        self.module = module.eval()

    def predict(self, images: np.ndarray) -> np.ndarray:
        """Channels-last in/out; handles (B, H, W, C) images and
        (B, D, H, W, C) volumes (torch modules are channels-first)."""
        torch = self._torch
        if images.ndim == 5:
            to_cf, to_cl = (0, 4, 1, 2, 3), (0, 2, 3, 4, 1)
        else:
            to_cf, to_cl = (0, 3, 1, 2), (0, 2, 3, 1)
        x = torch.from_numpy(np.ascontiguousarray(images)).permute(*to_cf)
        with torch.no_grad():
            y = self.module(x)
        if isinstance(y, (list, tuple)):
            y = y[0]
        return y.detach().cpu().permute(*to_cl).numpy()
