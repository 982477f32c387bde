"""What every plain reference shares: precision, weights, tiling.

Nothing here imports the program. A reference is float32 ``jax.numpy``
with every contraction at ``Precision.HIGHEST``; a lower precision is
modelled by rounding both operands of every contraction onto that
precision's grid first (``quantize``) and contracting the rounded values
exactly. ``"f32"`` is the reference, ``"bf16"`` what the configurations
state, ``"fp8"`` (e4m3, one scale per tensor) the step below it: the
control that has to come out as not correct.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("f32", "bf16", "fp8")
_E4M3_MAX = 448.0


def quantize(x, precision: str):
    """``x`` rounded onto the grid of ``precision``, returned as f32."""
    x = x.astype(jnp.float32)
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def dense(x, kernel, bias, precision: str):
    y = jnp.matmul(
        quantize(x, precision), quantize(kernel, precision), precision=HIGHEST
    )
    return y if bias is None else y + bias


def conv(x, kernel, bias, precision: str, stride: int = 1, padding="SAME"):
    y = jax.lax.conv_general_dilated(
        quantize(x, precision),
        quantize(kernel, precision),
        (stride, stride),
        padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST,
    )
    return y if bias is None else y + bias


def conv_transpose(x, kernel, bias, precision: str):
    """Transposed convolution whose stride equals its kernel size: each
    input pixel becomes one k x k output block. ``out[k*i + a] =
    kernel[k-1-a] * x[i]`` (the gradient-of-convolution convention, the
    one a checkpoint converted from torch expects)."""
    k = kernel.shape[0]
    b, h, w, _ = x.shape
    y = jnp.einsum(
        "bhwc,ijco->bhiwjo",
        quantize(x, precision),
        quantize(kernel[::-1, ::-1], precision),
        precision=HIGHEST,
    )
    return y.reshape(b, h * k, w * k, kernel.shape[-1]) + bias


def layer_norm(x, scale, bias, eps: float = 1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def model_kwargs(config: dict) -> dict:
    """The keyword arguments a configuration hands its architecture,
    less the input channels (the references take those apart)."""
    return {k: config[k] for k in config["model_kwargs"] if k != "in_channels"}


# ---- weights ----------------------------------------------------------------


def _std(name: str, shape: tuple[int, ...]) -> tuple[float, float]:
    """(mean, std) of one leaf: kernels at 1/sqrt(fan_in) so activations
    keep their scale through the depth, norm scales around 1, every bias
    and table small but not zero (a zero table hides a wrong gather)."""
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "kernel":
        return 0.0, 1.0 / math.sqrt(math.prod(shape[:-1]))
    if leaf == "scale":
        return 1.0, 0.1
    return 0.0, 0.05


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = abs(int(seed))
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def make_weights(shapes: dict[str, tuple[int, ...]], seed: int) -> dict:
    """Every leaf on the device, float32, in ONE jitted call from the
    seed. Flat ``{"a/b/kernel": array}``: the layout an ``.npz`` package
    stores and the references read."""
    names = sorted(shapes)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            mean, std = _std(name, shapes[name])
            noise = jax.random.normal(
                jax.random.fold_in(key, i), shapes[name], jnp.float32
            )
            out[name] = mean + std * noise
        return out

    return jax.jit(build)(seed_key(seed))


# ---- tiling (what ``default_blocksize_parameter`` / ``max_tile`` promise) ----


def tile_starts(size: int, tile: int, overlap: int) -> list[int]:
    """Offsets that cover [0, size) with ``overlap`` between tiles, the
    last tile clamped to end at ``size``."""
    stride = max(tile - overlap, 1)
    return sorted(
        {min(s, max(size - tile, 0)) for s in range(0, max(size - overlap, 1), stride)}
    )


def ramp(tile: int, overlap: int) -> np.ndarray:
    """Linear edge ramp, 1 inside: the blend weight of one tile axis."""
    r = np.ones(tile, np.float32)
    if overlap > 0:
        edge = np.linspace(1.0 / (overlap + 1), 1.0, overlap, dtype=np.float32)
        r[:overlap] = edge
        r[-overlap:] = edge[::-1]
    return r


def n_tiles(height: int, width: int, tile: int, overlap: int) -> int:
    return len(tile_starts(height, tile, overlap)) * len(
        tile_starts(width, tile, overlap)
    )


def predict(forward, image: np.ndarray, tile: int, max_tile: int, overlap: int,
            block: int = 4) -> np.ndarray:
    """What one request has to return. ``forward(tiles) -> outputs`` is
    the model on a block of equal tiles. An item above ``max_tile`` is
    cut into overlapping ``tile``-sized pieces, each predicted alone,
    and blended by the separable linear ramp; anything else is one
    forward pass. Blocks of ``block`` rows keep the float32 activations
    inside the chip's memory."""
    image = np.asarray(image, np.float32)
    n, h, w, _ = image.shape

    def run(rows: np.ndarray) -> np.ndarray:
        outs = [
            np.asarray(forward(jnp.asarray(rows[i : i + block])))
            for i in range(0, len(rows), block)
        ]
        return np.concatenate(outs)

    if max(h, w) <= max_tile:
        return run(image)
    ys, xs = tile_starts(h, tile, overlap), tile_starts(w, tile, overlap)
    weight2d = ramp(tile, overlap)[:, None] * ramp(tile, overlap)[None, :]
    out = None
    for b in range(n):
        coords = [(y, x) for y in ys for x in xs]
        tiles = np.stack([image[b, y : y + tile, x : x + tile] for y, x in coords])
        preds = run(tiles)
        acc = np.zeros((h, w, preds.shape[-1]), np.float64)
        norm = np.zeros((h, w, 1), np.float64)
        for (y, x), p in zip(coords, preds):
            acc[y : y + tile, x : x + tile] += p * weight2d[..., None]
            norm[y : y + tile, x : x + tile] += weight2d[..., None]
        item = (acc / norm).astype(np.float32)
        if out is None:
            out = np.zeros((n, *item.shape), np.float32)
        out[b] = item
    return out
