"""Operations and least bytes of one cpsam forward pass, from shapes.

Counted: every matrix product, 2 flops per multiply-add; attention is
global over the whole token grid in every block (1024 tokens for a
256 px tile), as the reference has it. XLA's count exceeds this one by
the norms, softmax and GELU (a few percent). Least bytes: input read, output written, parameters read
once, as stored (f32).
"""

from __future__ import annotations

import math

from benchmarks.references._common import model_kwargs
from benchmarks.references.cpsam import DEFAULTS, param_shapes


def flops(shape: tuple[int, ...], kwargs: dict) -> float:
    k = {**DEFAULTS, **kwargs}
    b, h, w, cin = shape
    p, d, heads = k["patch_size"], k["dim"], k["num_heads"]
    hd = d // heads
    gh, gw = h // p, w // p
    n = gh * gw
    hidden = int(d * k["mlp_ratio"])
    total = 2.0 * n * (p * p * cin) * d  # patch embedding
    per_block = (
        2.0 * n * d * 3 * d                 # qkv
        + 2.0 * n * d * d                   # proj
        + 2 * 2.0 * heads * n * n * hd      # QK^T, PV
        + 2.0 * heads * n * (gh + gw) * hd  # rel-pos
        + 2 * 2.0 * n * d * hidden          # mlp
    )
    total += k["depth"] * per_block
    total += 2.0 * n * d * k["neck_dim"]
    total += 2.0 * n * 9 * k["neck_dim"] ** 2
    total += 2.0 * n * k["neck_dim"] * p * p * 3    # readout
    return b * total


def param_count(kwargs: dict, in_channels: int) -> int:
    return sum(math.prod(s) for s in param_shapes(kwargs, in_channels).values())


def min_bytes(shape: tuple[int, ...], kwargs: dict) -> float:
    b, h, w, cin = shape
    return 4.0 * (b * h * w * (cin + 3) + param_count(kwargs, cin))


def flops_per_pixel(kwargs: dict, in_channels: int, tile: int) -> float:
    """Per useful input pixel: one native tile's work over its pixels."""
    return flops((1, tile, tile, in_channels), kwargs) / (tile * tile)


# ---- what the readers call: a configuration, and the path's program key ----------


def flops_per_unit(config: dict) -> float:
    """Operations per unit of work as the path counts it: an input pixel."""
    return flops_per_pixel(
        model_kwargs(config), int(config["in_channels"]), int(config["native_tile"])
    )


def program_flops(key: tuple[int, ...], config: dict) -> float:
    """The image path's program key is the program's input shape."""
    return flops(key, model_kwargs(config))


def program_min_bytes(key: tuple[int, ...], config: dict) -> float:
    return min_bytes(key, model_kwargs(config))
