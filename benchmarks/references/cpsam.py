"""Plain reference of cpsam (Pachitariu, Rariden, Stringer 2025,
``cellpose/vit_sam.py``): the segment-anything ViT image encoder
(Kirillov et al. 2023, ``ImageEncoderViT``: learned position embedding,
pre-norm blocks, decomposed relative-position bias added from the
UNSCALED query, 1x1 + 3x3 neck with channel LayerNorms) with attention
made global in every block (``blk.window_size = 0``: all 1024 tokens of
a 256 px tile attend to each other, SAM's 14-token windows are gone) and
one transposed convolution of stride = patch back to 3 maps per pixel.

Float32 ``jax.numpy``; imports nothing of the program. Written for the
grid the published tables are stored at (input = patch * pretrain_grid,
256 px for cpsam), which is the only tile size the benchmark sends, so
no table is ever resized.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import _common as c

DEFAULTS = dict(
    patch_size=8, dim=1024, depth=24, num_heads=16, mlp_ratio=4.0,
    window_size=0, global_attn_indexes=tuple(range(24)), neck_dim=256,
    pretrain_grid=32,
)


def _cfg(kwargs: dict) -> dict:
    k = {**DEFAULTS, **kwargs}
    if k["window_size"] or set(k["global_attn_indexes"]) != set(range(k["depth"])):
        raise ValueError("cpsam attends globally in every block: no windows here")
    return k


def param_shapes(kwargs: dict, in_channels: int) -> dict[str, tuple[int, ...]]:
    k = _cfg(kwargs)
    p, d, hd = k["patch_size"], k["dim"], k["dim"] // k["num_heads"]
    hidden = int(d * k["mlp_ratio"])
    s: dict[str, tuple[int, ...]] = {
        "encoder/patch_embed/kernel": (p, p, in_channels, d),
        "encoder/patch_embed/bias": (d,),
        "encoder/pos_embed": (1, k["pretrain_grid"], k["pretrain_grid"], d),
        "encoder/neck_conv1/kernel": (1, 1, d, k["neck_dim"]),
        "encoder/neck_conv2/kernel": (3, 3, k["neck_dim"], k["neck_dim"]),
        "out/kernel": (p, p, k["neck_dim"], 3),
        "out/bias": (3,),
    }
    for n in ("neck_norm1", "neck_norm2"):
        s[f"encoder/{n}/scale"] = (k["neck_dim"],)
        s[f"encoder/{n}/bias"] = (k["neck_dim"],)
    for i in range(k["depth"]):
        pre = f"encoder/block{i}/"
        extent = k["pretrain_grid"]
        for n in ("norm1", "norm2"):
            s[f"{pre}{n}/scale"] = (d,)
            s[f"{pre}{n}/bias"] = (d,)
        s[f"{pre}attn/qkv/kernel"] = (d, 3 * d)
        s[f"{pre}attn/qkv/bias"] = (3 * d,)
        s[f"{pre}attn/proj/kernel"] = (d, d)
        s[f"{pre}attn/proj/bias"] = (d,)
        s[f"{pre}attn/rel_pos_h"] = (2 * extent - 1, hd)
        s[f"{pre}attn/rel_pos_w"] = (2 * extent - 1, hd)
        s[f"{pre}mlp_lin1/kernel"] = (d, hidden)
        s[f"{pre}mlp_lin1/bias"] = (hidden,)
        s[f"{pre}mlp_lin2/kernel"] = (hidden, d)
        s[f"{pre}mlp_lin2/bias"] = (d,)
    return s


def _attention(params, pre: str, x, heads: int, precision: str):
    """(B, S, S, dim) grid -> same, all S*S tokens attending to each
    other, with the decomposed relative-position bias."""
    b, s, _, d = x.shape
    hd = d // heads
    qkv = c.dense(x, params[f"{pre}qkv/kernel"], params[f"{pre}qkv/bias"], precision)
    qkv = qkv.reshape(b, s * s, 3, heads, hd)
    q, k, v = (jnp.moveaxis(qkv[:, :, i], 2, 1) for i in range(3))  # (b,nh,N,hd)
    qq, kq = c.quantize(q, precision), c.quantize(k, precision)
    scores = jnp.einsum(
        "bnqc,bnkc->bnqk", qq * hd**-0.5, kq, precision=c.HIGHEST
    )
    idx = jnp.arange(s)[:, None] - jnp.arange(s)[None, :] + (s - 1)
    rel_h = c.quantize(params[f"{pre}rel_pos_h"][idx], precision)  # (s,s,hd)
    rel_w = c.quantize(params[f"{pre}rel_pos_w"][idx], precision)
    q_grid = qq.reshape(b, heads, s, s, hd)
    bias_h = jnp.einsum("bnhwc,hkc->bnhwk", q_grid, rel_h, precision=c.HIGHEST)
    bias_w = jnp.einsum("bnhwc,wkc->bnhwk", q_grid, rel_w, precision=c.HIGHEST)
    scores = scores.reshape(b, heads, s, s, s, s)
    scores = scores + bias_h[..., :, None] + bias_w[..., None, :]
    probs = jax.nn.softmax(scores.reshape(b, heads, s * s, s * s), axis=-1)
    out = jnp.einsum(
        "bnqk,bnkc->bnqc", c.quantize(probs, precision),
        c.quantize(v, precision), precision=c.HIGHEST,
    )
    out = jnp.moveaxis(out, 1, 2).reshape(b, s, s, d)
    return c.dense(
        out, params[f"{pre}proj/kernel"], params[f"{pre}proj/bias"], precision
    )


def forward(params: dict, x, kwargs: dict, precision: str = "f32"):
    """(B, 256, 256, C_in) -> (B, 256, 256, 3): flow_y, flow_x, cellprob."""
    k = _cfg(kwargs)
    p, heads = k["patch_size"], k["num_heads"]
    b, h, w, _ = x.shape
    g = k["pretrain_grid"]
    if (h, w) != (p * g, p * g):
        raise ValueError(f"this reference is written for {p * g}x{p * g} tiles")
    x = c.conv(
        x.astype(jnp.float32), params["encoder/patch_embed/kernel"],
        params["encoder/patch_embed/bias"], precision, stride=p, padding="VALID",
    )
    x = x + params["encoder/pos_embed"]
    for i in range(k["depth"]):
        pre = f"encoder/block{i}/"
        y = c.layer_norm(x, params[f"{pre}norm1/scale"], params[f"{pre}norm1/bias"])
        x = x + _attention(params, f"{pre}attn/", y, heads, precision)
        y = c.layer_norm(x, params[f"{pre}norm2/scale"], params[f"{pre}norm2/bias"])
        y = c.dense(
            y, params[f"{pre}mlp_lin1/kernel"], params[f"{pre}mlp_lin1/bias"],
            precision,
        )
        y = jax.nn.gelu(y, approximate=False)
        y = c.dense(
            y, params[f"{pre}mlp_lin2/kernel"], params[f"{pre}mlp_lin2/bias"],
            precision,
        )
        x = x + y
    x = c.conv(x, params["encoder/neck_conv1/kernel"], None, precision)
    x = c.layer_norm(
        x, params["encoder/neck_norm1/scale"], params["encoder/neck_norm1/bias"]
    )
    x = c.conv(x, params["encoder/neck_conv2/kernel"], None, precision)
    x = c.layer_norm(
        x, params["encoder/neck_norm2/scale"], params["encoder/neck_norm2/bias"]
    )
    return c.conv_transpose(x, params["out/kernel"], params["out/bias"], precision)
