"""SAM ViT image encoder + cellpose readout — the *pretrained* cpsam
architecture.

The reference's cellpose-finetuning app exists to fine-tune the
pretrained Cellpose-SAM foundation model
(ref apps/cellpose-finetuning/main.py:2248 —
``models.CellposeModel(pretrained_model=...)``, default ``cpsam``;
model_template.py wraps ``cellpose.vit_sam.Transformer``). cpsam is the
segment-anything ViT-L image encoder (patch 8, 256x256 inputs, learned
position embeddings, decomposed relative-position attention, windowed
attention with periodic global blocks, 256-channel neck) with a
transposed-conv readout to cellpose's 3-channel map (flow_y, flow_x,
cellprob logits).

This module is the structurally-faithful flax twin of that public
architecture, so a converted cpsam torch checkpoint
(``runtime.convert.cpsam_name_map``) drops into ``model.init``'s exact
pytree and fine-tuning starts from the foundation weights instead of
random init. Parameter path names below are chosen to line up 1:1 with
the torch state_dict keys — change them only together with the name
map.

TPU notes: matmuls run bf16 on the MXU. Attention never writes its
(B*heads, N, N) scores to HBM: the decomposed rel-pos bias
``bias_h[n, k_h] + bias_w[n, k_w]`` is folded into the QK^T contraction
(``SAMAttention``: q gets the two bias rows appended, k the one-hot of
its own row and column, so ``q' k'^T`` is the biased score; for cpsam
64 + 32 + 32 = 128, the lane width) and ``softmax(q' k'^T) v`` runs
through ``ops.attention`` — the fused Pallas kernel on a TPU, its plain
XLA reference elsewhere. Adding the bias at score size instead (a 5-D
broadcast, a copy and an f32 softmax over 512 MB a block) was 60-65 % of
the served step's device time (PERF.md section 6, PR 27). Window
partition is a reshape (no data movement beyond layout). Shapes are
static per (H, W) bucket as everywhere else in the framework.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from bioengine_tpu.ops.attention import attention


def _resize_rel_pos(rel_pos: jnp.ndarray, needed: int) -> jnp.ndarray:
    """(L, head_dim) table -> (needed, head_dim) via linear resize (SAM
    interpolates when query/key extent differs from pretraining)."""
    if rel_pos.shape[0] == needed:
        return rel_pos
    return jax.image.resize(
        rel_pos.astype(jnp.float32),
        (needed, rel_pos.shape[1]),
        method="linear",
    ).astype(rel_pos.dtype)


def _rel_pos_gather(q_size: int, k_size: int, rel_pos: jnp.ndarray):
    """Decomposed relative-position table lookup (SAM's get_rel_pos):
    returns (q_size, k_size, head_dim)."""
    max_dist = 2 * max(q_size, k_size) - 1
    table = _resize_rel_pos(rel_pos, max_dist)
    coords = (
        jnp.arange(q_size)[:, None] * max(k_size / q_size, 1.0)
        - jnp.arange(k_size)[None, :] * max(q_size / k_size, 1.0)
        + (k_size - 1) * max(q_size / k_size, 1.0)
    )
    return table[coords.astype(jnp.int32)]


class SAMAttention(nn.Module):
    """Multi-head attention over a (B, H, W, dim) token grid with SAM's
    decomposed relative position bias.

    ``table_size`` is the PRETRAINING spatial extent the rel-pos tables
    were stored at (window size for windowed blocks, the pretrain grid
    for global ones): the parameters are declared at that checkpoint
    shape — so converted weights always load — and resized at use when
    the runtime grid differs (flax validates provided param shapes
    against the declared shape at apply time).

    The bias of query n = (h, w) against key (k_h, k_w) is
    ``q[n]·Rh[h, k_h] + q[n]·Rw[w, k_w]`` (SAM's
    ``add_decomposed_rel_pos``, unscaled q). It is not added to a score
    tensor: with ``q' = [q·hd^-½, bias_h[n, :], bias_w[n, :]]`` and
    ``k' = [k, onehot(k_h, H), onehot(k_w, W)]`` the contraction
    ``q'·k'`` is the biased score term for term, at depth hd + H + W and
    scale 1. The one-hots are exact in any dtype and the contraction
    accumulates in f32."""

    dim: int
    num_heads: int
    table_size: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, H, W, _ = x.shape
        nh, hd = self.num_heads, self.dim // self.num_heads
        qkv = nn.Dense(3 * self.dim, dtype=self.dtype, name="qkv")(x)
        qkv = qkv.reshape(B, H * W, 3, nh, hd)
        q, k, v = jnp.moveaxis(qkv, 2, 0)  # (B, N, nh, hd)
        q = jnp.moveaxis(q, 2, 1)  # (B, nh, N, hd)
        k = jnp.moveaxis(k, 2, 1)
        v = jnp.moveaxis(v, 2, 1)

        rel_h = self.param(
            "rel_pos_h",
            nn.initializers.zeros,
            (2 * self.table_size - 1, hd),
            jnp.float32,
        )
        rel_w = self.param(
            "rel_pos_w",
            nn.initializers.zeros,
            (2 * self.table_size - 1, hd),
            jnp.float32,
        )
        Rh = _rel_pos_gather(H, H, rel_h).astype(self.dtype)  # (H, H, hd)
        Rw = _rel_pos_gather(W, W, rel_w).astype(self.dtype)  # (W, W, hd)
        q_r = q.reshape(B, nh, H, W, hd)
        bias_h = jnp.einsum("bnhwc,hkc->bnhwk", q_r, Rh)
        bias_w = jnp.einsum("bnhwc,wkc->bnhwk", q_r, Rw)
        q_fold = jnp.concatenate(
            [
                q * (hd**-0.5),
                bias_h.reshape(B, nh, H * W, H),
                bias_w.reshape(B, nh, H * W, W),
            ],
            axis=-1,
        )
        key_pos = jnp.concatenate(
            [
                jnp.repeat(jnp.eye(H, dtype=self.dtype), W, axis=0),
                jnp.tile(jnp.eye(W, dtype=self.dtype), (H, 1)),
            ],
            axis=-1,
        )  # (N, H + W): key n = (n // W, n % W), one-hot twice
        k_fold = jnp.concatenate(
            [k, jnp.broadcast_to(key_pos, (B, nh, H * W, H + W))], axis=-1
        )

        out = attention(q_fold, k_fold, v, scale=1.0)  # (B, nh, N, hd)
        out = jnp.moveaxis(out, 1, 2).reshape(B, H, W, self.dim)
        return nn.Dense(self.dim, dtype=self.dtype, name="proj")(out)


def _window_partition(x, ws: int):
    """(B, H, W, C) -> (B*nw, ws, ws, C) with bottom/right padding."""
    B, H, W, C = x.shape
    ph, pw = (-H) % ws, (-W) % ws
    x = jnp.pad(x, ((0, 0), (0, ph), (0, pw), (0, 0)))
    Hp, Wp = H + ph, W + pw
    x = x.reshape(B, Hp // ws, ws, Wp // ws, ws, C)
    x = jnp.moveaxis(x, 2, 3).reshape(-1, ws, ws, C)
    return x, (Hp, Wp)


def _window_unpartition(x, ws: int, padded, orig):
    Hp, Wp = padded
    H, W = orig
    B = x.shape[0] // ((Hp // ws) * (Wp // ws))
    x = x.reshape(B, Hp // ws, Wp // ws, ws, ws, -1)
    x = jnp.moveaxis(x, 3, 2).reshape(B, Hp, Wp, -1)
    return x[:, :H, :W]


class SAMBlock(nn.Module):
    dim: int
    num_heads: int
    mlp_ratio: float = 4.0
    window_size: int = 0  # 0 = global attention
    table_size: int = 14  # stored rel-pos extent (see SAMAttention)
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        shortcut = x
        x = nn.LayerNorm(dtype=jnp.float32, name="norm1")(x).astype(
            self.dtype
        )
        if self.window_size > 0:
            win, padded = _window_partition(x, self.window_size)
            win = SAMAttention(
                self.dim, self.num_heads, self.table_size, self.dtype,
                name="attn",
            )(win)
            x = _window_unpartition(
                win, self.window_size, padded, x.shape[1:3]
            )
        else:
            x = SAMAttention(
                self.dim, self.num_heads, self.table_size, self.dtype,
                name="attn",
            )(x)
        x = shortcut + x
        y = nn.LayerNorm(dtype=jnp.float32, name="norm2")(x).astype(
            self.dtype
        )
        y = nn.Dense(
            int(self.dim * self.mlp_ratio), dtype=self.dtype,
            name="mlp_lin1",
        )(y)
        y = nn.gelu(y, approximate=False)
        y = nn.Dense(self.dim, dtype=self.dtype, name="mlp_lin2")(y)
        return x + y


class SAMEncoder(nn.Module):
    """segment-anything ImageEncoderViT, NHWC. Output: (B, gh, gw, 256)
    neck features at 1/patch resolution."""

    patch_size: int = 8
    dim: int = 1024
    depth: int = 24
    num_heads: int = 16
    mlp_ratio: float = 4.0
    window_size: int = 14
    global_attn_indexes: Sequence[int] = (5, 11, 17, 23)
    neck_dim: int = 256
    pretrain_grid: int = 32  # 256 px / patch 8
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        p = self.patch_size
        B, H, W, _ = x.shape
        gh, gw = H // p, W // p
        x = nn.Conv(
            self.dim, (p, p), strides=(p, p), dtype=self.dtype,
            name="patch_embed",
        )(x.astype(self.dtype))
        pos = self.param(
            "pos_embed",
            nn.initializers.zeros,
            (1, self.pretrain_grid, self.pretrain_grid, self.dim),
            jnp.float32,
        )
        # keyed off the actual table shape (not the attribute) so a
        # checkpoint trained at a different grid still loads and resizes
        if pos.shape[1:3] != (gh, gw):
            pos = jax.image.resize(
                pos, (1, gh, gw, self.dim), method="bilinear"
            )
        x = x + pos.astype(self.dtype)
        for i in range(self.depth):
            ws = 0 if i in self.global_attn_indexes else self.window_size
            x = SAMBlock(
                self.dim,
                self.num_heads,
                self.mlp_ratio,
                ws,
                # checkpoints store windowed tables at the window extent
                # and global tables at the pretraining grid extent
                table_size=ws if ws > 0 else self.pretrain_grid,
                dtype=self.dtype,
                name=f"block{i}",
            )(x)
        x = nn.Conv(
            self.neck_dim, (1, 1), use_bias=False, dtype=self.dtype,
            name="neck_conv1",
        )(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="neck_norm1")(x).astype(
            self.dtype
        )
        x = nn.Conv(
            self.neck_dim, (3, 3), padding="SAME", use_bias=False,
            dtype=self.dtype, name="neck_conv2",
        )(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="neck_norm2")(x)
        return x


class CpSAM(nn.Module):
    """cpsam: SAM ViT encoder + transposed-conv readout to cellpose's
    (B, H, W, 3) f32 logits (flow_y, flow_x, cellprob) — same output
    contract as ``CellposeNet``/``CellposeSAM``, so the loss, train
    step, flow postprocessing, and jax_params serving path all work
    unchanged. Input is 3-channel (cpsam convention); the finetuning
    app pads its 2-channel [cyto, nucleus] batches with a zero channel.

    Defaults are ViT-L @ patch 8 — the cpsam checkpoint shape. For
    tests and CI, shrink ``dim/depth/num_heads`` (the name map scales
    with ``depth``)."""

    patch_size: int = 8
    dim: int = 1024
    depth: int = 24
    num_heads: int = 16
    mlp_ratio: float = 4.0
    window_size: int = 14
    global_attn_indexes: Sequence[int] = (5, 11, 17, 23)
    neck_dim: int = 256
    pretrain_grid: int = 32
    in_channels: int = 3
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        feats = SAMEncoder(
            patch_size=self.patch_size,
            dim=self.dim,
            depth=self.depth,
            num_heads=self.num_heads,
            mlp_ratio=self.mlp_ratio,
            window_size=self.window_size,
            global_attn_indexes=self.global_attn_indexes,
            neck_dim=self.neck_dim,
            pretrain_grid=self.pretrain_grid,
            dtype=self.dtype,
            name="encoder",
        )(x)
        out = nn.ConvTranspose(
            3,
            (self.patch_size, self.patch_size),
            strides=(self.patch_size, self.patch_size),
            dtype=jnp.float32,
            name="out",
        )(feats.astype(jnp.float32))
        return out

    @property
    def divisor(self) -> int:
        return self.patch_size
