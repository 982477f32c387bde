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
(q gets the two bias rows appended, k the one-hot of its own row and
column, so ``q' k'^T`` is the biased score; for cpsam 64 + 32 + 32 =
128, the lane width). ``SAMAttention`` hands ``ops.attention
.packed_attention`` the qkv projection's output as it is and the two
tables; on a TPU a global block's 64-wide heads on a 32 x 32 grid go
through the packed Pallas kernel, which reads head pairs out of that
array, forms the bias rows, q' and k' in VMEM and writes what ``proj``
reads: no attention operand is relaid in HBM (those relayouts were 42 %
of the served step's device time, the score-size bias before them
60-65 %; PERF.md section 6, PRs 31 and 27). Windows, other grids and
other backends unpack to ``(B, heads, N, .)`` operands inside
``ops.attention``: the fused kernel on a TPU, its plain XLA reference
elsewhere. A block's MLP half, ``x + lin2(gelu(lin1(norm2(x))))``, is
one call of ``ops.mlp.mlp`` with the ``mlp_lin1`` / ``mlp_lin2``
parameters: exact GELU in its erf form (what torch's ``nn.GELU()``
states), evaluated once an element on the f32 accumulator; on a TPU,
where the shapes have tiles (dim and hidden whole lane widths, the rows
a multiple of a row tile: every cpsam width), one Pallas kernel that
keeps the ``(rows, hidden)`` activation in VMEM, the plain XLA
reference elsewhere. Written as two ``nn.Dense`` around
``nn.gelu(approximate=False)``, XLA put jax's erfc form of the same
function, a 72-operation chain with an ``exponential``, into the
operand prologue of the second matmul: a fifth of the served step
(PERF.md section 6, PR 36). Window partition is a reshape (no data
movement beyond layout); a windowed block un-partitions before its MLP,
which therefore sees ``(B, H, W, dim)`` like a global block's. Shapes
are static per (H, W) bucket as everywhere else in the framework.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from bioengine_tpu.ops.attention import packed_attention
from bioengine_tpu.ops.mlp import mlp


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
    ``q[n]·rel_h[h - k_h + H - 1] + q[n]·rel_w[w - k_w + W - 1]`` (SAM's
    ``add_decomposed_rel_pos``, unscaled q). It is not added to a score
    tensor: ``ops.attention.packed_attention`` folds it into the QK^T
    contraction, and which program runs that is read off the shapes
    there, never set here."""

    dim: int
    num_heads: int
    table_size: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, H, W, _ = x.shape
        nh, hd = self.num_heads, self.dim // self.num_heads
        qkv = nn.Dense(3 * self.dim, dtype=self.dtype, name="qkv")(x)

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
        out = packed_attention(
            qkv.reshape(B, H * W, 3 * self.dim),
            _resize_rel_pos(rel_h, 2 * H - 1).astype(self.dtype),
            _resize_rel_pos(rel_w, 2 * W - 1).astype(self.dtype),
            grid=(H, W),
            heads=nh,
        )  # (B, N, dim)
        out = out.reshape(B, H, W, self.dim)
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


class _DenseParams(nn.Module):
    """An ``nn.Dense``'s parameters without its product: the same
    names, shapes, dtype and initialisers under the same scope, so the
    same tree and, for a seed, the same values. ``ops.mlp.mlp`` does the
    multiplying."""

    features: int

    @nn.compact
    def __call__(self, fan_in: int):
        kernel = self.param(
            "kernel", nn.linear.default_kernel_init,
            (fan_in, self.features), jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,),
            jnp.float32,
        )
        return kernel, bias


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
        hidden = int(self.dim * self.mlp_ratio)
        w1, b1 = _DenseParams(hidden, name="mlp_lin1")(self.dim)
        w2, b2 = _DenseParams(self.dim, name="mlp_lin2")(hidden)
        # x + lin2(gelu(lin1(y))), exact GELU: one call, and which
        # program runs it is read off the shapes there
        return mlp(y, w1, b1, w2, b2, x)


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
