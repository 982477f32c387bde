"""Plain reference of the toy char-level decoder: numpy, float64, one
full forward pass over a whole sequence, no cache. Pre-norm blocks
(LayerNorm eps 1e-5, multi-head causal attention, tanh-GELU MLP),
learned positions, output head tied to the token embedding. It imports
nothing of the program; the weights are drawn again from the seed in the
order the app draws them (``numpy.random.default_rng(seed)``: token and
position tables at 0.02, then a layer at a time wq, wk, wv, wo, w1, w2
at 1/sqrt(fan_in); norm scales 1, every bias 0)."""

from __future__ import annotations

import numpy as np


def make_weights(config: dict, seed: int) -> dict:
    rng = np.random.default_rng(abs(int(seed)))
    d, ff = int(config["d_model"]), int(config["d_ff"])

    def w(*shape, scale):
        return rng.normal(0.0, scale, size=shape).astype(np.float32).astype(np.float64)

    weights = {
        "tok_emb": w(int(config["vocab"]), d, scale=0.02),
        "pos_emb": w(int(config["max_len"]), d, scale=0.02),
        "layers": [],
    }
    for _ in range(int(config["n_layers"])):
        weights["layers"].append({
            "wq": w(d, d, scale=d**-0.5), "wk": w(d, d, scale=d**-0.5),
            "wv": w(d, d, scale=d**-0.5), "wo": w(d, d, scale=d**-0.5),
            "w1": w(d, ff, scale=d**-0.5), "w2": w(ff, d, scale=ff**-0.5),
        })
    return weights


def norm(x):
    mean = x.mean(-1, keepdims=True)
    return (x - mean) / np.sqrt(((x - mean) ** 2).mean(-1, keepdims=True) + 1e-5)


def gelu(x):
    return 0.5 * x * (1.0 + np.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x**3)))


def forward(weights: dict, config: dict, tokens: list[int]) -> np.ndarray:
    """Logits ``[len(tokens), vocab]``: row p is what follows tokens[:p + 1]."""
    n, heads = len(tokens), int(config["n_heads"])
    x = weights["tok_emb"][tokens] + weights["pos_emb"][:n]
    hd = x.shape[-1] // heads
    future = np.triu(np.ones((n, n), bool), 1)
    for layer in weights["layers"]:
        h = norm(x)
        q, k, v = ((h @ layer[m]).reshape(n, heads, hd) for m in ("wq", "wk", "wv"))
        scores = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        scores = np.where(future[None], -np.inf, scores)
        attn = np.exp(scores - scores.max(-1, keepdims=True))
        attn /= attn.sum(-1, keepdims=True)
        x = x + np.einsum("hqk,khd->qhd", attn, v).reshape(n, -1) @ layer["wo"]
        x = x + gelu(norm(x) @ layer["w1"]) @ layer["w2"]
    return norm(x) @ weights["tok_emb"].T
