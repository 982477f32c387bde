"""Operations of the toy decoder, from shapes: matrix products only, 2
flops a multiply-add, per generated token (the attention over the cached
positions is left out: a few percent at these lengths)."""

from __future__ import annotations


def flops_per_unit(config: dict) -> float:
    """Per token generated: every weight matrix once, and the tied head."""
    d, ff = int(config["d_model"]), int(config["d_ff"])
    per_layer = 4 * d * d + 2 * d * ff
    return 2.0 * (int(config["n_layers"]) * per_layer + int(config["vocab"]) * d)


def program_flops(key: tuple, config: dict):
    """A key of this path is a kind of request, which runs a prefill and
    a step a token: there is no count for one compiled program."""
    return None


def program_min_bytes(key: tuple, config: dict):
    return None
