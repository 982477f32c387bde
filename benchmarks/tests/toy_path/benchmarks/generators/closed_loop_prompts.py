"""Closed loop of prompts: N clients, each awaiting its whole reply
before it sends the next prompt. The traffic file gives the number of
clients and a deck: ``count`` requests of ``prompt_chars`` characters
that ask for ``max_new_tokens`` greedy tokens. As in ``closed_loop``,
every client walks the whole deck in an order of its own drawn from the
file's ``order``; the seed makes the prompts (a small pool for each kind
of request) and says which one a request sends. ``drive`` is
``closed_loop``'s."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.generators.closed_loop import drive  # noqa: F401  (the contract's second half)


@dataclasses.dataclass(frozen=True)
class Request:
    prompt_chars: int
    max_new_tokens: int
    prompt: int      # index into the pool of this kind

    @property
    def kind(self) -> tuple[int, int]:
        return (self.prompt_chars, self.max_new_tokens)


@dataclasses.dataclass
class Plan:
    clients: list[list[Request]]
    pool: dict[tuple[int, int], list[str]]
    lead_in: float


def kinds(traffic: dict) -> list[tuple[int, int]]:
    return sorted({(e["prompt_chars"], e["max_new_tokens"]) for e in traffic["deck"]})


def prompt(rng, chars: int) -> str:
    return "".join(chr(c) for c in rng.integers(97, 123, chars))


def plan(traffic: dict, config: dict, seed: int) -> Plan:
    rng = np.random.default_rng(abs(int(seed)))
    pool = {
        kind: [prompt(rng, kind[0]) for _ in range(int(traffic["pool"]))]
        for kind in kinds(traffic)
    }
    deck = [
        (e["prompt_chars"], e["max_new_tokens"])
        for e in traffic["deck"] for _ in range(e["count"])
    ]
    dealer = np.random.default_rng(int(traffic["order"]))
    clients = [
        [
            Request(*deck[i], prompt=int(rng.integers(traffic["pool"])))
            for i in dealer.permutation(len(deck))
        ]
        for _ in range(int(traffic["clients"]))
    ]
    return Plan(clients, pool, float(traffic["lead_in_s"]))
