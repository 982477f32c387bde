"""Closed loop: N clients, each awaiting its reply before it sends the
next request. The traffic file gives the number of clients, the
``default_blocksize_parameter`` every request carries (or null) and a
deck: ``count`` requests of ``items`` images of ``size`` x ``size``.
Every client walks the whole deck again and again, in an order of its
own drawn from the file's ``order``, not from the seed: where the large
requests fall decides the tail, and a seed that dealt them anew moved
the 95th percentile by 6 % between seeds where two runs of one seed
agreed within 0.8 % (chip runs of PR 25). The seed makes the images (a
small pool for each kind of request) and says which one a request sends.

The window opens on clients in their stride: they loop for the file's
``lead_in_s`` first, which counts as set-up. Clients that all send their
first request at the same instant start in lock-step, and how that
resolved (in the window's first seconds, a stall of the server's event
loop of 0.4 to 1.5 s in one run of five, none in the others) decided a
run's throughput within 1.5 % and the mode of its 95th percentile (chip
runs of PR 25).
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Awaitable, Callable

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    items: int
    size: int
    image: int      # index into the pool of this (items, size)

    @property
    def kind(self) -> tuple[int, int]:
        return (self.items, self.size)


@dataclasses.dataclass
class Plan:
    clients: list[list[Request]]                 # one cyclic deck each
    pool: dict[tuple[int, int], list[np.ndarray]]
    blocksize: int | None
    lead_in: float                               # seconds before the window

    def pixels(self, request: Request) -> int:
        return request.items * request.size * request.size


def kinds(traffic: dict) -> list[tuple[int, int]]:
    return sorted({(e["items"], e["size"]) for e in traffic["deck"]})


def plan(traffic: dict, config: dict, seed: int) -> Plan:
    channels = int(config["in_channels"])
    rng = np.random.default_rng(abs(int(seed)))
    pool = {
        kind: [
            rng.standard_normal((kind[0], kind[1], kind[1], channels), np.float32)
            for _ in range(int(traffic["pool"]))
        ]
        for kind in kinds(traffic)
    }
    deck = [
        (e["items"], e["size"]) for e in traffic["deck"] for _ in range(e["count"])
    ]
    dealer = np.random.default_rng(int(traffic["order"]))
    clients = []
    for _ in range(int(traffic["clients"])):
        order = dealer.permutation(len(deck))
        clients.append(
            [
                Request(*deck[i], image=int(rng.integers(traffic["pool"])))
                for i in order
            ]
        )
    return Plan(clients, pool, traffic.get("blocksize"), float(traffic["lead_in_s"]))


async def drive(
    plan_: Plan,
    send: Callable[[int, int, Request], Awaitable[dict]],
    seconds: float,
    on_open: Callable[[], Awaitable[None]],
) -> tuple[float, float]:
    """Every client loops over its deck through the lead-in and then
    until ``seconds`` of window have passed; the requests in flight at
    the close are awaited. ``send(client, ordinal, request)`` performs
    and records one request; ``on_open()`` runs when the window opens.
    Returns the window's (start, end) on ``time.perf_counter``."""
    start = time.perf_counter() + plan_.lead_in
    end = start + seconds

    async def client(c: int) -> None:
        deck = plan_.clients[c]
        n = 0
        while time.perf_counter() < end:
            await send(c, n, deck[n % len(deck)])
            n += 1

    async def opener() -> None:
        await asyncio.sleep(max(0.0, start - time.perf_counter()))
        await on_open()

    await asyncio.gather(opener(), *(client(c) for c in range(len(plan_.clients))))
    return start, end
