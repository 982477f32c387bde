"""The toy token path: ``apps/generate`` as shipped, through a streamed
call. Scaffolding for the harness's seam, not support for a model.

Every request is ``call_stream(<app service>, <deployment.method>,
prompt=..., max_new_tokens=...)`` on a client connection, and every item
of the reply is stamped on ``time.perf_counter`` as it arrives
(``first_item``, ``items``). The app's service publishes its unary
``generate`` alone (``apps/proxy.py`` registers schema methods, and
``generate_stream`` is none), which a streamed call receives as a stream
of one item, the whole reply; a service that publishes the generator
itself sends one item a token. This module reads both. Work is counted
in tokens generated. The package is nothing on disk: the app draws its
weights from ``BIOENGINE_GENERATE_SEED``.

The check: the reference's logits over each sampled prompt with its
served tokens, in one plain forward pass with no cache; ``logit_gap`` is
the widest gap by which a served token's logit lies below the
reference's best at its position, over the spread (standard deviation)
of that position's logits. Greedy tokens served right read 0 to
rounding.
"""

from __future__ import annotations

import importlib
import os
import time
from pathlib import Path

import numpy as np

from benchmarks.harness import Cell, not_comparable

# docs/OPERATIONS.md, the decode engine's table: the pool a cache-backed
# path has to size
OPERATOR_ENV = ("BIOENGINE_DECODE_KV_BLOCKS",)
ENGINES = "generate_deployment"
THROUGHPUT = None


def make_package(config: dict, seed: int, out_dir: Path) -> dict:
    seed = abs(int(seed))
    os.environ["BIOENGINE_GENERATE_SEED"] = str(seed)
    return {"seed": seed, "method": config["deployment"]["method"]}


def deployment_kwargs(package: dict) -> dict:
    return {}


def generator_of(cell: Cell):
    return importlib.import_module(f"benchmarks.generators.{cell.traffic['generator']}")


def programs(cell: Cell) -> dict[tuple, tuple[int, int]]:
    """One key a kind of request: a lone request runs a prefill and then
    a step for each token, so no key is one compiled program."""
    return {("generate", *kind): kind for kind in generator_of(cell).kinds(cell.traffic)}


def lone_payload(cell: Cell, key: tuple, request: tuple[int, int], rng) -> dict:
    chars, tokens = request
    return {"prompt": generator_of(cell).prompt(rng, chars), "max_new_tokens": tokens}


def payload(plan, request) -> dict:
    return {
        "prompt": plan.pool[request.kind][request.prompt],
        "max_new_tokens": request.max_new_tokens,
    }


def describe(plan, request) -> dict:
    return {"prompt": request.prompt, "work": request.max_new_tokens}


async def perform(conn, service_id: str, package: dict, plan, asked: dict,
                  sample_id: str) -> dict:
    tokens: list[int] = []
    stamps: list[float] = []
    async for item in conn.call_stream(service_id, package["method"], **asked):
        stamps.append(time.perf_counter())
        # the whole reply in one item, or one token an item
        tokens.extend(item["tokens"] if "tokens" in item else [item["token"]])
    return {
        "end": stamps[-1],
        "ok": len(tokens) == asked["max_new_tokens"],
        "first_item": stamps[0],
        "items": stamps,
        "output": [int(t) for t in tokens],
    }


def compare(cell: Cell, seed: int, sample: list[dict], pool: dict) -> dict[str, float]:
    """No control: the toy decoder states no precision and is in no cell."""
    reference = importlib.import_module(
        f"benchmarks.references.{cell.config['reference']}"
    )
    weights = reference.make_weights(cell.config, seed)
    worst = 0.0
    for entry in sample:
        request, served = entry["request"], entry["output"]
        if len(served) != request.max_new_tokens:
            return not_comparable(cell.config["limits"])
        prompt = [ord(c) % 256 for c in pool[entry["kind"]][request.prompt]]
        logits = reference.forward(weights, cell.config, prompt + served)
        for i, token in enumerate(served):
            # the logits at position p choose the token at p + 1
            at = logits[len(prompt) - 1 + i]
            worst = max(worst, float((at.max() - at[token]) / max(at.std(), 1e-30)))
    return {"logit_gap": worst}
