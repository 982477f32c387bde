"""The image path: ``infer`` on the deployed ``apps/model-runner``.

Every request is ``infer(model_id, inputs, default_blocksize_parameter,
sample_id)`` over a client connection, one reply a request. The package
is a ``jax_params`` model package (``rdf.yaml``, ``weights.npz`` and the
manifest that selects the streamed-weights load) in a collection the
entry deployment takes as its package cache. Work is counted in input
pixels. The check cuts and blends tiles as ``default_blocksize_parameter``
and ``max_tile`` promise and compares every reply of the sample with the
plain float32 reference.

What a path module gives the harness (``benchmarks/README.md``, "Adding
a served path"):

  OPERATOR_ENV        the operator variables a configuration of this
                      path may set, each named in ``docs/OPERATIONS.md``
  ENGINES             the deployment whose replicas hold the engines
  THROUGHPUT          (end-to-end metric, units of work to one of its
                      unit) computed from the work served, or ``None``
  make_package        what the app serves, from configuration and seed
  deployment_kwargs   keyword arguments of ``deploy_app`` for it
  programs            program key -> the lone request that runs it
  lone_payload        that request's payload (warm-up, labelling traces)
  payload, describe   a plan's request: what is sent, and the record's
                      keys of the path's own (``work`` among them)
  perform             one request on a connection: ``end`` (when the
                      reply was whole, on ``time.perf_counter``), ``ok``,
                      ``server_ms``, ``output`` (kept for the check); a
                      streamed path adds the stamps of its items
  compare             one reading per key of the configuration's limits
  control_sample      the requests the precision control compares
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import time
from pathlib import Path

import numpy as np

from benchmarks.generators.closed_loop import Request, kinds
from benchmarks.harness import Cell, not_comparable
from benchmarks.references import _common
from benchmarks.references._common import model_kwargs

# docs/OPERATIONS.md "Sizing /dev/shm for the object store"
OPERATOR_ENV = ("BIOENGINE_RPC_STORE_MB",)
ENGINES = "runtime_deployment"
THROUGHPUT = ("throughput_mpx_s", 1e6)
BATCH_LADDER = (1, 2, 4, 8, 16, 32, 64)


def reference_module(config: dict):
    return importlib.import_module(f"benchmarks.references.{config['reference']}")


# ---- package and deployment ----------------------------------------------------


def make_package(config: dict, seed: int, collection: Path) -> dict:
    """Writes ``collection/<model_id>/`` and points the program at the
    collection; returns what ``deployment_kwargs`` and ``perform`` need."""
    import yaml

    model_id = f"bench-{config['name']}"
    package = collection / model_id
    package.mkdir(parents=True)
    shapes = reference_module(config).param_shapes(
        model_kwargs(config), int(config["in_channels"])
    )
    weights = _common.make_weights(shapes, seed)
    flat = {k: np.asarray(v) for k, v in weights.items()}
    del weights
    with open(package / "weights.npz", "wb") as f:
        np.savez(f, **flat)
        # 1.2 GB of dirty pages are written back during set-up, not half
        # a minute later in the middle of the window
        f.flush()
        os.fsync(f.fileno())
    # the manifest beside the npz selects the streamed-weights path
    (package / "weights.npz.manifest.json").write_text(
        json.dumps(
            {k: {"shape": list(v.shape), "dtype": str(v.dtype)} for k, v in flat.items()},
            sort_keys=True,
        )
    )
    kwargs = {k: config[k] for k in config["model_kwargs"]}
    (package / "rdf.yaml").write_text(
        yaml.safe_dump(
            {
                "type": "model",
                "name": model_id,
                "description": f"benchmark package of {config['name']}, random weights",
                "inputs": [{"name": "input0", "axes": config["axes"]}],
                "outputs": [{"name": "output0", "axes": config["axes"]}],
                "weights": {
                    "jax_params": {
                        "source": "weights.npz",
                        "architecture": {
                            "name": config["architecture"],
                            "kwargs": kwargs,
                        },
                    }
                },
            }
        )
    )
    # the default source is https://hypha.aicell.io, unreachable here
    os.environ["BIOENGINE_LOCAL_MODEL_PATH"] = str(collection)
    return {"model_id": model_id, "collection": collection}


def deployment_kwargs(package: dict) -> dict:
    """The entry deployment's package cache IS the collection: the
    package counts as fetched, as it is on every request but a model's
    first."""
    return {"entry_deployment": {"cache_dir": str(package["collection"])}}


# ---- programs: every one the mix can form ---------------------------------------


def tiling_of(cell: Cell) -> tuple[int, int, int]:
    """(tile, max_tile, overlap) a request of this cell is served with."""
    engine = cell.config["engine"]
    block = cell.traffic.get("blocksize")
    if block:
        return int(block), int(block), int(engine["tile_overlap"])
    return int(engine["tile"]), int(engine["max_tile"]), int(engine["tile_overlap"])


def batch_bucket(n: int) -> int:
    return next(b for b in BATCH_LADDER if b >= n)


def programs(cell: Cell) -> dict[tuple[int, ...], tuple[int, int]]:
    """Program input shape -> the (items, size) of a lone request that
    runs it. Tiled requests run chunks of ``tile_batch`` tiles, each
    padded up the batch ladder; the others are co-batched by the runtime
    (at most ``max_ongoing_requests`` of them), the sum padded likewise."""
    tile, max_tile, overlap = tiling_of(cell)
    chunk = int(cell.config["engine"]["tile_batch"])
    channels = int(cell.config["in_channels"])
    slots = int(
        cell.config["deployment"]["shipped"]["runtime_deployment"][
            "max_ongoing_requests"
        ]
    )
    out: dict[tuple[int, ...], tuple[int, int]] = {}
    direct_items: set[int] = set()
    for items, size in kinds(cell.traffic):
        if size > max_tile:
            n = _common.n_tiles(size, size, tile, overlap)
            for left in {min(chunk, n - i) for i in range(0, n, chunk)}:
                out.setdefault(
                    (batch_bucket(left), tile, tile, channels), (items, size)
                )
        else:
            direct_items.add(items)
    if direct_items:
        (size,) = {s for _, s in kinds(cell.traffic) if s <= max_tile}
        for bucket in BATCH_LADDER:
            if bucket <= batch_bucket(slots * max(direct_items)):
                out[(bucket, size, size, channels)] = (bucket, size)
    return out


def lone_payload(cell: Cell, key: tuple[int, ...], request: tuple[int, int], rng):
    items, size = request
    return rng.standard_normal((items, size, size, key[-1]), np.float32)


# ---- one request ----------------------------------------------------------------


def payload(plan, request: Request) -> np.ndarray:
    return plan.pool[request.kind][request.image]


def describe(plan, request: Request) -> dict:
    pixels = plan.pixels(request)
    return {"image": request.image, "pixels": pixels, "work": pixels}


async def perform(conn, service_id: str, package: dict, plan, array: np.ndarray,
                  sample_id: str) -> dict:
    kwargs = dict(model_id=package["model_id"], inputs=array, sample_id=sample_id)
    if plan.blocksize:
        kwargs["default_blocksize_parameter"] = int(plan.blocksize)
    reply = await conn.call(service_id, "infer", **kwargs)
    return {
        "end": time.perf_counter(),
        "ok": reply["_meta"]["backend"] == "xla",
        "server_ms": float(reply["_meta"]["duration_ms"]),
        # a copy: the decoded array is a view that pins its object in
        # the RPC plane's shared-memory store
        "output": np.array(reply["output0"], np.float32),
    }


# ---- the check ------------------------------------------------------------------


def control_sample(cell: Cell, plan) -> list[dict]:
    """One request of every kind in the mix, nothing served: the control
    computes its own answer in the program's place."""
    return [
        {"kind": kind, "request": Request(*kind, image=0), "output": None}
        for kind in kinds(cell.traffic)
    ]


def compare(cell: Cell, seed: int, sample: list[dict], pool: dict,
            precision: str = "f32") -> dict[str, float]:
    """The numbers ``correct`` rests on, worst over the sample: the
    relative l2 distance between reply and reference, and the largest
    absolute gap over the reference's largest value. ``precision`` below
    f32 exists for the control, which compares the reference in a lower
    precision in the program's place."""
    import jax

    ref = reference_module(cell.config)
    kwargs = model_kwargs(cell.config)
    weights = _common.make_weights(
        ref.param_shapes(kwargs, int(cell.config["in_channels"])), seed
    )
    tile, max_tile, overlap = tiling_of(cell)

    def forward_in(prec: str):
        fn = jax.jit(functools.partial(ref.forward, kwargs=kwargs, precision=prec))
        return lambda tiles: fn(weights, tiles)

    exact = forward_in("f32")
    lower = forward_in(precision) if precision != "f32" else None
    worst = {"rel_l2": 0.0, "max_err": 0.0}
    cache: dict[tuple, np.ndarray] = {}
    for entry in sample:
        key = (entry["kind"], entry["request"].image)
        image = pool[entry["kind"]][entry["request"].image]
        if key not in cache:
            cache[key] = _common.predict(exact, image, tile, max_tile, overlap)
        want = cache[key]
        if lower is not None:
            got = _common.predict(lower, image, tile, max_tile, overlap)
        else:
            got = np.asarray(entry["output"], np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            return not_comparable(cell.config["limits"])
        diff = got.astype(np.float64) - want
        worst["rel_l2"] = max(
            worst["rel_l2"],
            float(np.linalg.norm(diff) / max(np.linalg.norm(want), 1e-30)),
        )
        worst["max_err"] = max(
            worst["max_err"],
            float(np.max(np.abs(diff)) / max(np.max(np.abs(want)), 1e-30)),
        )
    return worst
