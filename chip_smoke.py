#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

Drives worker -> model-runner -> InferenceEngine once, in ONE process,
through the entry points a user calls, at the published width of the
repo's U-Net (``UNet2D(features=(32, 64, 128, 256))`` on 256x256 tiles,
random weights from ``jax.random.key(0)``), and checks what comes out by
the repo's own means. Phases, each printing one line with its wall
seconds and the ``device_kind`` it ran on:

  gate     jax.devices() must be a TPU (there is no CPU mode)
  start    the object ``python -m bioengine_tpu.worker --mode
           single-machine`` builds, port 0, workspace under the output dir
  package  a local collection holding one ``jax_params`` package
  deploy   ``deploy_app(local_path="apps/model-runner")`` over a real
           client connection, waited to HEALTHY
  serve    16 concurrent 256x256 ``infer`` calls (warm-up), the same 16
           again (checked against ``jax.jit(model.apply)``), one 1200x1200
           image through the tiled pipeline, one call over the HTTP bridge
  trace    start_profiling -> 4 requests -> stop_profiling; the xplane
           must hold a TPU device plane with events
  kernel   the Pallas attention kernel compiled by Mosaic at the
           ViT-B/14@448 and cpsam shapes (plain depth, the folded
           128/64 depth, and the packed call of the served program
           over the qkv projection's layout) and the MLP kernel at the
           served program's shape, forward and gradient
  stop     worker.stop(); no thread may outlive it

Nothing is caught and continued: the first failed check raises, the
exit status is non-zero and no result line is printed. On success the
LAST line of stdout is one JSON object naming the device as JAX reports
it. Everything the run writes lands under ``chiprun_out/chip_smoke/``
(workspace, model cache, generated package, trace), except the XLA
compile cache, which lives where ``utils/compile_cache.py`` puts it so
that a second run finds it again.

The phase lines are information, not metrics.

``--chips N`` (builder's four-chip check) stages a copy of the app whose
runtime deployment leases N chips and additionally requires a ``dp=N``
mesh over N distinct TPU ids with memory in use on each;
``--parity-with FILE`` compares the checked outputs with those a
previous run saved (``<out>/outputs-chips<N>.npz``).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import functools
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Any, Optional

REPO = Path(__file__).resolve().parent
DEFAULT_OUT = REPO / "chiprun_out" / "chip_smoke"
MODEL_ID = "smoke-unet2d"
# |a - b| <= BF16_TOL * max(1, max|b|): four bf16 epsilons (2**-8) of
# the reference's range — what separates two XLA programs computing the
# same bf16 network with different fusions, not a loose "looks similar"
BF16_TOL = 2.0**-6

# (B, H, N, q/k depth, v depth, scale; None = depth**-0.5): ViT-B/14 heads
# at 448x448 — the first shape the embedder turns the kernel on for —
# one cpsam tile's heads at plain depth, and what the served cpsam
# program runs since PR 27: 16 tiles' heads with the relative-position
# bias folded into the contraction (64 + 32 + 32 lanes, scale 1)
KERNEL_SHAPES = (
    (2, 12, 1025, 64, 64, None),
    (1, 16, 1024, 64, 64, None),
    (16, 16, 1024, 128, 64, 1.0),
)
# (B, heads, (H, W)) at 64-wide heads: what the served cpsam program runs
# since PR 31, the packed call over the qkv projection's own layout
PACKED_SHAPES = ((16, 16, (32, 32)),)
# (shape of the tokens, hidden): the MLP half of a block of the served
# cpsam program since PR 36, one kernel with the hidden activation in VMEM
MLP_SHAPES = (((16, 32, 32, 1024), 4096),)


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """What the smoke runs. The defaults are the chip run; the tier-1
    rehearsal passes the CPU platform and a toy width."""

    platform: str = "tpu"
    features: tuple[int, ...] = (32, 64, 128, 256)
    tile: int = 256            # request size of the batched rounds
    concurrency: int = 16      # requests per round
    big: int = 1200            # > EngineConfig.max_tile: runs tiled
    chips: int = 1
    out_dir: Path = DEFAULT_OUT
    parity_with: Optional[Path] = None
    kernel_shapes: tuple = KERNEL_SHAPES
    packed_shapes: tuple = PACKED_SHAPES
    mlp_shapes: tuple = MLP_SHAPES


_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Report:
    """One ``[chip_smoke]`` line per phase; holds what later phases and
    the final JSON need. Every line carries two counts: ``jit_compiles``,
    how often jit had to obtain an executable in the phase (jax records
    one backend-compile event each time, persistent-cache hit or not),
    and ``xla_cache_writes``, how many persistent-cache entries the
    phase added — each a real XLA compile, since a hit writes none."""

    def __init__(self) -> None:
        import jax.monitoring

        self.device: dict = {}
        self.jit_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            self.jit_compiles += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_event)

    @contextlib.contextmanager
    def phase(self, name: str):
        from bioengine_tpu.utils import compile_cache

        info: dict[str, Any] = {}
        writes = len(compile_cache.list_entries())
        compiles = self.jit_compiles
        t0 = time.perf_counter()
        yield info
        info["jit_compiles"] = self.jit_compiles - compiles
        info["xla_cache_writes"] = len(compile_cache.list_entries()) - writes
        fields = " ".join(f"{k}={json.dumps(v)}" for k, v in info.items())
        print(
            f"[chip_smoke] {name}: ok {time.perf_counter() - t0:.1f}s "
            f"device_kind={json.dumps(self.device.get('kind'))} {fields}",
            flush=True,
        )


def check(cond: bool, message: str) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {message}")


# ---- gate -------------------------------------------------------------------


def device_gate(cfg: SmokeConfig, report: Report) -> None:
    import importlib.metadata

    import jax
    import jaxlib

    from bioengine_tpu.utils.compile_cache import (
        enable_persistent_compilation_cache,
    )

    # the same call the worker makes at start (idempotent); before the
    # phase so that its cache-write count starts from the real listing
    cache_dir = enable_persistent_compilation_cache()
    with report.phase("gate") as info:
        devices = jax.devices()
        d0 = devices[0]
        check(
            d0.platform == cfg.platform,
            f"jax.devices()[0].platform is '{d0.platform}', need "
            f"'{cfg.platform}'",
        )
        check(
            len(devices) >= cfg.chips,
            f"--chips {cfg.chips} but JAX sees {len(devices)} device(s)",
        )
        report.device = {
            "platform": d0.platform,
            "kind": d0.device_kind,
            "count": len(devices),
        }
        info.update(
            platform=d0.platform,
            count=len(devices),
            jax=jax.__version__,
            jaxlib=jaxlib.__version__,
            libtpu=importlib.metadata.version("libtpu"),
            compile_cache=cache_dir,
        )


# ---- start ------------------------------------------------------------------


async def start_worker(cfg: SmokeConfig, report: Report):
    """Returns (worker, client connection, worker service id)."""
    from bioengine_tpu.native import store as native_store
    from bioengine_tpu.rpc.client import connect_to_server
    from bioengine_tpu.worker.__main__ import (
        create_parser,
        worker_kwargs_from_args,
    )
    from bioengine_tpu.worker.worker import BioEngineWorker

    with report.phase("start") as info:
        workspace = cfg.out_dir / "workspace"
        args = create_parser().parse_args(
            [
                "--mode", "single-machine",
                "--host", "127.0.0.1",
                "--port", "0",
                "--workspace-dir", str(workspace),
            ]
        )
        worker = BioEngineWorker(**worker_kwargs_from_args(args))
        endpoints = await worker.start()
        topology = worker.cluster.status["topology"]
        check(
            topology["platform"] == cfg.platform,
            f"cluster topology is {topology['platform']}, "
            f"need {cfg.platform}",
        )
        # the RPC shm fast path rests on the native library, which must
        # build from committed files on a clean machine
        check(
            native_store.native_available(),
            "native object store did not build/load (see the warning above)",
        )
        conn = await connect_to_server(
            {
                "server_url": endpoints["rpc_url"],
                "token": (workspace / "admin_token").read_text(),
            }
        )
        info.update(
            rpc_url=endpoints["rpc_url"],
            n_chips=topology["n_chips"],
            shm_fast_path=conn.describe()["shm"],
        )
    return worker, conn, endpoints["service_id"]


# ---- package ----------------------------------------------------------------


def make_package(cfg: SmokeConfig, report: Report):
    """Writes the collection; returns (model, params) for the reference."""
    import jax
    import jax.numpy as jnp
    import yaml

    from bioengine_tpu.models.unet import UNet2D
    from bioengine_tpu.runtime.convert import flatten_params, save_params_npz
    from bioengine_tpu.runtime.weight_stream import write_manifest

    with report.phase("package") as info:
        collection = cfg.out_dir / "collection"
        package = collection / MODEL_ID
        package.mkdir(parents=True)
        model = UNet2D(features=cfg.features, out_channels=1)
        # jitted: un-jitted, init compiles one program per primitive
        params = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, cfg.tile, cfg.tile, 1))
        )["params"]
        save_params_npz(str(package / "weights.npz"), params)
        # the manifest is what selects the default streamed-weights path
        flat = flatten_params(params)
        write_manifest(package / "weights.npz", flat)
        (package / "rdf.yaml").write_text(
            yaml.safe_dump(
                {
                    "type": "model",
                    "name": "Smoke UNet2D",
                    "description": "chip_smoke model, random weights",
                    "inputs": [{"name": "input0", "axes": "byxc"}],
                    "outputs": [{"name": "output0", "axes": "byxc"}],
                    "weights": {
                        "jax_params": {
                            "source": "weights.npz",
                            "architecture": {
                                "name": "unet2d",
                                "kwargs": {
                                    "features": list(cfg.features),
                                    "out_channels": 1,
                                },
                            },
                        }
                    },
                }
            )
        )
        # the default source is https://hypha.aicell.io, unreachable here
        os.environ["BIOENGINE_LOCAL_MODEL_PATH"] = str(collection)
        info.update(
            features=list(cfg.features),
            param_bytes=int(sum(v.nbytes for v in flat.values())),
        )
    return model, params


# ---- deploy -----------------------------------------------------------------


def stage_app(cfg: SmokeConfig) -> Path:
    """The bundled app as shipped, or — for ``--chips N`` — a staged
    copy whose runtime deployment leases N chips and nothing else."""
    import yaml

    app_dir = REPO / "apps" / "model-runner"
    if cfg.chips == 1:
        return app_dir
    staged = cfg.out_dir / f"model-runner-chips{cfg.chips}"
    shutil.copytree(app_dir, staged)
    manifest = yaml.safe_load((staged / "manifest.yaml").read_text())
    manifest["deployment_config"]["runtime_deployment"]["chips"] = cfg.chips
    (staged / "manifest.yaml").write_text(yaml.safe_dump(manifest))
    return staged


async def deploy(cfg: SmokeConfig, report: Report, conn, worker_sid: str):
    """Returns (app_id, app service id)."""
    with report.phase("deploy") as info:
        # deploy_startup_applications swallows deploy errors; this does not
        result = await conn.call(
            worker_sid,
            "deploy_app",
            local_path=str(stage_app(cfg)),
            deployment_kwargs={
                "entry_deployment": {
                    "cache_dir": str(cfg.out_dir / "model-cache")
                }
            },
        )
        app_id = result["app_id"]
        deadline = time.monotonic() + 300
        while True:
            status = await conn.call(worker_sid, "get_app_status", app_id)
            states = {
                name: [r["state"] for r in dep["replicas"]]
                for name, dep in status["deployments"].items()
            }
            if all(s == ["HEALTHY"] for s in states.values()):
                break
            check(time.monotonic() < deadline, f"not HEALTHY: {states}")
            await asyncio.sleep(0.2)
        info.update(app_id=app_id, replicas=states)
    return app_id, result["service_id"]


# ---- serve ------------------------------------------------------------------


async def engine_views(conn, worker_sid: str, app_id: str) -> list[dict]:
    """Each runtime replica's engine as ``mesh_info()`` reports it, plus
    the replica's lease and the engine's pipeline stats. Usually one;
    where chips are free the autoscaler may add a second replica while
    the first round waits on its compiles."""
    status = await conn.call(worker_sid, "get_app_status", app_id)
    views = []
    for replica in status["deployments"]["runtime_deployment"]["replicas"]:
        mesh = replica["mesh"]
        for key, engine in mesh["engines"].items():
            views.append(
                {
                    "lease": mesh["lease"],
                    "pipeline": replica["pipeline_stats"][key],
                    **engine,
                }
            )
    check(bool(views), "no runtime replica has loaded the model")
    return views


def program_keys(views: list[dict]) -> set[str]:
    return {k for v in views for k in v["programs"]["compile_seconds"]}


async def metric_value(conn, worker_sid: str, name: str) -> float:
    families = await conn.call(worker_sid, "get_metrics")
    return sum(s["value"] for s in families[name]["series"])


def assert_close(got, want, what: str) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{what}: shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite values")
    err = float(np.max(np.abs(got - want)))
    bound = BF16_TOL * max(1.0, float(np.max(np.abs(want))))
    check(err <= bound, f"{what}: max abs error {err:.4g} > {bound:.4g}")
    return err


async def serve(
    cfg: SmokeConfig, report: Report, conn, worker_sid, app_id, app_sid,
    model, params,
) -> None:
    import aiohttp
    import jax
    import numpy as np

    rng = np.random.default_rng(0)
    tiles = [
        rng.normal(size=(1, cfg.tile, cfg.tile, 1)).astype(np.float32)
        for _ in range(cfg.concurrency)
    ]

    async def one_round(tag: str) -> list[dict]:
        return await asyncio.gather(
            *(
                conn.call(
                    app_sid, "infer",
                    model_id=MODEL_ID, inputs=x, sample_id=f"{tag}-{i}",
                )
                for i, x in enumerate(tiles)
            )
        )

    def check_meta(reply: dict) -> None:
        check(
            reply["_meta"]["backend"] == "xla",
            f"_meta.backend is {reply['_meta']['backend']!r}, need 'xla'",
        )

    async def counters() -> dict:
        return {
            name: await metric_value(conn, worker_sid, name)
            for name in (
                "batcher_requests_total",
                "batcher_batches_total",
                "program_cache_misses_total",
            )
        }

    with report.phase("serve") as info:
        start = await counters()
        # warm-up: the batcher co-batches, so this compiles every batch
        # bucket it forms
        for reply in await one_round("warm"):
            check_meta(reply)
        warm_programs = program_keys(
            await engine_views(conn, worker_sid, app_id)
        )
        warm_counters = await counters()
        warm_compiles = report.jit_compiles

        replies = await one_round("checked")
        views = await engine_views(conn, worker_sid, app_id)
        checked_counters = await counters()
        # a batch bucket the warm-up round never formed may compile in
        # the checked round — printed with its shape, never silent — but
        # a shape the warm-up round compiled may not: every
        # program-cache miss must be a new key, and with no new key jit
        # must not have asked for a single executable (a silent retrace
        # inside a warmed program would)
        new_programs = program_keys(views) - warm_programs
        for key in sorted(new_programs):
            print(f"[chip_smoke] serve: checked round compiled {key}", flush=True)
        misses = (
            checked_counters["program_cache_misses_total"]
            - warm_counters["program_cache_misses_total"]
        )
        check(
            misses == len(new_programs),
            f"checked round: {misses} program-cache misses but "
            f"{len(new_programs)} new shapes — a warmed shape recompiled",
        )
        check(
            bool(new_programs) or report.jit_compiles == warm_compiles,
            f"checked round: {report.jit_compiles - warm_compiles} jit "
            "compile(s) with no new program-cache key",
        )

        # reference: plain jit of the same model on an engine's device
        engine_device = next(
            d for d in jax.devices() if d.id == views[0]["device_ids"][0]
        )
        apply = jax.jit(lambda p, x: model.apply({"params": p}, x))
        ref_params = jax.device_put(params, engine_device)
        outputs = []
        worst = 0.0
        for i, (x, reply) in enumerate(zip(tiles, replies)):
            check_meta(reply)
            want = np.asarray(apply(ref_params, jax.device_put(x, engine_device)))
            worst = max(
                worst, assert_close(reply["output0"], want, f"request {i}")
            )
            outputs.append(np.asarray(reply["output0"]))

        # each engine sits on its replica's leased device(s), and they
        # are the platform
        for view in views:
            chips = view["per_chip"]
            check(
                sorted(view["device_ids"]) == sorted(view["lease"]),
                f"engine on {view['device_ids']}, lease {view['lease']}",
            )
            check(
                all(c["platform"] == cfg.platform for c in chips.values()),
                f"engine devices not all {cfg.platform}: {chips}",
            )
            if cfg.chips > 1:
                check(
                    view["mesh"] == {"dp": cfg.chips},
                    f"mesh is {view['mesh']}, need dp={cfg.chips}",
                )
                check(
                    len(set(view["device_ids"])) == cfg.chips,
                    f"device ids not distinct: {view['device_ids']}",
                )
                check(
                    all(c.get("bytes_in_use") for c in chips.values()),
                    f"a leased chip holds no memory: {chips}",
                )

        # the batcher formed at least one batch > 1
        requests = (
            checked_counters["batcher_requests_total"]
            - start["batcher_requests_total"]
        )
        batches = (
            checked_counters["batcher_batches_total"]
            - start["batcher_batches_total"]
        )
        check(
            requests == 2 * cfg.concurrency and batches < requests,
            f"{batches} batches for {requests} batched requests",
        )

        # one image above max_tile: tiled, through the engine's stream
        big = rng.normal(size=(1, cfg.big, cfg.big, 1)).astype(np.float32)
        reply = await conn.call(
            app_sid, "infer", model_id=MODEL_ID, inputs=big, sample_id="big"
        )
        check_meta(reply)
        out = np.asarray(reply["output0"])
        check(out.shape == big.shape, f"tiled output shape {out.shape}")
        check(bool(np.isfinite(out).all()), "tiled output non-finite")
        views = await engine_views(conn, worker_sid, app_id)
        check(
            any(v["pipeline"]["chunks"] > 0 for v in views),
            "tiled pipeline ran no chunk",
        )

        # one call across the JSON HTTP bridge
        # ws://host:port/ws -> http://host:port; the bridge takes the
        # bare app id, not the workspace-qualified service id
        http_url = "http" + conn.url[len("ws"):].removesuffix("/ws")
        async with aiohttp.ClientSession() as session:
            async with session.post(
                f"{http_url}/call/{app_id}/infer",
                json={
                    "kwargs": {
                        "model_id": MODEL_ID,
                        "inputs": tiles[0].tolist(),
                        "sample_id": "http",
                    }
                },
            ) as resp:
                body = await resp.json()
                check(resp.status == 200, f"HTTP {resp.status}: {body}")
        check_meta(body["result"])
        assert_close(body["result"]["output0"], outputs[0], "HTTP bridge reply")

        views = await engine_views(conn, worker_sid, app_id)
        info.update(
            requests=int(requests),
            batches=int(batches),
            max_abs_err_vs_jit=round(worst, 5),
            device_ids=[v["device_ids"] for v in views],
            mesh=views[0]["mesh"],
            programs=sorted(program_keys(views)),
            real_compiles=sum(v["programs"]["real_compiles"] for v in views),
            persistent_hits=sum(
                v["programs"]["persistent_hits"] for v in views
            ),
            new_in_checked_round=len(new_programs),
        )
    np.savez(
        cfg.out_dir / f"outputs-chips{cfg.chips}.npz", outputs=np.stack(outputs)
    )
    if cfg.parity_with is not None:
        with np.load(cfg.parity_with) as other:
            assert_close(
                np.stack(outputs), other["outputs"], f"parity with {cfg.parity_with}"
            )


# ---- trace ------------------------------------------------------------------


async def trace(cfg: SmokeConfig, report: Report, conn, worker_sid, app_sid) -> None:
    import jax
    import numpy as np

    with report.phase("trace") as info:
        trace_dir = cfg.out_dir / "trace"
        await conn.call(worker_sid, "start_profiling", trace_dir=str(trace_dir))
        x = np.random.default_rng(1).normal(
            size=(1, cfg.tile, cfg.tile, 1)
        ).astype(np.float32)
        for i in range(4):
            await conn.call(
                app_sid, "infer",
                model_id=MODEL_ID, inputs=x, sample_id=f"trace-{i}",
            )
        await conn.call(worker_sid, "stop_profiling")
        (xplane,) = trace_dir.rglob("*.xplane.pb")
        profile = jax.profiler.ProfileData.from_file(str(xplane))
        device_events = {
            plane.name: sum(len(list(line.events)) for line in plane.lines)
            for plane in profile.planes
            if plane.name.startswith("/device:TPU:")
        }
        check(
            any(n > 0 for n in device_events.values()),
            f"no TPU device plane with events in {xplane} "
            f"(planes: {[p.name for p in profile.planes]})",
        )
        info.update(
            xplane=str(xplane.relative_to(cfg.out_dir)),
            xplane_bytes=xplane.stat().st_size,
            device_plane_events=device_events,
        )


# ---- kernel -----------------------------------------------------------------

def kernel(cfg: SmokeConfig, report: Report) -> None:
    import jax
    import jax.numpy as jnp

    from bioengine_tpu.ops.attention import (
        reference_attention,
        unpacked_attention,
    )
    from bioengine_tpu.ops.mlp import reference_mlp
    from bioengine_tpu.ops.pallas.attention import (
        flash_attention,
        packed_flash_attention,
    )
    from bioengine_tpu.ops.pallas.mlp import fused_mlp

    on_chip = cfg.platform == "tpu"  # the rehearsal interprets the kernel

    def flash(q, k, v, causal, scale):
        return flash_attention(
            q, k, v, causal=causal, scale=scale, interpret=not on_chip
        )

    # everything jitted: op-by-op, the reference and its gradient would
    # compile one program per primitive
    reference = jax.jit(reference_attention, static_argnums=(3, 4))

    def grads(fn, causal, scale):
        def total(q, k, v):
            return fn(q, k, v, causal, scale).astype(jnp.float32).sum()

        return jax.jit(jax.grad(total, argnums=(0, 1, 2)))

    def packed_grads(fn, argnums=(0, 1, 2)):
        return jax.jit(
            jax.grad(
                lambda *a: fn(*a).astype(jnp.float32).sum(), argnums=argnums
            )
        )

    with report.phase("kernel") as info:
        errors = {}
        for B, H, N, d_qk, d_v, scale in cfg.kernel_shapes:
            q, k, v = jax.jit(
                lambda: tuple(
                    jax.random.normal(key, (B, H, N, d), jnp.bfloat16)
                    for key, d in zip(
                        jax.random.split(jax.random.key(0), 3),
                        (d_qk, d_qk, d_v),
                    )
                )
            )()
            for causal in (False, True):
                tag = f"{B}x{H}x{N}x{d_qk}/{d_v}{'-causal' if causal else ''}"
                lowered = flash_attention.lower(
                    q, k, v, causal=causal, scale=scale, interpret=not on_chip
                )
                check(
                    not on_chip or "tpu_custom_call" in lowered.as_text(),
                    f"kernel {tag}: lowered module holds no Mosaic custom call",
                )
                fwd = assert_close(
                    flash(q, k, v, causal, scale),
                    reference(q, k, v, causal, scale),
                    f"kernel {tag} forward",
                )
                got = grads(flash, causal, scale)(q, k, v)
                want = grads(reference_attention, causal, scale)(q, k, v)
                grad = max(
                    assert_close(g, w, f"kernel {tag} d{name}")
                    for g, w, name in zip(got, want, "qkv")
                )
                errors[tag] = [round(fwd, 5), round(grad, 5)]
        for B, heads, (H, W) in cfg.packed_shapes:
            tag = f"packed-{B}x{heads}x{H}x{W}"
            operands = jax.jit(
                lambda: tuple(
                    scale * jax.random.normal(key, shape, jnp.bfloat16)
                    for key, scale, shape in zip(
                        jax.random.split(jax.random.key(1), 3),
                        (1.0, 0.3, 0.3),
                        (
                            (B, H * W, 3 * heads * 64),
                            (2 * H - 1, 64),
                            (2 * W - 1, 64),
                        ),
                    )
                )
            )()
            how = dict(grid=(H, W), heads=heads, interpret=not on_chip)
            packed = functools.partial(packed_flash_attention, **how)
            plain = functools.partial(
                unpacked_attention, reference_attention,
                grid=(H, W), heads=heads,
            )
            lowered = packed_flash_attention.lower(*operands, **how)
            check(
                not on_chip or "tpu_custom_call" in lowered.as_text(),
                f"kernel {tag}: lowered module holds no Mosaic custom call",
            )
            fwd = assert_close(
                packed(*operands), jax.jit(plain)(*operands),
                f"kernel {tag} forward",
            )
            grad = max(
                assert_close(g, w, f"kernel {tag} d{name}")
                for g, w, name in zip(
                    packed_grads(packed)(*operands),
                    packed_grads(plain)(*operands),
                    ("qkv", "rel_h", "rel_w"),
                )
            )
            errors[tag] = [round(fwd, 5), round(grad, 5)]
        for shape, hidden in cfg.mlp_shapes:
            dim = shape[-1]
            tag = f"mlp-{'x'.join(map(str, shape))}/{hidden}"
            # y, w1, b1, w2, b2, shortcut: bf16 tokens, the f32 layers
            # of the parameter tree
            operands = jax.jit(
                lambda: tuple(
                    (scale * jax.random.normal(key, shape_, jnp.float32)).astype(dtype)
                    for key, scale, shape_, dtype in zip(
                        jax.random.split(jax.random.key(2), 6),
                        (1.0, dim**-0.5, 0.5, hidden**-0.5, 0.5, 1.0),
                        (shape, (dim, hidden), (hidden,), (hidden, dim), (dim,), shape),
                        (jnp.bfloat16,) + (jnp.float32,) * 4 + (jnp.bfloat16,),
                    )
                )
            )()
            fused = functools.partial(fused_mlp, interpret=not on_chip)
            lowered = fused_mlp.lower(*operands, interpret=not on_chip)
            check(
                not on_chip or "tpu_custom_call" in lowered.as_text(),
                f"kernel {tag}: lowered module holds no Mosaic custom call",
            )
            fwd = assert_close(
                fused(*operands), jax.jit(reference_mlp)(*operands),
                f"kernel {tag} forward",
            )

            grad = max(
                assert_close(g, w, f"kernel {tag} d{name}")
                for g, w, name in zip(
                    packed_grads(fused, tuple(range(6)))(*operands),
                    packed_grads(reference_mlp, tuple(range(6)))(*operands),
                    ("y", "w1", "b1", "w2", "b2", "shortcut"),
                )
            )
            errors[tag] = [round(fwd, 5), round(grad, 5)]
        info.update(max_abs_err_fwd_grad=errors)


# ---- start ... stop ---------------------------------------------------------


@contextlib.asynccontextmanager
async def worker_session(cfg: SmokeConfig, report: Report):
    """start ... stop around the phases in between; yields (client
    connection, worker service id). A failed phase is not caught and
    continued: the run has failed and re-raises, but the worker is
    stopped first so that no thread outlives the traceback."""
    worker, conn, worker_sid = await start_worker(cfg, report)
    try:
        yield conn, worker_sid
    except BaseException:
        await worker.stop()
        raise
    with report.phase("stop"):
        await conn.disconnect()
        await worker.stop()


def lingering_threads() -> list[str]:
    """Non-daemon threads that would keep the interpreter from exiting
    by itself (an engine's request threads, if a close was missed)."""
    return [
        t.name
        for t in threading.enumerate()
        if t is not threading.main_thread() and not t.daemon and t.is_alive()
    ]


# ---- main -------------------------------------------------------------------


async def run(cfg: SmokeConfig, report: Report) -> None:
    async with worker_session(cfg, report) as (conn, worker_sid):
        model, params = make_package(cfg, report)
        app_id, app_sid = await deploy(cfg, report, conn, worker_sid)
        await serve(
            cfg, report, conn, worker_sid, app_id, app_sid, model, params
        )
        await trace(cfg, report, conn, worker_sid, app_sid)
        kernel(cfg, report)


def main(argv: Optional[list[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1)
    parser.add_argument("--parity-with", type=Path, default=None)
    args = parser.parse_args(argv)
    cfg = SmokeConfig(chips=args.chips, parity_with=args.parity_with)
    report = Report()
    device_gate(cfg, report)
    # each run starts from an empty workspace (a previous life's
    # deployed.json would be re-adopted); saved outputs of other runs stay
    for sub in ("workspace", "model-cache", "collection", "trace",
                f"model-runner-chips{cfg.chips}"):
        shutil.rmtree(cfg.out_dir / sub, ignore_errors=True)
    cfg.out_dir.mkdir(parents=True, exist_ok=True)
    asyncio.run(run(cfg, report))
    report.close()
    left = lingering_threads()
    check(not left, f"threads outlived worker.stop(): {left}")
    print(json.dumps({"ok": True, "device": report.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
