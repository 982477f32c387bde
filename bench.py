"""Headline benchmark with a hard wall-clock deadline and guaranteed output.

Measures the BASELINE.json single-chip configs plus two targeted
substages the round-4 verdict asked for:

  1. DINOv2-geometry ViT-B/14 embedding throughput (headline) — the
     reference publishes ~500 images/sec on one A100 (fp16, batch 64)
     for DINOv2 ViT-B/14 cell-crop embedding
     (ref apps/cell-image-search/README.md:122, embedder.py:11,40-70).
     ``vs_baseline`` = images/sec / 500.
  2. U-Net 256x256 tile inference images/sec (model-runner hot path,
     ref apps/model-runner/runtime_deployment.py:234-312).
  3. Cellpose fine-tune train step/sec at batch 8 x 256x256
     (ref apps/cellpose-finetuning/main.py:1278-1360).
  4. TPU index search latency: Flat 100K / IVFFlat 200K / IVFPQ 1M
     (ADC path) vs the reference FAISS-CPU baselines
     (ref apps/cell-image-search/README.md:132-134).
  5. flash: XLA attention vs the Pallas flash kernel at n_tokens >=
     1024 — the regime where the embedder's auto mode would enable it.
  6. UNet3D volumetric throughput (32x256x256 stack).

DEADLINE DESIGN. The orchestrator guarantees exactly ONE final JSON
line on stdout before ``BENCH_DEADLINE`` seconds (default 480), no
matter what: all measurement runs in a subprocess whose stdout is
streamed line-by-line into shared state; the MAIN thread is a watchdog
that waits until the deadline margin, kills the subprocess group if it
is still alive, and prints the final JSON assembled from whatever
stages completed. The orchestrator itself never imports JAX (a chip
belongs to one process at a time: the ``--worker`` child holds it).

EXIT STATUS. 0 only when the worker's probe found a TPU (or
``BENCH_PLATFORM=cpu`` asked for the CPU) AND every wanted stage
completed ok; 1 otherwise — the JSON line is printed either way.

The worker itself is deadline-aware: it receives its remaining budget
and skips stages whose estimated cost no longer fits, emitting
``skipped`` stage lines so the artifact says what was dropped and why
(no silent truncation).

Timing note: each config runs ITERS iterations inside one jitted
``lax.scan`` with a serial data dependency between iterations (each
step's input is perturbed by the previous step's output mean,
preventing XLA from hoisting the loop-invariant computation), and
forces completion with a device->host fetch of the scalar carry. One
round-trip is amortized over the whole scan.

Prints exactly ONE JSON line on stdout (the last line):
  {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N,
   "extra": {...other stages, probe info, skipped, diagnostics...}}

Env overrides:
  BENCH_DEADLINE=N      hard total wall-clock seconds (default 480)
  BENCH_PLATFORM=cpu    run on host CPU (tiny shapes, not a real number)
  BENCH_ATTEMPTS=N      subprocess attempts (default 2)
  BENCH_TIMEOUT=N       per-attempt cap, also capped by the deadline
  BENCH_STALL=N         kill an attempt after N s with no stage output
                        (mid-stage hang detector; default 240)
  BENCH_CONFIGS=a,b,c   subset of vit,unet,sharded_serving,
                        multihost_mesh,cold_start,cellpose,search,
                        observability_overhead,scheduler_goodput,flash,
                        unet3d,ivfpq,pqflat,rpc_transport,
                        request_overhead,router_scaling,token_streaming
  BENCH_ROUTER_LEGS=a,b router counts for the router_scaling stage
                        (default 1,2,4,8)
  BENCH_REPS=N          timed reps per stage (default 2, best-of)
  BENCH_PROFILE=dir     capture a jax.profiler trace of one rep per config
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

BASELINE_VIT_IMG_PER_SEC = 500.0  # ref cell-image-search/README.md:122 (1x A100)

# single source of the stage set: (name, estimated worst-case seconds on
# a healthy chip incl. compile) in priority order — headline + cheap
# stages first so a tight budget still yields the metrics that matter
STAGE_COSTS = {
    "vit": 60,
    "unet": 45,
    "sharded_serving": 50,
    "multihost_mesh": 45,
    "cold_start": 50,
    "pipeline_overlap": 60,
    "cellpose": 60,
    "search": 40,
    "observability_overhead": 25,
    "scheduler_goodput": 25,
    "gray_failure": 20,
    "flash": 55,
    "unet3d": 70,
    "ivfpq": 70,   # measured 46 s standalone (train 20 + encode 22)
    "pqflat": 80,
    "rpc_transport": 60,
    "request_overhead": 30,
    "router_scaling": 30,
    "token_streaming": 45,
}
DEFAULT_CONFIGS = tuple(STAGE_COSTS)

# ---------------------------------------------------------------------------
# Worker: runs in a subprocess, prints one JSON line per stage on stdout.
# ---------------------------------------------------------------------------


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _timed_scan(run, *args) -> float:
    """Best-of-reps wall time for a pre-jitted serial-dependency scan.

    BENCH_PROFILE=<dir>: capture a jax.profiler trace of one timed rep
    (inspect with tensorboard / xprof)."""
    import numpy as np

    reps = int(os.environ.get("BENCH_REPS", "2"))
    _ = np.asarray(run(*args))  # warmup: compile + one full execution
    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir:
        import jax

        with jax.profiler.trace(profile_dir):
            _ = np.asarray(run(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        _ = np.asarray(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best


# ViT-B/14 @224 analytic forward FLOPs (multiply+add = 2 per MAC):
# per block 24*N*d^2 + 4*N^2*d with N=257, d=768; 12 blocks + patch
# embed ≈ 46.3 GFLOP/image.
VIT_FLOPS_PER_IMAGE = 46.3e9
# bf16 peak FLOP/s per chip, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16). A kind
# that is not in the table gets no utilization figure at all.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _bench_vit(cpu: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from bioengine_tpu.models.vit import ViT

    # batch 128, bf16 softmax and XLA attention: the same config as
    # apps/cell-image-search/embedder.py (the ``flash`` stage compares
    # the Pallas kernel in the long-sequence regime).
    batch, iters = (4, 2) if cpu else (128, 20)
    model = ViT(patch_size=14, dim=768, depth=12, num_heads=12)  # ViT-B/14
    images = jnp.zeros((batch, 224, 224, 3), jnp.bfloat16)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 224, 224, 3), jnp.float32)
    )["params"]

    def chained(params, images):
        def step(carry, _):
            x = images + carry.astype(images.dtype)
            emb = model.apply({"params": params}, x)
            return jnp.mean(emb).astype(jnp.float32), None

        carry, _ = jax.lax.scan(step, jnp.float32(0.0), None, length=iters)
        return carry

    best = _timed_scan(jax.jit(chained), params, images)
    ips = batch * iters / best
    out = {
        "images_per_sec": round(ips, 2),
        "batch": batch,
        "softmax_dtype": "bfloat16",
        "attention": "xla",
    }
    device_kind = jax.devices()[0].device_kind
    peak = PEAK_BF16_FLOPS.get(device_kind)
    if peak is not None:
        out["mfu_pct"] = round(100 * ips * VIT_FLOPS_PER_IMAGE / peak, 1)
        out["flops_convention"] = (
            f"2*MAC, 46.3 GFLOP/img vs {peak / 1e12:.0f} TF/s "
            f"{device_kind} bf16 peak"
        )
    return out


def _bench_unet(cpu: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from bioengine_tpu.models.unet import UNet2D

    batch, iters = (2, 2) if cpu else (16, 20)
    model = UNet2D(features=(32, 64, 128, 256), out_channels=1)
    tiles = jnp.zeros((batch, 256, 256, 1), jnp.float32)
    params = model.init(jax.random.key(0), tiles)["params"]

    def chained(params, tiles):
        def step(carry, _):
            x = tiles + carry * jnp.float32(1e-6)
            out = model.apply({"params": params}, x)
            return jnp.mean(out).astype(jnp.float32), None

        carry, _ = jax.lax.scan(step, jnp.float32(0.0), None, length=iters)
        return carry

    best = _timed_scan(jax.jit(chained), params, tiles)
    return {"images_per_sec": round(batch * iters / best, 2), "batch": batch}


def _bench_unet3d(cpu: bool) -> dict:
    """Volumetric family throughput: UNet3D on a 32x256x256 stack (the
    engine's direct bucketed path — one jitted forward per volume)."""
    import jax
    import jax.numpy as jnp

    from bioengine_tpu.models.unet3d import UNet3D

    if cpu:
        depth, hw, iters, feats = 4, 32, 2, (4, 8)
    else:
        depth, hw, iters, feats = 32, 256, 10, (16, 32, 64)
    model = UNet3D(features=feats, out_channels=1)
    vol = jnp.zeros((1, depth, hw, hw, 1), jnp.float32)
    params = model.init(jax.random.key(0), vol)["params"]

    def chained(params, vol):
        def step(carry, _):
            x = vol + carry * jnp.float32(1e-6)
            out = model.apply({"params": params}, x)
            return jnp.mean(out).astype(jnp.float32), None

        carry, _ = jax.lax.scan(step, jnp.float32(0.0), None, length=iters)
        return carry

    best = _timed_scan(jax.jit(chained), params, vol)
    voxels = depth * hw * hw
    return {
        "volumes_per_sec": round(iters / best, 3),
        "mvoxels_per_sec": round(iters * voxels / best / 1e6, 1),
        "shape": [depth, hw, hw],
    }


def _sharded_serving_measure(cpu: bool) -> dict:
    """The in-interpreter body of the sharded_serving stage — runs in
    its OWN subprocess (``--sharded-worker``) so the forced 4-host-
    device XLA flag never touches the layout any other stage is
    measured under."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bioengine_tpu.models.unet import UNet2D
    from bioengine_tpu.runtime.engine import EngineConfig, InferenceEngine
    from bioengine_tpu.runtime.program_cache import CompiledProgramCache

    devices = jax.devices()
    k = min(4, len(devices))
    if cpu:
        hw, feats, batch, iters = 128, (8, 16), 16, 4
    else:
        hw, feats, batch, iters = 512, (32, 64, 128, 256), 32, 8
    model = UNet2D(features=feats, out_channels=1)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, hw, hw, 1), jnp.float32)
    )["params"]
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, hw, hw, 1)).astype(np.float32)
    reps = int(os.environ.get("BENCH_REPS", "2"))

    def build(devs):
        return InferenceEngine(
            "sharded-serving-bench",
            lambda p, t: model.apply({"params": p}, t),
            params,
            divisor=model.divisor,
            config=EngineConfig(max_tile=hw),
            cache=CompiledProgramCache(),
            devices=devs,
        )

    def throughput(engine) -> tuple[float, np.ndarray]:
        out = engine.predict(x)  # warmup: compile + staging buffers
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                engine.predict(x)
            best = min(best, time.perf_counter() - t0)
        return batch * iters / best, out

    e1 = build(devices[:1])
    try:
        per_sec_1, y1 = throughput(e1)
    finally:
        e1.close()
    result = {
        "batch": batch,
        "image_hw": hw,
        "n_devices": k,
        "images_per_sec_1chip": round(per_sec_1, 2),
    }
    if k < 2:
        # single-chip environment: the sharded leg cannot run — say so
        # instead of silently reporting a degenerate 1x
        result.update(
            images_per_sec_dp=None, speedup=None,
            dp_scaling_efficiency=None, mesh=None,
            parity_max_abs_err=None, parity_ok=None,
            note="only one device visible — dp leg skipped",
        )
        return result
    ek = build(devices[:k])
    try:
        per_sec_k, yk = throughput(ek)
        mesh = ek.mesh_shape
    finally:
        ek.close()
    speedup = per_sec_k / max(per_sec_1, 1e-9)
    err = float(np.max(np.abs(y1 - yk)))
    result.update(
        images_per_sec_dp=round(per_sec_k, 2),
        speedup=round(speedup, 3),
        dp_scaling_efficiency=round(speedup / k, 3),
        mesh=mesh,
        parity_max_abs_err=err,
        parity_ok=bool(
            np.allclose(y1, yk, rtol=1e-4, atol=1e-5)
        ),
    )
    return result


def _bench_sharded_serving(cpu: bool) -> dict:
    """1-chip vs K-chip engine throughput on the same bucketed batch
    workload (the serving hot path: host batch -> sharded device_put ->
    jitted forward -> host readback), plus the dp scaling efficiency
    (speedup / K) and a parity check between the two engines' outputs.

    On TPU this is the sharded-serving headline: a K-chip replica
    should deliver ~K x the 1-chip throughput. On CPU the measurement
    needs a forced 4-host-device layout — and that XLA flag must NOT
    leak into the layout every other stage runs under (their numbers
    would stop being comparable to earlier BENCH_r{N}.json rounds), so
    the stage runs in its own subprocess (``bench.py --sharded-worker``)
    where the flag is injected. On TPU the measurement runs in-process:
    the real chips are already visible, no flag is needed, and a second
    process must not contend with the worker for the accelerator."""
    if not cpu:
        return _sharded_serving_measure(False)
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--sharded-worker"],
        capture_output=True,
        text=True,
        env=env,
        # deliberately NOT BENCH_TIMEOUT (the orchestrator's per-attempt
        # cap) — a driver tightening that knob must not starve the
        # subprocess mid-compile
        timeout=float(os.environ.get("BENCH_SHARDED_WORKER_TIMEOUT", "240")),
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded-worker rc={proc.returncode}: {proc.stderr[-500:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def sharded_worker_main() -> int:
    """``bench.py --sharded-worker``: one stage, own interpreter, prints
    one JSON line (the measurement dict) on stdout."""
    cpu = os.environ.get("BENCH_PLATFORM", "").lower() == "cpu"
    if cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    print(json.dumps(_sharded_serving_measure(cpu)), flush=True)
    return 0


# ---------------------------------------------------------------------------
# multihost_mesh stage: the SAME pipeline-mesh deployment spec measured
# on a 1-host mesh vs spanning 2 simulated hosts (serving/mesh_plan.py
# + mesh_replica.py over real in-process websockets) — images/sec both
# legs, activation-transfer accounting, scaling efficiency, and the
# RpcStats proof that activations rode the zero-copy OOB path.
# ---------------------------------------------------------------------------

_MESH_BENCH_MANIFEST = """\
name: Mesh Bench
id: mesh-bench
id_emoji: "\U0001F578"
description: two-stage pipeline mesh for the multihost_mesh stage
type: tpu-serve
version: 1.0.0
deployments:
  - mesh_dep:MeshDep
authorized_users: ["*"]
deployment_config:
  mesh_dep:
    num_replicas: 1
    autoscale: false
    mesh:
      stages: 2
      chips_per_stage: 2
      kind: pipeline
"""

_MESH_BENCH_SOURCE = '''\
import numpy as np

from bioengine_tpu.rpc import schema_method

N_STAGES = 2
CHANNELS = 16


def stage_params(stage):
    rng = np.random.default_rng(100 + stage)
    return {
        "w": (rng.standard_normal((CHANNELS, CHANNELS)) * 0.2).astype(
            np.float32
        ),
        "b": (rng.standard_normal((CHANNELS,)) * 0.1).astype(np.float32),
    }


class MeshDep:
    async def async_init(self):
        import jax.numpy as jnp

        from bioengine_tpu.runtime.engine import (
            InferenceEngine,
            resolve_devices,
        )

        shard = getattr(self, "bioengine_mesh_shard", None)
        lease = getattr(self, "bioengine_device_ids", None)
        devices = resolve_devices(list(lease)) if lease else None
        axes = dict(shard["axes"]) if shard else {"dp": -1}
        stages = (
            [int(shard["stage"])] if shard is not None else range(N_STAGES)
        )
        self.engines = {}
        for k in stages:
            last = k == N_STAGES - 1

            def make_apply(last=last):
                def apply_fn(params, x):
                    y = x @ params["w"] + params["b"]
                    return y if last else jnp.maximum(y, 0.0)

                return apply_fn

            self.engines[k] = InferenceEngine(
                f"mesh-bench-stage-{k}",
                make_apply(),
                stage_params(k),
                devices=devices,
                mesh_axes=axes,
            )

    @schema_method
    async def run_stage(self, stage: int, inputs, context=None):
        """One pipeline stage's forward."""
        return await self.engines[int(stage)].predict_async(
            np.asarray(inputs, np.float32)
        )

    @schema_method
    async def predict(self, inputs, context=None):
        """Full forward (entry method the mesh driver intercepts)."""
        x = np.asarray(inputs, np.float32)
        for k in sorted(self.engines):
            x = await self.engines[k].predict_async(x)
        return x

    async def close(self):
        for engine in self.engines.values():
            engine.close()
'''


def _mesh_bench_reference(x):
    """Independent numpy forward of the bench app's 2-stage model."""
    import numpy as np

    ch = 16
    params = []
    for stage in range(2):
        rng = np.random.default_rng(100 + stage)
        params.append(
            (
                (rng.standard_normal((ch, ch)) * 0.2).astype(np.float32),
                (rng.standard_normal((ch,)) * 0.1).astype(np.float32),
            )
        )
    h = np.maximum(x @ params[0][0] + params[0][1], 0.0)
    return h @ params[1][0] + params[1][1]


def _multihost_mesh_measure(n_hosts: int) -> dict:
    """One leg: in-process control plane (real websockets), ``n_hosts``
    worker hosts, ONE mesh deployment from the same spec — measured
    requests/sec plus the mesh driver's transfer accounting and the
    server codec's OOB counters."""
    import asyncio
    import tempfile
    from pathlib import Path

    import numpy as np

    async def run() -> dict:
        from bioengine_tpu.apps.builder import AppBuilder
        from bioengine_tpu.cluster.state import ClusterState
        from bioengine_tpu.cluster.topology import TpuTopology
        from bioengine_tpu.rpc.server import RpcServer
        from bioengine_tpu.serving import ServeController
        from bioengine_tpu.worker_host import WorkerHost

        tmp = Path(tempfile.mkdtemp(prefix="bench-mesh-"))
        app_dir = tmp / "src"
        app_dir.mkdir()
        (app_dir / "manifest.yaml").write_text(_MESH_BENCH_MANIFEST)
        (app_dir / "mesh_dep.py").write_text(_MESH_BENCH_SOURCE)

        server = RpcServer(host="127.0.0.1", admin_users=["admin"])
        await server.start()
        token = server.issue_token("admin", is_admin=True)
        controller = ServeController(
            ClusterState(TpuTopology(chips=(), n_hosts=1, platform="cpu")),
            health_check_period=3600,
        )
        controller.attach_rpc(server, admin_users=["admin"])
        hosts = []
        try:
            for i in range(n_hosts):
                host = WorkerHost(
                    server_url=server.url,
                    token=token,
                    host_id=f"bh{i}",
                    workspace_dir=tmp / f"ws{i}",
                )
                await host.start()
                hosts.append(host)
            built = AppBuilder(workdir_root=tmp / "apps").build(
                app_id="mesh-bench", local_path=app_dir
            )
            await controller.deploy("mesh-bench", built.specs)
            mesh = controller.apps["mesh-bench"].replicas["mesh_dep"][0]
            handle = controller.get_handle("mesh-bench", "mesh_dep")

            batch, hw = 8, 32
            rng = np.random.default_rng(0)
            x = rng.standard_normal((batch, hw, hw, 16)).astype(np.float32)
            out = np.asarray(await handle.call("predict", x))  # warmup
            err = float(np.max(np.abs(out - _mesh_bench_reference(x))))

            iters = int(os.environ.get("BENCH_MESH_ITERS", "12"))
            reps = int(os.environ.get("BENCH_REPS", "2"))
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                for _ in range(iters):
                    await handle.call("predict", x)
                best = min(best, time.perf_counter() - t0)
            n_calls = reps * iters + 1  # transfer totals span every call
            stats = mesh.engine.stats()
            rpc = server.stats.as_dict()
            return {
                "n_hosts": n_hosts,
                "batch": batch,
                "image_hw": hw,
                "cross_host": mesh.plan.cross_host,
                "hosts": mesh.plan.hosts,
                "images_per_sec": round(batch * iters / best, 2),
                "parity_max_abs_err": err,
                "parity_ok": bool(err < 1e-3),
                "transfer_bytes_per_request": int(
                    stats["transfer_bytes"] / n_calls
                ),
                "transfer_seconds_per_request": round(
                    stats["transfer_seconds"] / n_calls, 6
                ),
                "oob_payloads_out": rpc["oob_payloads_out"],
                "legacy_msgs_out": rpc["legacy_msgs_out"],
            }
        finally:
            for host in hosts:
                try:
                    await host.stop()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
            await controller.stop()
            await server.stop()

    return asyncio.run(run())


def _bench_multihost_mesh(cpu: bool) -> dict:
    """1-host vs 2-simulated-host pipeline mesh on the SAME workload
    and the SAME deployment spec — the topology-portability headline.
    ``scaling_efficiency`` (2-host / 1-host images/sec) reads as the
    cost of crossing hosts: ~1.0 means the activation hops are free
    relative to compute; well under 1.0 means the split is
    transfer-bound at this model size. On CPU each leg runs in its own
    ``--multihost-worker`` subprocess under a forced 4-host-device
    layout (the flag never touches the orchestrator's interpreter,
    same isolation as --sharded-worker); numbers there are core-bound
    and informational — schema, parity, and the OOB pin are the
    contract."""
    legs: dict[int, dict] = {}
    for n_hosts in (1, 2):
        if not cpu:
            legs[n_hosts] = _multihost_mesh_measure(n_hosts)
            continue
        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
        proc = subprocess.run(
            [
                sys.executable,
                os.path.abspath(__file__),
                "--multihost-worker",
                str(n_hosts),
            ],
            capture_output=True,
            text=True,
            env=env,
            timeout=float(
                os.environ.get("BENCH_MULTIHOST_WORKER_TIMEOUT", "240")
            ),
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"multihost-worker({n_hosts}) rc={proc.returncode}: "
                f"{proc.stderr[-500:]}"
            )
        legs[n_hosts] = json.loads(proc.stdout.strip().splitlines()[-1])
    one, two = legs[1], legs[2]
    speed_1, speed_2 = one["images_per_sec"], two["images_per_sec"]
    return {
        "batch": two["batch"],
        "image_hw": two["image_hw"],
        "stages": 2,
        "images_per_sec_1host": speed_1,
        "images_per_sec_2host": speed_2,
        "scaling_efficiency": round(speed_2 / max(speed_1, 1e-9), 3),
        "cross_host_overhead_ms_per_request": round(
            (
                two["batch"] / max(speed_2, 1e-9)
                - one["batch"] / max(speed_1, 1e-9)
            )
            * 1000,
            3,
        ),
        "transfer_bytes_per_request": two["transfer_bytes_per_request"],
        "transfer_seconds_per_request": two["transfer_seconds_per_request"],
        "cross_host_1host": one["cross_host"],
        "cross_host_2host": two["cross_host"],
        "parity_ok": bool(one["parity_ok"] and two["parity_ok"]),
        "parity_max_abs_err": max(
            one["parity_max_abs_err"], two["parity_max_abs_err"]
        ),
        # the zero-copy pin: activation frames were extracted into OOB
        # scatter-gather tables (RpcStats), never legacy inline packs
        "oob_payloads_out": two["oob_payloads_out"],
        "legacy_msgs_out": two["legacy_msgs_out"],
    }


def multihost_worker_main() -> int:
    """``bench.py --multihost-worker N``: one mesh leg (N in-process
    hosts), own interpreter, prints one JSON line on stdout."""
    cpu = os.environ.get("BENCH_PLATFORM", "").lower() == "cpu"
    if cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    idx = sys.argv.index("--multihost-worker")
    n_hosts = int(sys.argv[idx + 1])
    print(json.dumps(_multihost_mesh_measure(n_hosts)), flush=True)
    return 0


# ---------------------------------------------------------------------------
# cold_start stage: replica time-to-first-request, cold vs warm-cache vs
# warm-pool, on the model-runner jax_params path.
# ---------------------------------------------------------------------------


def _make_cold_start_package(root: str) -> str:
    """A tiny self-contained jax_params model package (model-runner
    layout: rdf.yaml + weights.npz + key→shape streaming manifest) the
    cold-start legs load — same shape as the real Zoo packages, small
    enough that COMPILE dominates, exactly like production."""
    from pathlib import Path

    import jax
    import jax.numpy as jnp
    import numpy as np
    import yaml

    from bioengine_tpu.models.unet import UNet2D
    from bioengine_tpu.runtime.convert import flatten_params, save_params_npz
    from bioengine_tpu.runtime.weight_stream import write_manifest

    d = Path(root) / "coldstart-unet"
    d.mkdir(parents=True, exist_ok=True)
    model = UNet2D(features=(8, 16), out_channels=1)
    x = np.random.default_rng(0).normal(size=(1, 64, 64, 1)).astype(np.float32)
    params = model.init(jax.random.key(0), jnp.asarray(x))["params"]
    save_params_npz(str(d / "weights.npz"), params)
    write_manifest(d / "weights.npz", flatten_params(params))
    np.save(d / "test_input.npy", x)
    (d / "rdf.yaml").write_text(
        yaml.safe_dump(
            {
                "type": "model",
                "name": "ColdStart UNet",
                "description": "cold-start bench model",
                "inputs": [{"name": "input0", "axes": "byxc"}],
                "outputs": [{"name": "output0", "axes": "byxc"}],
                "test_inputs": ["test_input.npy"],
                "documentation": "README.md",
                "weights": {
                    "jax_params": {
                        "source": "weights.npz",
                        "architecture": {
                            "name": "unet2d",
                            "kwargs": {"features": [8, 16], "out_channels": 1},
                        },
                    }
                },
            }
        )
    )
    (d / "README.md").write_text("cold-start bench model")
    return str(d)


def _load_model_runner_module():
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "apps",
        "model-runner",
        "runtime_deployment.py",
    )
    spec = importlib.util.spec_from_file_location("bench_mr_rt", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cold_start_worker_main() -> int:
    """``bench.py --cold-start-worker``: ONE replica cold start in its
    own interpreter (the only honest way to measure it — an in-process
    leg would hit the in-memory program cache). Builds the model-runner
    Pipeline against $BENCH_COLDSTART_PACKAGE with the persistent XLA
    cache at $JAX_COMPILATION_CACHE_DIR and reports the TTFR breakdown as
    one JSON line."""
    cpu = os.environ.get("BENCH_PLATFORM", "").lower() == "cpu"
    if cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from bioengine_tpu.utils.compile_cache import (
        enable_persistent_compilation_cache,
    )

    package = os.environ["BENCH_COLDSTART_PACKAGE"]
    # the parent leg put the (initially empty) cache directory in
    # JAX_COMPILATION_CACHE_DIR
    enable_persistent_compilation_cache()
    rt = _load_model_runner_module()
    x = np.load(os.path.join(package, "test_input.npy"))
    t_start = time.perf_counter()
    pipeline = rt.Pipeline(package)
    build_s = time.perf_counter() - t_start
    t1 = time.perf_counter()
    pipeline.predict(x)
    first_request_s = time.perf_counter() - t1
    ttfr_s = time.perf_counter() - t_start
    info = pipeline.cold_start_info()
    print(
        json.dumps(
            {
                "ttfr_s": round(ttfr_s, 4),
                "build_s": round(build_s, 4),
                "first_request_s": round(first_request_s, 4),
                "weights_s": info.get("weights_seconds"),
                "compile_s": info.get("compile_seconds"),
                "streamed": info.get("streamed"),
                "persistent_cache_hits": info.get("persistent_cache_hits"),
                "real_compiles": info.get("real_compiles"),
            }
        ),
        flush=True,
    )
    return 0


def _cold_start_warm_pool_leg(package: str) -> dict:
    """The warm-pool leg runs in-process by design: promotion IS an
    in-process list move, and the promoted standby's programs live in
    its own warm program cache. Measures promote → first request on a
    controller-managed pool of 1."""
    import asyncio

    import numpy as np

    from bioengine_tpu.cluster.state import ClusterState
    from bioengine_tpu.serving import (
        DeploymentSpec,
        ServeController,
        WarmPoolConfig,
    )

    rt = _load_model_runner_module()
    x = np.load(os.path.join(package, "test_input.npy"))

    class ColdStartApp:
        def __init__(self):
            self.pipeline = None

        async def async_init(self):
            self.pipeline = await asyncio.to_thread(rt.Pipeline, package)

        async def test_deployment(self):
            # a standby is warm BECAUSE its self-test compiled the
            # serving programs — exactly what production app tests do
            await asyncio.to_thread(self.pipeline.predict, x)

        async def predict(self):
            out = await asyncio.to_thread(self.pipeline.predict, x)
            return list(next(iter(out.values())).shape)

        def close(self):
            if self.pipeline is not None:
                self.pipeline.close()

    async def run() -> dict:
        controller = ServeController(ClusterState(), health_check_period=3600)
        spec = DeploymentSpec(
            name="entry",
            instance_factory=ColdStartApp,
            num_replicas=1,
            max_replicas=4,
            autoscale=False,
            warm_pool=WarmPoolConfig(size=1, refill=False),
        )
        app = await controller.deploy("coldstart-bench", [spec])
        pool = controller._warm_pools[("coldstart-bench", "entry")]
        try:
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if pool.standbys and all(
                    r.state.value == "HEALTHY" for r in pool.standbys
                ):
                    break
                await asyncio.sleep(0.05)
            else:
                raise RuntimeError("warm standby never became HEALTHY")
            t0 = time.perf_counter()
            promoted = await controller._add_replica(app, spec)
            promote_s = time.perf_counter() - t0
            await promoted.call("predict")
            ttfr_s = time.perf_counter() - t0
            return {
                "ttfr_s": round(ttfr_s, 4),
                "promote_s": round(promote_s, 4),
                "first_request_s": round(ttfr_s - promote_s, 4),
                "promoted_from_warm_pool": bool(
                    promoted.promoted_from_warm_pool
                ),
                "promotions": pool.promotions,
            }
        finally:
            await controller.stop()

    return asyncio.run(run())


def _bench_cold_start(cpu: bool) -> dict:
    """Replica TTFR on the model-runner path, three legs: COLD (fresh
    process, empty compile cache), WARM-CACHE (fresh process, the cache
    the cold leg just populated — the shared-tier experience of a new
    host after ``program.cache_fetch``), WARM-POOL (standby promotion).
    The acceptance number is speedup_warm_pool: the warm path must beat
    the cold path by ≥10x."""
    import tempfile

    if not cpu:
        # one process per chip: this --worker process has held the
        # device since its probe op, and the cold/warm-cache legs are
        # fresh processes that build a Pipeline on it — they would die
        # in libtpu at backend init. Fail the stage typed; the legs
        # belong to an orchestrator that stays off JAX (ROADMAP S1).
        raise RuntimeError(
            "cold_start: the stage's fresh-process legs need the "
            "device this worker process already holds (one process "
            "per chip); run it with BENCH_PLATFORM=cpu"
        )
    root = tempfile.mkdtemp(prefix="bench-coldstart-")
    package = _make_cold_start_package(root)
    cache_dir = os.path.join(root, "xla-cache")

    def subprocess_leg() -> dict:
        env = dict(os.environ)
        env["BENCH_COLDSTART_PACKAGE"] = package
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        proc = subprocess.run(
            [
                sys.executable,
                os.path.abspath(__file__),
                "--cold-start-worker",
            ],
            capture_output=True,
            text=True,
            env=env,
            timeout=float(
                os.environ.get("BENCH_COLDSTART_WORKER_TIMEOUT", "180")
            ),
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"cold-start worker rc={proc.returncode}: "
                f"{proc.stderr[-500:]}"
            )
        return json.loads(proc.stdout.strip().splitlines()[-1])

    cold = subprocess_leg()
    warm_cache = subprocess_leg()  # same dir, populated by the cold leg
    warm_pool = _cold_start_warm_pool_leg(package)
    return {
        "cold": cold,
        "warm_cache": warm_cache,
        "warm_pool": warm_pool,
        "speedup_warm_cache": round(
            cold["ttfr_s"] / max(warm_cache["ttfr_s"], 1e-9), 2
        ),
        "speedup_warm_pool": round(
            cold["ttfr_s"] / max(warm_pool["ttfr_s"], 1e-9), 2
        ),
        "warm_cache_hit_observed": bool(
            (warm_cache.get("persistent_cache_hits") or 0) > 0
        ),
    }


def _bench_pipeline_overlap(cpu: bool) -> dict:
    """Serial vs overlapped tiled inference (the engine's blockwise
    path, runtime/pipeline.py): same model, same tiles, same programs —
    the delta is purely host/device overlap (async dispatch window +
    staging/stitch threads + donated buffers). Reports both
    throughputs, the speedup, the per-stage seconds, and the measured
    overlap efficiency (device-busy / wall). On CPU the backend
    dispatch is near-synchronous, so the numbers are informational —
    the stage exists there to prove the path runs and the artifact
    schema holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bioengine_tpu.models.unet import UNet2D
    from bioengine_tpu.runtime.engine import EngineConfig, InferenceEngine
    from bioengine_tpu.runtime.pipeline import PipelineStats
    from bioengine_tpu.runtime.program_cache import CompiledProgramCache

    if cpu:
        hw, tile, overlap, feats, items, tile_batch = 192, 64, 8, (4, 8), 1, 4
    else:
        hw, tile, overlap, feats, items, tile_batch = (
            2048, 512, 64, (32, 64, 128, 256), 2, 8,
        )
    model = UNet2D(features=feats, out_channels=1)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, tile, tile, 1), jnp.float32)
    )["params"]
    cfg = EngineConfig(
        max_tile=tile, tile=tile, tile_overlap=overlap,
        tile_batch=tile_batch, pipeline_depth=2,
    )
    engine = InferenceEngine(
        "pipeline-bench",
        lambda p, x: model.apply({"params": p}, x),
        params,
        divisor=model.divisor,
        config=cfg,
        cache=CompiledProgramCache(),
    )
    rng = np.random.default_rng(0)
    x = rng.standard_normal((items, hw, hw, 1)).astype(np.float32)
    reps = int(os.environ.get("BENCH_REPS", "2"))

    engine.predict_serial(x)  # warmup: compile every chunk program
    best_serial = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        engine.predict_serial(x)
        best_serial = min(best_serial, time.perf_counter() - t0)

    engine.predict(x)  # pipelined warmup (threads, staging buffers)
    best_pipe = float("inf")
    stats = None
    for _ in range(reps):
        # fresh stats per rep so overlap efficiency reflects the best
        # rep alone, not warmup or earlier reps
        engine.pipeline_stats = PipelineStats(depth=cfg.pipeline_depth)
        t0 = time.perf_counter()
        engine.predict(x)
        dt = time.perf_counter() - t0
        if dt < best_pipe:
            best_pipe, stats = dt, engine.pipeline_stats
    try:
        n_tiles = items * len(
            engine._tile_plan((hw, hw), engine._axis_specs(4)).coords
        )
        stage_detail = stats.as_dict()
        return {
            "serial_s": round(best_serial, 3),
            "pipelined_s": round(best_pipe, 3),
            "speedup": round(best_serial / max(best_pipe, 1e-9), 3),
            "serial_tiles_per_sec": round(n_tiles / best_serial, 2),
            "pipelined_tiles_per_sec": round(n_tiles / best_pipe, 2),
            "overlap_efficiency": stage_detail["overlap_efficiency"],
            "pipeline_stats": stage_detail,
            "image_hw": hw,
            "tile": tile,
            "depth": cfg.pipeline_depth,
            "n_tiles": n_tiles,
        }
    finally:
        engine.close()


def _bench_cellpose(cpu: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from bioengine_tpu.models.cellpose import (
        CellposeConfig,
        create_model_and_state,
        make_train_step,
    )

    batch, hw, iters = (2, 64, 2) if cpu else (8, 256, 10)
    _, state = create_model_and_state(
        CellposeConfig(), jax.random.key(0), input_hw=(hw, hw)
    )
    step_fn = make_train_step(dp_axis=None)
    images = jnp.zeros((batch, hw, hw, 2), jnp.float32)
    flows = jnp.zeros((batch, hw, hw, 2), jnp.float32)
    cellprob = jnp.zeros((batch, hw, hw), jnp.float32)

    def chained(state, images, flows, cellprob):
        def body(carry, _):
            st, c = carry
            x = images + c * jnp.float32(1e-6)
            st, metrics = step_fn(st, x, flows, cellprob)
            return (st, metrics["loss"].astype(jnp.float32)), None

        (st, c), _ = jax.lax.scan(
            body, (state, jnp.float32(0.0)), None, length=iters
        )
        return c

    best = _timed_scan(jax.jit(chained), state, images, flows, cellprob)
    return {"steps_per_sec": round(iters / best, 2), "batch": batch, "hw": hw}


def _bench_flash(cpu: bool) -> dict:
    """XLA fused attention vs the Pallas flash kernel, head-to-head, at
    the sequence lengths where the embedder's auto mode would switch
    the kernel on (n_tokens >= 1024). Reports ms/call for both plus the
    speedup, so the threshold in
    apps/cell-image-search/embedder.py is justified (or falsified) by
    hardware data instead of a one-off sweep (VERDICT r4 weak #4)."""
    import jax
    import jax.numpy as jnp

    from bioengine_tpu.ops.pallas import flash_attention

    B, H, D = (1, 2, 64) if cpu else (8, 12, 64)
    seqs = (128,) if cpu else (1024, 2048)
    iters = 2 if cpu else 20
    out: dict = {"iters": iters, "shape_bhd": [B, H, D]}

    def xla_attn(q, k, v):
        s = jnp.einsum("bhnd,bhmd->bhnm", q, k) * (D**-0.5)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhnm,bhmd->bhnd", p, v)

    for n in seqs:
        key = jax.random.key(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, H, n, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, H, n, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, H, n, D), jnp.bfloat16)

        res = {}
        for name, attn in (("xla", xla_attn), ("pallas", flash_attention)):

            def chained(q, k, v, attn=attn):
                def step(carry, _):
                    o = attn(q + carry.astype(q.dtype), k, v)
                    return jnp.mean(o).astype(jnp.float32), None

                c, _ = jax.lax.scan(
                    step, jnp.float32(0.0), None, length=iters
                )
                return c

            best = _timed_scan(jax.jit(chained), q, k, v)
            res[f"{name}_ms_per_call"] = round(1000 * best / iters, 3)
        res["pallas_speedup"] = round(
            res["xla_ms_per_call"] / max(res["pallas_ms_per_call"], 1e-9), 2
        )
        out[f"n{n}"] = res
    return out


def _bench_search(cpu: bool) -> dict:
    """TPU index query latency vs the reference's FAISS-CPU baselines:
    FlatIP <5 ms at 100K vectors, IVFFlat <20 ms at 1M
    (ref apps/cell-image-search/README.md:132-133).

    Corpus = unit-norm gaussian blobs around cluster centers (real
    embedding corpora are clustered; on UNstructured random data the
    IVF probe selection hits unrepresentatively tiny lists). Two
    numbers per index: single-query p50 (includes the per-execution
    completion latency of the serving path) and batch-64 amortized per-query
    latency (the index's real throughput)."""
    import numpy as np

    mod = _load_index_module()
    rng = np.random.default_rng(0)
    n_flat, n_ivf = (2000, 10000) if cpu else (100_000, 200_000)
    dim = 768

    corpus_flat = _blob_corpus(rng, n_flat, dim, 64)
    corpus_ivf = _blob_corpus(rng, n_ivf, dim, 128 if not cpu else 16)
    out = {}
    for label, index, corpus in (
        ("flat_100k", mod.FlatIPIndex(corpus_flat), corpus_flat),
        ("ivfflat_200k", mod.IVFFlatIndex.build(
            corpus_ivf,
            nlist=128 if not cpu else 16,
            n_init=1,  # build cost is not the metric; query latency is
        ), corpus_ivf),
    ):
        out[label] = _time_index(index, corpus, rng, dim)
    return out


def _load_index_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "cis_index",
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "apps", "cell-image-search", "index.py",
        ),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _blob_corpus(rng, n, dim, n_centers):
    import numpy as np

    centers = rng.standard_normal((n_centers, dim)).astype(np.float32)
    pts = centers[rng.integers(0, n_centers, n)] + 0.3 * (
        rng.standard_normal((n, dim)).astype(np.float32)
    )
    return pts / np.linalg.norm(pts, axis=1, keepdims=True)


def _time_index(index, sample, rng, dim, n_single=20, n_batch=5) -> dict:
    """p50/best single-query + batch-64 amortized latency; queries drawn
    near corpus points for realistic probe selectivity. Every timed
    single query is DISTINCT — repeating one query would measure a
    cache-warm rescan of the same probed lists and flatter the p50."""
    import numpy as np

    qs = sample[rng.integers(0, len(sample), n_single)] + 0.05 * (
        rng.standard_normal((n_single, dim)).astype(np.float32)
    )
    qb = sample[rng.integers(0, len(sample), 64)] + 0.05 * (
        rng.standard_normal((64, dim)).astype(np.float32)
    )
    index.search(qs[:1], 10)  # warmup: device upload + compile
    index.search(qb, 10)
    singles, batches = [], []
    for i in range(n_single):
        t0 = time.perf_counter()
        index.search(qs[i : i + 1], 10)
        singles.append(time.perf_counter() - t0)
    for _ in range(n_batch):
        t0 = time.perf_counter()
        index.search(qb, 10)
        batches.append(time.perf_counter() - t0)
    singles.sort()
    batches.sort()
    return {
        "n_vectors": index.ntotal,
        "p50_ms": round(1000 * singles[len(singles) // 2], 3),
        "best_ms": round(1000 * singles[0], 3),
        "batch64_per_query_ms": round(
            1000 * batches[len(batches) // 2] / 64, 4
        ),
    }


def _lloyd(x, k, iters, rng):
    """Plain-numpy Lloyd k-means (random init). sklearn's MiniBatchKMeans
    at nlist=1024 on 100K x 768 measured 141 s — its per-iteration
    bookkeeping dominates; BLAS matmul assignment + bincount means run
    the same training in ~10 s, and codebook *quality* beyond a few
    Lloyd rounds is irrelevant to a latency benchmark."""
    import numpy as np

    c = x[rng.choice(len(x), size=k, replace=False)].astype(np.float32)
    for _ in range(iters):
        a = np.argmax(2.0 * (x @ c.T) - (c * c).sum(1), axis=1)
        sums = np.zeros_like(c)
        np.add.at(sums, a, x)
        cnt = np.bincount(a, minlength=k).astype(np.float32)
        nz = cnt > 0
        c[nz] = sums[nz] / cnt[nz, None]
    return c


def _bench_ivfpq(cpu: bool) -> dict:
    """IVFPQ ADC search latency at 1M x 768 — the index class that
    matters at the reference's 58M headline (<80 ms FAISS-CPU,
    ref apps/cell-image-search/README.md:134,232). Honest labels: the
    corpus is 1M (not 58M); coarse+PQ training and the first 100K
    encodes are REAL (the full memory-lean ingestion path — only one
    ~300 MB chunk of raw vectors ever exists, never the 3 GB corpus);
    the remaining rows are drawn from the real empirical
    (assignment, code) joint so list sizes and the ADC gather path are
    production-shaped. Recall is not the metric; latency is."""
    import numpy as np

    mod = _load_index_module()
    rng = np.random.default_rng(0)
    dim = 768
    if cpu:
        n_total, chunk, n_train, nlist = 20_000, 10_000, 5_000, 64
    else:
        # 25K training vectors: sub-codebook quality beyond a few Lloyd
        # rounds doesn't move LATENCY, and halving the train set cuts
        # ~30 s off the stage so the full default stage set fits the
        # driver deadline more often
        n_total, chunk, n_train, nlist = 1_000_000, 100_000, 25_000, 1024
    M, dsub = mod.IVFPQIndex.M, dim // mod.IVFPQIndex.M

    t0 = time.perf_counter()
    first = _blob_corpus(rng, chunk, dim, 256 if not cpu else 16)
    train = first[:n_train]
    centroids = _lloyd(train, nlist, iters=5, rng=rng)
    cnorm2 = (centroids**2).sum(1)

    def assign(x):  # exact nearest centroid via one matmul (unit-norm x)
        return np.argmax(2.0 * (x @ centroids.T) - cnorm2, axis=1)

    resid_train = (train - centroids[assign(train)]).reshape(
        n_train, M, dsub
    )
    # all M sub-codebooks trained together: (N, M, dsub) vs (M, 256, dsub)
    codebooks = np.stack(
        [
            _lloyd(resid_train[:, m], min(256, n_train), 5, rng)
            for m in range(M)
        ]
    )
    if codebooks.shape[1] < 256:  # cpu tiny mode
        codebooks = np.pad(
            codebooks, ((0, 0), (0, 256 - codebooks.shape[1]), (0, 0))
        )
    train_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    cb_norm2 = (codebooks**2).sum(2)  # (M, 256)
    # REAL encode of the first chunk (the full ingestion path: coarse
    # assign + per-subspace ADC argmin)...
    a_real = assign(first)
    r = np.ascontiguousarray(
        (first - centroids[a_real])
        .reshape(len(first), M, dsub)
        .transpose(1, 0, 2)
    )
    codes_real = np.empty((len(first), M), np.uint8)
    for m in range(M):
        # argmin ||s - c||^2 = argmax 2 s.c - ||c||^2
        codes_real[:, m] = np.argmax(
            2.0 * (r[m] @ codebooks[m].T) - cb_norm2[m], axis=1
        ).astype(np.uint8)
    # ...then the remaining corpus is drawn ROW-WISE from the real
    # empirical joint distribution (assignment, code) — preserving list
    # sizes and code-list correlation, which with nlist/nprobe are what
    # search latency depends on; the ADC gather path scans synthetic
    # codes exactly like real ones. Encoding all 1M for real costs
    # ~210 s of thin single-core GEMMs for zero latency fidelity gain;
    # the corpus_note labels this honestly.
    n_syn = n_total - len(first)
    pick = rng.integers(0, len(first), n_syn)
    codes = np.concatenate([codes_real, codes_real[pick]])
    assigns = np.concatenate([a_real, a_real[pick]]).astype(np.int32)
    order = np.argsort(assigns, kind="stable")
    sorted_a = assigns[order]
    starts = np.searchsorted(sorted_a, np.arange(nlist))
    ends = np.searchsorted(sorted_a, np.arange(nlist), side="right")
    index = mod.IVFPQIndex(
        centroids,
        codebooks,
        codes[order],
        order.astype(np.int64),
        np.stack([starts, ends], axis=1),
        nprobe=32,
    )
    encode_s = time.perf_counter() - t0

    sample = first[:64]
    timing = _time_index(index, sample, rng, dim, n_single=10, n_batch=3)

    # recall@10 vs EXACT search, on the real-encoded subset only
    # (VERDICT r5 item 5): synthetic rows share base vectors with real
    # ones, so quality is only measurable where both the codes and the
    # ground truth are real. The sweep justifies (or falsifies)
    # nprobe=32 with data instead of convention.
    order_r = np.argsort(a_real, kind="stable")
    sorted_ar = a_real[order_r]
    bounds_r = np.stack(
        [
            np.searchsorted(sorted_ar, np.arange(nlist)),
            np.searchsorted(sorted_ar, np.arange(nlist), side="right"),
        ],
        axis=1,
    )
    recall_index = mod.IVFPQIndex(
        centroids,
        codebooks,
        codes_real[order_r],
        order_r.astype(np.int64),
        bounds_r,
        nprobe=32,
    )
    n_q = 8 if cpu else 64
    qs_r = first[rng.integers(0, len(first), n_q)] + 0.05 * (
        rng.standard_normal((n_q, dim)).astype(np.float32)
    )
    exact10 = np.argsort(-(qs_r @ first.T), axis=1)[:, :10]
    recall = {}
    for nprobe in (8, 16, 32, 64):
        if nprobe > nlist:
            continue
        recall_index.nprobe = nprobe
        _, approx10 = recall_index.search(qs_r, 10)
        hits = sum(
            len(set(approx10[i].tolist()) & set(exact10[i].tolist()))
            for i in range(n_q)
        )
        recall[f"nprobe_{nprobe}"] = round(hits / (10 * n_q), 3)

    return {
        **timing,
        "nlist": nlist,
        "nprobe": 32,
        "pq": f"m={M}x8bit",
        "train_seconds": round(train_s, 1),
        "encode_seconds": round(encode_s, 1),
        "recall_at_10": recall,
        "recall_note": f"vs exact IP search over the {len(first)} "
        f"real-encoded vectors, {n_q} held-out-style queries",
        "corpus_note": f"{n_total} vectors (58M FAISS baseline is "
        f"{58_000_000 // n_total}x larger): {len(first)} real-encoded + "
        f"{n_syn} drawn from the trained empirical (assignment, code) "
        "joint — latency-representative ADC path",
    }


def _bench_pqflat(cpu: bool) -> dict:
    """Device-resident PQ exact scan (PQFlatTPU) at 1M codes: the
    HBM-resident alternative to CPU IVFPQ — no probe selection, no
    recall loss, the full 58M-scale corpus fits one chip
    (apps/cell-image-search/index.py PQFlatIndex). Codes here are
    random uint8 (the gather+accumulate+top_k cost is independent of
    code values); the per-query ADC tables are real."""
    import numpy as np

    mod = _load_index_module()
    rng = np.random.default_rng(0)
    n = 50_000 if cpu else 1_000_000
    dim = 768
    codebooks = rng.standard_normal((96, 256, 8)).astype(np.float32)
    codes = rng.integers(0, 256, (n, 96), dtype=np.uint8)
    index = mod.PQFlatIndex(codebooks, codes)
    sample = rng.standard_normal((64, dim)).astype(np.float32)
    sample /= np.linalg.norm(sample, axis=1, keepdims=True)
    timing = _time_index(index, sample, rng, dim, n_single=10, n_batch=3)
    return {
        **timing,
        # codes stay uint8 on device (1 byte/code), so host nbytes IS
        # the HBM residency
        "resident_bytes": int(index._codes_dev.nbytes),
        "corpus_note": f"{n} random codes, exact full scan on device "
        "(no IVF probes); 58M would be ~5.5 GB HBM-resident",
    }


def _bench_rpc_transport(cpu: bool) -> dict:
    """RPC data-plane round-trip throughput, three ways: the legacy
    single-blob encoder (every array copied 3+ times per direction),
    zero-copy out-of-band frames (one copy per direction, chunked
    multi-frame above the 32 MB frame limit), and the same-host shm
    fast path (one copy total — the store put; the receiver maps the
    segment). One real websocket client against a real server in this
    process; the echo service returns the array unchanged, so each
    round trip moves the payload across the wire twice. The ``big``
    leg round-trips a >256 MB array through chunked frames — the size
    the old twin ``max_msg_size`` caps made impossible.

    Env: BENCH_RPC_SIZES_MB / BENCH_RPC_BIG_MB (0 disables the big
    leg) / BENCH_RPC_REPS."""
    import asyncio

    import numpy as np

    from bioengine_tpu.native.store import open_store
    from bioengine_tpu.rpc.client import connect_to_server
    from bioengine_tpu.rpc.server import RpcServer

    default_sizes = "1,64" if cpu else "1,64,256"
    sizes_mb = [
        float(s)
        for s in os.environ.get("BENCH_RPC_SIZES_MB", default_sizes).split(",")
        if s.strip()
    ]
    big_mb = float(os.environ.get("BENCH_RPC_BIG_MB", "272"))
    reps_env = os.environ.get("BENCH_RPC_REPS")

    def reps_for(mb: float) -> int:
        if reps_env:
            return int(reps_env)
        return 10 if mb <= 4 else (5 if mb <= 64 else 2)

    async def time_path(conn, server, arr: np.ndarray) -> dict:
        reps = reps_for(arr.nbytes / 1e6)
        out = await conn.call("bioengine/echo", "echo", arr)  # warmup
        if not np.array_equal(np.asarray(out), arr):
            raise RuntimeError("echo corrupted the payload")
        del out
        # data-plane cost measured on the SAME traffic via RpcStats:
        # client encode+decode plus server encode+decode per round
        # trip. The e2e wall number additionally carries the websocket
        # stack (masking, frame parse, socket copies) — a fixed toll
        # both codecs pay, and on slow virtualized network stacks the
        # dominant one, so both views are reported.
        def codec_seconds() -> float:
            return (
                conn.codec.stats.encode_seconds
                + conn.codec.stats.decode_seconds
                + server.stats.encode_seconds
                + server.stats.decode_seconds
            )
        codec0 = codec_seconds()
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = await conn.call("bioengine/echo", "echo", arr)
            times.append(time.perf_counter() - t0)
            del out                      # free shm pins before next rep
            conn.codec.drain_pins()
        codec_rt = (codec_seconds() - codec0) / reps
        times.sort()
        p50 = times[len(times) // 2]
        return {
            "p50_ms": round(1000 * p50, 2),
            "p95_ms": round(
                1000 * times[min(int(len(times) * 0.95), len(times) - 1)], 2
            ),
            "mb_per_sec": round(2 * arr.nbytes / 1e6 / p50, 1),
            "codec_ms_per_roundtrip": round(1000 * codec_rt, 2),
            "codec_mb_per_sec": round(
                2 * arr.nbytes / 1e6 / max(codec_rt, 1e-9), 1
            ),
            "reps": reps,
        }

    async def run_path(name: str, store) -> dict:
        server = RpcServer(shm_store=store)
        await server.start()
        server.register_local_service({"id": "echo", "echo": lambda a: a})
        conn = await connect_to_server(
            {
                "server_url": f"http://127.0.0.1:{server.port}",
                "protocols": [] if name == "legacy" else None,
                "shm_store": store,
            }
        )
        res: dict = {}
        try:
            if name == "shm" and conn.codec.shm_store is None:
                return {"skipped": "shm negotiation failed"}
            for mb in sizes_mb:
                n = int(mb * 1024 * 1024 // 4)
                arr = np.arange(n, dtype=np.float32)
                if (
                    name == "legacy"
                    and arr.nbytes + 65536 > conn.codec.config.max_msg_size
                ):
                    # the legacy encoder still lives under the old
                    # single-message ceiling — exactly the cap the
                    # chunked oob path removes
                    res[f"mb{mb:g}"] = {"skipped": "exceeds legacy frame cap"}
                    continue
                res[f"mb{mb:g}"] = await time_path(conn, server, arr)
            if name == "oob" and big_mb > 0:
                arr = np.arange(
                    int(big_mb * 1024 * 1024 // 4), dtype=np.float32
                )
                chunked_before = conn.codec.stats.chunked_msgs_out
                t0 = time.perf_counter()
                out = await conn.call("bioengine/echo", "echo", arr)
                dt = time.perf_counter() - t0
                ok = np.array_equal(np.asarray(out), arr)
                res["big_roundtrip"] = {
                    "mb": big_mb,
                    "ok": bool(ok),
                    "seconds": round(dt, 2),
                    "chunked": conn.codec.stats.chunked_msgs_out
                    > chunked_before,
                }
            res["transport_stats"] = conn.codec.stats.as_dict()
        finally:
            await conn.disconnect()
            await server.stop()
        return res

    async def run() -> dict:
        # dedicated bench segment so real deployments' stores are
        # untouched; LocalObjectStore fallback still exercises the path
        # in-process when no native toolchain exists
        cap = int(max(sizes_mb) * 4 + 64) * 1024 * 1024
        store = open_store("bioengine-rpc-bench", capacity=cap, create=True)
        try:
            paths = {
                "legacy": await run_path("legacy", None),
                "oob": await run_path("oob", None),
                "shm": await run_path("shm", store),
            }
        finally:
            store.destroy()
        out: dict = {"sizes_mb": sizes_mb, "paths": paths}
        # headline ratios at the largest size present on both paths:
        # e2e wall (includes the websocket stack — both codecs pay it
        # identically) and the data-plane round trip (encode+decode,
        # measured on the same live traffic — what the zero-copy
        # rebuild actually changes)
        for mb in sorted(sizes_mb, reverse=True):
            key = f"mb{mb:g}"
            leg = paths["legacy"].get(key, {})
            oob = paths["oob"].get(key, {})
            if "p50_ms" in leg and "p50_ms" in oob:
                out["speedup_oob_vs_legacy"] = round(
                    leg["p50_ms"] / oob["p50_ms"], 2
                )
                out["codec_roundtrip_speedup_oob_vs_legacy"] = round(
                    leg["codec_ms_per_roundtrip"]
                    / max(oob["codec_ms_per_roundtrip"], 1e-9),
                    2,
                )
                out["speedup_at_mb"] = mb
                shm = paths["shm"].get(key, {})
                if "p50_ms" in shm:
                    out["speedup_shm_vs_legacy"] = round(
                        leg["p50_ms"] / shm["p50_ms"], 2
                    )
                break
        big = paths.get("oob", {}).get("big_roundtrip")
        if big is not None:
            out["big_roundtrip"] = big
        out["note"] = (
            "codec_* = data-plane encode+decode measured on the live "
            "round trips (what the zero-copy rebuild changes); e2e "
            "wall additionally pays the websocket stack (mask + frame "
            "parse + socket copies), identical for every codec and "
            "dominant on slow virtualized loopback"
        )
        return out

    return asyncio.run(run())


def _bench_request_overhead(cpu: bool) -> dict:  # noqa: ARG001 — pure host path
    """Per-request microsecond budget on the SMALL-request hot path.

    Three legs in one interpreter against a trivial echo/add service
    over the real websocket stack: ``baseline`` is yesterday's stack
    end to end (oob1+trace1 wire, no fast frames, per-call supervised
    task dispatch, pre-fast1 request bookkeeping via compat_pre_fast1,
    TCP); ``fast_tcp`` isolates the codec + inline-
    dispatch de-tax on the identical wire; ``fast`` adds the same-host
    unix-socket listener — the full optimized path a co-located worker
    gets. Legs run INTERLEAVED in rounds and each reports its best
    round, so whole-machine drift (noisy CI neighbors) cancels out of
    the ratios. Each leg reports the uncontended path (one request in
    flight at a time — the acceptance gate: fast must be >=2x baseline
    req/s) and a pipelined-concurrency path (C callers multiplexed on
    one connection).

    The decomposition buckets attribute the baseline per-request budget:
    ``codec`` is measured on the live traffic via RpcStats (client +
    server encode+decode); ``tracing_ctx`` / ``scoring`` / ``scheduler``
    / ``asyncio_hop`` are targeted perf_counter_ns micro-probes of the
    exact operations the request path runs per call; ``wire_residual``
    is what remains of the uncontended p50 — the aiohttp frame machinery
    and event-loop wakeups that every codec pays.

    Env: BENCH_REQ_ROUNDS / BENCH_REQ_N / BENCH_REQ_CALLERS /
    BENCH_REQ_PER_CALLER."""
    import asyncio

    from bioengine_tpu.rpc import protocol
    from bioengine_tpu.rpc.client import connect_to_server
    from bioengine_tpu.rpc.server import RpcServer
    from bioengine_tpu.serving.scheduler import HeuristicCostModel, batch_signature
    from bioengine_tpu.utils import tracing

    rounds = int(os.environ.get("BENCH_REQ_ROUNDS", "9"))
    n_serial = int(os.environ.get("BENCH_REQ_N", "400"))
    callers = int(os.environ.get("BENCH_REQ_CALLERS", "32"))
    per_caller = int(os.environ.get("BENCH_REQ_PER_CALLER", "40"))

    async def setup_leg(fast: bool, uds: bool = False) -> dict:
        server = RpcServer(
            shm_store=None,
            inline_dispatch=fast,
            uds_path="/tmp/bioengine-bench-req.sock" if uds else None,
        )
        await server.start()
        server.register_local_service(
            {"id": "echo", "echo": lambda x: x, "add": lambda a, b: a + b}
        )
        conn = await connect_to_server(
            {
                "server_url": (
                    f"unix://{server.uds_path}"
                    if uds
                    else f"http://127.0.0.1:{server.port}"
                ),
                # baseline = the pre-fast1 stack end to end: oob1+trace1
                # declared (yesterday's wire bytes) AND the pre-fast1
                # per-request bookkeeping (uuid call ids, wait_for
                # timeout chain) via compat_pre_fast1 — this PR also
                # de-taxed the shared request path, so without the
                # compat flag the baseline leg would silently inherit
                # those wins and under-state the pre-PR cost
                "protocols": (
                    None
                    if fast
                    else [protocol.PROTO_OOB1, protocol.PROTO_TRACE1]
                ),
                "compat_pre_fast1": not fast,
            }
        )
        return {
            "server": server,
            "conn": conn,
            "transport": "uds" if uds else "tcp",
        }

    def codec_seconds(leg: dict) -> float:
        return (
            leg["conn"].codec.stats.encode_seconds
            + leg["conn"].codec.stats.decode_seconds
            + leg["server"].stats.encode_seconds
            + leg["server"].stats.decode_seconds
        )

    async def serial_round(conn) -> dict:
        lat_us: list = []
        t_start = time.perf_counter()
        for _ in range(n_serial):
            t0 = time.perf_counter_ns()
            await conn.call("bioengine/echo", "echo", "ping")
            lat_us.append((time.perf_counter_ns() - t0) / 1000.0)
        wall = time.perf_counter() - t_start
        lat_us.sort()
        return {
            "req_per_sec": n_serial / wall,
            "p50_us": lat_us[len(lat_us) // 2],
            "p95_us": lat_us[min(int(len(lat_us) * 0.95), len(lat_us) - 1)],
        }

    async def concurrent_round(conn) -> float:
        async def caller() -> None:
            for _ in range(per_caller):
                await conn.call("bioengine/echo", "add", 1, 2)

        t0 = time.perf_counter()
        await asyncio.gather(*[caller() for _ in range(callers)])
        return callers * per_caller / (time.perf_counter() - t0)

    def probe_us(fn, n: int = 20000) -> float:
        fn()  # warm
        t0 = time.perf_counter_ns()
        for _ in range(n):
            fn()
        return (time.perf_counter_ns() - t0) / n / 1000.0

    async def probe_hop_us(n: int = 5000) -> float:
        # the per-call supervised-task tax inline dispatch removes:
        # create_task + loop schedule + run + completion wakeup
        async def nop() -> None:
            pass

        loop = asyncio.get_running_loop()
        await loop.create_task(nop())  # warm
        t0 = time.perf_counter_ns()
        for _ in range(n):
            await loop.create_task(nop())
        return (time.perf_counter_ns() - t0) / n / 1000.0

    scorer = HeuristicCostModel()
    features = {
        "load": 0.4,
        "queued": 1,
        "max_ongoing": 8,
        "breaker_failures": 0,
        "signature_affinity": 1.0,
        "avoided": False,
        "probation": False,
        "group_size": 1,
    }

    async def run() -> dict:
        legs = {
            "baseline": await setup_leg(fast=False),
            "fast_tcp": await setup_leg(fast=True),
            "fast": await setup_leg(fast=True, uds=True),
        }
        try:
            for leg in legs.values():  # warm paths (caches, ws buffers)
                for _ in range(100):
                    await leg["conn"].call("bioengine/echo", "add", 1, 2)
            serial: dict = {k: [] for k in legs}
            conc: dict = {k: [] for k in legs}
            codec0 = {k: codec_seconds(leg) for k, leg in legs.items()}
            order = list(legs.items())
            for i in range(rounds):
                # interleave legs within each round so machine-wide
                # noise hits every leg of a round equally, and flip the
                # order on alternate rounds so weather that shifts
                # MID-round doesn't systematically favor one position
                seq = order if i % 2 == 0 else order[::-1]
                for k, leg in seq:
                    serial[k].append(await serial_round(leg["conn"]))
                for k, leg in seq:
                    conc[k].append(await concurrent_round(leg["conn"]))
            out_legs: dict = {}
            for k, leg in legs.items():
                total_reqs = rounds * (n_serial + callers * per_caller)
                codec_us = (
                    (codec_seconds(leg) - codec0[k]) / total_reqs * 1e6
                )
                best = max(serial[k], key=lambda r: r["req_per_sec"])
                med = sorted(
                    serial[k], key=lambda r: r["req_per_sec"]
                )[len(serial[k]) // 2]
                st = leg["conn"].codec.stats.as_dict()
                out_legs[k] = {
                    "transport": leg["transport"],
                    "uncontended": {
                        "req_per_sec": round(best["req_per_sec"], 1),
                        "p50_us": round(best["p50_us"], 1),
                        "p95_us": round(best["p95_us"], 1),
                        "median_req_per_sec": round(med["req_per_sec"], 1),
                        "n": n_serial,
                        "rounds": rounds,
                    },
                    "concurrent": {
                        "req_per_sec": round(max(conc[k]), 1),
                        "median_req_per_sec": round(
                            sorted(conc[k])[len(conc[k]) // 2], 1
                        ),
                        "callers": callers,
                        "n": callers * per_caller,
                    },
                    "codec_us_per_req": round(codec_us, 2),
                    "fast_frames": bool(leg["conn"].codec.fast),
                    "small_frames_out": st["small_frames_out"],
                    "fast_frame_hit_rate": st["fast_frame_hit_rate"],
                }
        finally:
            for leg in legs.values():
                await leg["conn"].disconnect()
                await leg["server"].stop()

        baseline = out_legs["baseline"]
        decomposition = {
            "codec_us": baseline["codec_us_per_req"],
            "tracing_ctx_us": round(
                probe_us(
                    lambda: (tracing.current_trace_and_span(), tracing.sampled())
                ),
                3,
            ),
            "scheduler_us": round(
                probe_us(
                    lambda: batch_signature("echo", (1, 2.0), {"scale": 2.0})
                ),
                3,
            ),
            "scoring_us": round(probe_us(lambda: scorer.score(features)), 3),
            "asyncio_hop_us": round(await probe_hop_us(), 3),
        }
        accounted = sum(decomposition.values())
        decomposition["wire_residual_us"] = round(
            max(baseline["uncontended"]["p50_us"] - accounted, 0.0), 1
        )
        # PAIRED ratio estimator: the legs interleave inside each
        # round, so the ratio computed within one round sees the same
        # machine weather on both sides; the median over rounds then
        # rejects the outlier rounds entirely. A best-of-rounds or
        # grand-mean ratio is badly biased by one lucky/unlucky window
        # landing on a single leg.
        def paired_speedup(series: dict) -> float:
            ratios = sorted(
                f / max(b, 1e-9)
                for f, b in zip(series["fast"], series["baseline"])
            )
            return round(ratios[len(ratios) // 2], 2)

        serial_rps = {
            k: [r["req_per_sec"] for r in v] for k, v in serial.items()
        }
        return {
            "legs": out_legs,
            "decomposition_us": decomposition,
            "uncontended_speedup": paired_speedup(serial_rps),
            "concurrent_speedup": paired_speedup(conc),
            "threshold_bytes": protocol.FAST_THRESHOLD_DEFAULT,
            "note": (
                "baseline leg reproduces the pre-PR stack end to end "
                "(legacy wire config + compat_pre_fast1 request "
                "bookkeeping + task-per-call dispatch) in the same "
                "interpreter as the fast legs. "
                "legs interleave per round; speedups are the MEDIAN of "
                "per-round paired fast/baseline ratios (same-round "
                "pairing cancels machine drift); each leg also reports "
                "its best and median round. "
                "decomposition buckets attribute the BASELINE budget: "
                "codec from live RpcStats on the measured traffic; "
                "tracing/scheduler/scoring/asyncio-hop from targeted "
                "perf_counter_ns probes of the per-request operations; "
                "wire_residual = uncontended p50 minus accounted buckets "
                "(aiohttp frame machinery + loop wakeups)"
            ),
        }

    return asyncio.run(run())


def _bench_observability(cpu: bool) -> dict:  # noqa: ARG001 — pure host path
    """Per-request cost of the observability substrate on the serve
    hot path. Four legs over the same live controller + replica
    (DeploymentHandle.call -> route -> semaphore -> execute, the path
    every request pays regardless of model):

    - ``disabled``  — BIOENGINE_TRACING=0, BIOENGINE_METRICS=0,
      BIOENGINE_FLIGHT=0 (the PR-5 hot path: no context minted, no
      histogram observed, no flight ring)
    - ``unsampled`` — tracing on, head sampling 0.0, metrics on,
      flight OFF (the PR-6 production default — the baseline the
      flight leg is judged against)
    - ``flight``    — unsampled + the always-on flight recorder (the
      PR-7 production default; the acceptance gate reads
      ``overhead_flight_vs_unsampled_pct`` < 1 — the ring writes only
      on failure/transition edges, so the per-request cost is the
      enabled-checks)
    - ``telem``     — flight + the telemetry history pipeline running
      HOT: the controller's registry-delta sampler ticking plus a
      simulated worker-host push ingested every interval
      (BENCH_TELEM_INTERVAL, default 0.25 s — 40x the production 10 s
      cadence). The acceptance gate reads
      ``overhead_telem_vs_flight_pct`` < 1: history is scrape-time
      work off the request path, so the per-request cost must be
      event-loop noise only.
    - ``sampled``   — sampling 1.0 (the ceiling: full span recording
      + chip-seconds stamped on the trace root)

    Legs interleave round-robin so clock drift and CPU contention hit
    all of them equally; per-leg p50 comes from the pooled per-request
    times. The acceptance gate reads ``overhead_unsampled_pct``.
    """
    import asyncio

    import numpy as np

    from bioengine_tpu.cluster.state import ClusterState
    from bioengine_tpu.serving import DeploymentSpec, ServeController
    from bioengine_tpu.utils import flight, metrics, tracing

    rounds = int(os.environ.get("BENCH_OBS_ROUNDS", "5"))
    per_round = int(os.environ.get("BENCH_OBS_REQUESTS", "60"))

    class ObsApp:
        """~1-2 ms of real numpy work per request — the floor of a real
        serve request (LATENCY_BUCKETS_S starts at 1 ms; production
        calls run models). The overhead ratio is meaningless against an
        empty function, so ``overhead_abs_us`` (independent of the
        workload) is reported alongside it."""

        def __init__(self):
            self._x = np.random.default_rng(0).standard_normal(
                (384, 384)
            ).astype(np.float32)

        async def infer(self):
            return float((self._x @ self._x).sum())

    legs = {
        "disabled": {
            "BIOENGINE_TRACING": "0",
            "BIOENGINE_METRICS": "0",
            "BIOENGINE_FLIGHT": "0",
        },
        "unsampled": {
            "BIOENGINE_TRACE_SAMPLE": "0.0",
            "BIOENGINE_FLIGHT": "0",
        },
        "flight": {"BIOENGINE_TRACE_SAMPLE": "0.0"},
        "telem": {"BIOENGINE_TRACE_SAMPLE": "0.0"},
        "sampled": {"BIOENGINE_TRACE_SAMPLE": "1.0"},
    }
    knobs = [
        "BIOENGINE_TRACING",
        "BIOENGINE_METRICS",
        "BIOENGINE_TRACE_SAMPLE",
        "BIOENGINE_FLIGHT",
    ]

    def _apply(env: dict) -> None:
        for k in knobs:
            os.environ.pop(k, None)
        os.environ.update(env)
        tracing.reset_env_cache()
        metrics.reset_env_cache()
        flight.reset_env_cache()

    async def run() -> dict:
        controller = ServeController(ClusterState(), health_check_period=3600)
        saved = {k: os.environ.get(k) for k in knobs}
        try:
            await controller.deploy(
                "obs-bench",
                [DeploymentSpec(name="entry", instance_factory=ObsApp)],
            )
            handle = controller.get_handle("obs-bench")
            for _ in range(per_round):  # warmup
                await handle.call("infer")

            from bioengine_tpu.utils import telemetry as _telemetry

            telem_interval = float(
                os.environ.get("BENCH_TELEM_INTERVAL", "0.25")
            )
            host_sampler = _telemetry.RegistrySampler()
            host_sampler.source_id = "bench-host"  # never deduped as local

            async def telem_load(stop: asyncio.Event) -> None:
                # the telemetry pipeline under push load: the
                # controller's own tick plus a worker-host-shaped push
                # ingested each interval — everything the telem1 plane
                # does except the websocket hop (measured by the
                # rpc_transport stage; here the question is what
                # HISTORY costs the serve hot path)
                host_sampler.sample()
                while not stop.is_set():
                    controller.telemetry_tick()
                    snap = host_sampler.sample()
                    if snap:
                        controller.telemetry.ingest(
                            snap, host_id="bench-host"
                        )
                    try:
                        await asyncio.wait_for(stop.wait(), telem_interval)
                    except asyncio.TimeoutError:
                        pass

            times: dict[str, list] = {name: [] for name in legs}
            for _ in range(rounds):
                for name, env in legs.items():
                    _apply(env)
                    telem_stop = asyncio.Event()
                    telem_task = (
                        asyncio.ensure_future(telem_load(telem_stop))
                        if name == "telem"
                        else None
                    )
                    try:
                        for _ in range(per_round):
                            t0 = time.perf_counter()
                            await handle.call("infer")
                            times[name].append(time.perf_counter() - t0)
                    finally:
                        if telem_task is not None:
                            telem_stop.set()
                            await telem_task
                    if name == "sampled":
                        tracing.clear_spans()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            tracing.reset_env_cache()
            metrics.reset_env_cache()
            flight.reset_env_cache()
            await controller.stop()

        def p50_us(vals: list) -> float:
            return round(1e6 * sorted(vals)[len(vals) // 2], 1)

        out: dict = {
            "requests_per_leg": rounds * per_round,
            "legs": {name: {"p50_us": p50_us(v)} for name, v in times.items()},
        }
        base = out["legs"]["disabled"]["p50_us"]
        for name in ("unsampled", "flight", "telem", "sampled"):
            leg = out["legs"][name]["p50_us"]
            out[f"overhead_{name}_pct"] = round(100.0 * (leg - base) / base, 2)
            out[f"overhead_{name}_abs_us"] = round(leg - base, 1)
        # the flight-recorder acceptance gate: the always-on ring vs
        # the PR-6 unsampled baseline (its own leg, flight off)
        unsampled = out["legs"]["unsampled"]["p50_us"]
        flight_leg = out["legs"]["flight"]["p50_us"]
        out["overhead_flight_vs_unsampled_pct"] = round(
            100.0 * (flight_leg - unsampled) / unsampled, 2
        )
        # the push-telemetry acceptance gate: history pipeline hot vs
        # the flight leg it rides on (gate < 1 on the driver run)
        telem_leg = out["legs"]["telem"]["p50_us"]
        out["overhead_telem_vs_flight_pct"] = round(
            100.0 * (telem_leg - flight_leg) / flight_leg, 2
        )
        out["telem_interval_s"] = telem_interval
        out["note"] = (
            "unsampled = PR-6 default (tracing on, 0% head sampling, "
            "metrics on, flight ring off); flight = that plus the "
            "always-on flight recorder (PR-7 default, gate: "
            "overhead_flight_vs_unsampled_pct < 1 — the ring only "
            "writes on failure/transition edges); telem = flight plus "
            "the telemetry history pipeline ticking at 40x production "
            "cadence (PR-10 default, gate: "
            "overhead_telem_vs_flight_pct < 1 — history is scrape-time "
            "work off the request path); overhead vs the fully-disabled "
            "PR-5 hot path must sit within measurement noise (<2%). "
            "abs_us is workload-independent — the per-request cost of "
            "the substrate itself"
        )
        return out

    return asyncio.run(run())


def _bench_scheduler(cpu: bool) -> dict:  # noqa: ARG001 — pure host path
    """Per-request router vs global scheduler on the SAME mixed-priority
    workload (bursty waves of interactive + bulk against N replicas of a
    batch-friendly deployment whose forward has fixed overhead + small
    per-item cost — the accelerator shape). Reports per leg: goodput
    (interactive completions inside the SLO plus bulk completions, per
    wall second), per-class p50/p99, interactive SLO attainment, and
    batch occupancy (the lever cross-replica coalescing moves). A third
    interleaved leg measures the UNCONTENDED single-request path both
    ways — the scheduler's inline fast path must sit within noise of
    the router (<2% acceptance gate on hardware; CI numbers are
    informational, the schema is the contract)."""
    import asyncio

    from bioengine_tpu.cluster.state import ClusterState
    from bioengine_tpu.serving import (
        ContinuousBatcher,
        DeploymentSpec,
        RequestOptions,
        SchedulingConfig,
        ServeController,
    )

    n_replicas = 2
    rounds = int(os.environ.get("BENCH_SCHED_ROUNDS", "2"))
    waves = int(os.environ.get("BENCH_SCHED_WAVES", "10"))
    wave_interactive = 4
    wave_bulk = 8
    slo_s = float(os.environ.get("BENCH_SCHED_SLO_S", "0.25"))
    solo = int(os.environ.get("BENCH_SCHED_SOLO", "40"))

    class BatchServeApp:
        """The forward costs base + per-item and the device runs ONE
        forward at a time (the accelerator reality a lock models):
        bigger batches amortize the base, so occupancy converts
        directly into goodput once the deployment is capacity-bound."""

        batch_sizes: list = []

        def __init__(self):
            self._batcher = None
            self._device = None

        async def async_init(self):
            self._device = asyncio.Lock()
            self._batcher = ContinuousBatcher(
                self._run, max_batch=16, max_wait_ms=4.0
            )

        async def _run(self, sig, payloads):
            BatchServeApp.batch_sizes.append(len(payloads))
            async with self._device:
                await asyncio.sleep(0.012 + 0.0002 * len(payloads))
            return list(payloads)

        async def infer(self, x=0):
            return await self._batcher.submit("b", x)

        async def close(self):
            if self._batcher is not None:
                await self._batcher.close()

    def quantile(vals, q):
        if not vals:
            return None
        s = sorted(vals)
        return s[min(int(len(s) * q), len(s) - 1)]

    async def make_controller(scheduled: bool, replicas: int):
        controller = ServeController(ClusterState(), health_check_period=3600)
        await controller.deploy(
            "sched-bench",
            [
                DeploymentSpec(
                    name="entry",
                    instance_factory=BatchServeApp,
                    num_replicas=replicas,
                    max_ongoing_requests=32,
                    autoscale=False,
                    scheduling=(
                        SchedulingConfig(max_batch=16, max_wait_ms=4.0)
                        if scheduled
                        else None
                    ),
                )
            ],
        )
        return controller

    async def run_leg(scheduled: bool) -> dict:
        controller = await make_controller(scheduled, n_replicas)
        handle = controller.get_handle("sched-bench")
        BatchServeApp.batch_sizes = []
        lat = {"interactive": [], "bulk": []}
        failed = [0]
        opts = {
            "interactive": RequestOptions(
                priority="interactive", idempotent=True
            ),
            "bulk": RequestOptions(priority="bulk", idempotent=True),
        }

        async def one(cls):
            t0 = time.perf_counter()
            try:
                await handle.call("infer", x=0, options=opts[cls])
            except Exception:  # noqa: BLE001 — shed/failed counts against goodput
                failed[0] += 1
                return
            lat[cls].append(time.perf_counter() - t0)

        try:
            t_start = time.perf_counter()
            tasks = []
            for _ in range(waves):
                tasks.extend(
                    asyncio.create_task(one("interactive"))
                    for _ in range(wave_interactive)
                )
                tasks.extend(
                    asyncio.create_task(one("bulk"))
                    for _ in range(wave_bulk)
                )
                # arrivals outpace one-forward-at-a-time capacity: the
                # legs are compared under backlog, where routing and
                # occupancy decisions actually matter
                await asyncio.sleep(0.004)
            await asyncio.gather(*tasks)
            wall = time.perf_counter() - t_start
        finally:
            await controller.stop()
        inter_met = sum(1 for v in lat["interactive"] if v <= slo_s)
        good = inter_met + len(lat["bulk"])
        sizes = BatchServeApp.batch_sizes
        return {
            "wall_s": round(wall, 3),
            "goodput_rps": round(good / wall, 1),
            "failed": failed[0],
            "interactive_p50_ms": round(
                1000 * (quantile(lat["interactive"], 0.5) or 0), 2
            ),
            "interactive_p99_ms": round(
                1000 * (quantile(lat["interactive"], 0.99) or 0), 2
            ),
            "interactive_slo_met_pct": round(
                100.0 * inter_met / max(1, len(lat["interactive"])), 1
            ),
            "bulk_p50_ms": round(1000 * (quantile(lat["bulk"], 0.5) or 0), 2),
            "bulk_p99_ms": round(1000 * (quantile(lat["bulk"], 0.99) or 0), 2),
            "batch_occupancy": round(
                sum(sizes) / max(1, len(sizes)), 2
            ),
            "forwards": len(sizes),
        }

    async def run_uncontended() -> dict:
        """Sequential lone requests, the two paths interleaved so clock
        drift and CPU contention hit both equally."""
        router = await make_controller(False, 1)
        sched = await make_controller(True, 1)
        times = {"router": [], "scheduler": []}
        try:
            h_router = router.get_handle("sched-bench")
            h_sched = sched.get_handle("sched-bench")
            for _ in range(5):  # warmup both paths
                await h_router.call("infer", x=0)
                await h_sched.call("infer", x=0)
            for _ in range(solo):
                for name, h in (("router", h_router), ("scheduler", h_sched)):
                    t0 = time.perf_counter()
                    await h.call("infer", x=0)
                    times[name].append(time.perf_counter() - t0)
        finally:
            await router.stop()
            await sched.stop()
        r = 1e6 * quantile(times["router"], 0.5)
        s = 1e6 * quantile(times["scheduler"], 0.5)
        return {
            "requests_per_leg": solo,
            "router_p50_us": round(r, 1),
            "scheduler_p50_us": round(s, 1),
            "overhead_scheduler_pct": round(100.0 * (s - r) / r, 2),
            "overhead_scheduler_abs_us": round(s - r, 1),
        }

    async def run() -> dict:
        legs = {"router": [], "scheduler": []}
        for _ in range(rounds):  # interleaved rounds, like obs overhead
            legs["router"].append(await run_leg(False))
            legs["scheduler"].append(await run_leg(True))

        def best(leg_rounds):
            return max(leg_rounds, key=lambda d: d["goodput_rps"])

        router, scheduler = best(legs["router"]), best(legs["scheduler"])
        out = {
            "workload": {
                "replicas": n_replicas,
                "waves": waves,
                "wave_interactive": wave_interactive,
                "wave_bulk": wave_bulk,
                "interactive_slo_ms": round(slo_s * 1000, 1),
                "rounds": rounds,
            },
            "legs": {"router": router, "scheduler": scheduler},
            "goodput_speedup": round(
                scheduler["goodput_rps"] / max(router["goodput_rps"], 1e-9),
                3,
            ),
            "occupancy_gain": round(
                scheduler["batch_occupancy"]
                / max(router["batch_occupancy"], 1e-9),
                3,
            ),
            "uncontended": await run_uncontended(),
            "note": (
                "router = per-request least-loaded routing (PR 8 "
                "baseline); scheduler = global scheduler with "
                "cross-replica batching + weighted-fair priority "
                "queues on the SAME workload. goodput counts "
                "interactive completions inside the SLO plus all bulk "
                "completions per wall second; batch_occupancy is "
                "requests per engine forward. uncontended compares the "
                "lone-request path (scheduler fast path vs router) — "
                "the <2% overhead gate; sandbox numbers are "
                "core-bound, the TPU round supplies the headline."
            ),
        }
        return out

    return asyncio.run(run())


def _bench_gray_failure(cpu: bool) -> dict:  # noqa: ARG001 — pure host path
    """Gray-failure defense proof on the scenario engine's acceptance
    scenario: the SAME seeded slow-ramp incident (one replica degrades
    to ~30x service time while still passing health checks) run twice —
    without and with probation + hedging. Reports per leg: goodput,
    p50/p99, the healthy-baseline vs post-incident-tail p99 split, and
    the invariant verdicts. The defended leg's tail p99 must recover
    toward the healthy baseline (the p99_recovery invariant, <= 2x);
    the undefended leg must SHOW the degradation — both directions are
    the ok gate, so a scenario that stops exercising the failure fails
    the stage as loudly as a defense that stops working."""
    import asyncio
    import dataclasses

    from bioengine_tpu.testing.scenarios import (
        SLOW_REPLICA,
        run_scenario_async,
    )

    seed = int(os.environ.get("BENCH_GRAY_SEED", "7"))
    # 1 chip/replica: the bench worker's jax is already initialized
    # (single CPU device), and this scenario never re-places a replica
    # — the accounting invariant still runs, just on smaller leases
    scenario = dataclasses.replace(SLOW_REPLICA, chips_per_replica=1)

    async def run():
        undefended = await run_scenario_async(
            scenario, seed=seed, defenses=False
        )
        defended = await run_scenario_async(
            scenario, seed=seed, defenses=True
        )
        return undefended, defended

    undefended, defended = asyncio.run(run())

    def leg(r: dict) -> dict:
        ok = r["counts"].get("ok", 0)
        return {
            "requests": r["requests"],
            "failed": r["requests"] - ok,
            "wall_s": r["wall_s"],
            "goodput_rps": round(ok / max(r["wall_s"], 1e-9), 1),
            "p50_ms": r["latency_ms"]["p50"],
            "p99_ms": r["latency_ms"]["p99"],
            "baseline_p99_ms": r["phases"]["baseline_p99_ms"],
            "tail_p99_ms": r["phases"]["tail_p99_ms"],
            "probations": r["probations"],
            "hedges": r["hedges"],
            "invariants_ok": r["passed"],
        }

    legs = {"undefended": leg(undefended), "defended": leg(defended)}
    recovered = defended["invariants"]["p99_recovery"]["ok"]
    degraded = not undefended["invariants"]["p99_recovery"]["ok"]
    out = {
        "scenario": scenario.name,
        "seed": seed,
        "legs": legs,
        "tail_p99_improvement": round(
            legs["undefended"]["tail_p99_ms"]
            / max(legs["defended"]["tail_p99_ms"], 1e-9),
            2,
        ),
        "goodput_delta_pct": round(
            100.0
            * (
                legs["defended"]["goodput_rps"]
                - legs["undefended"]["goodput_rps"]
            )
            / max(legs["undefended"]["goodput_rps"], 1e-9),
            2,
        ),
        "p99_recovered": recovered,
        "degradation_shown": degraded,
        "ok": (
            defended["passed"]
            and recovered
            and degraded
            and legs["defended"]["failed"] == 0
            and legs["undefended"]["failed"] == 0
        ),
        "note": (
            "same seeded slow-ramp incident both legs (scenario "
            "engine, in-process multi-host harness). undefended = "
            "failover/breaker only (PR 4); defended = latency-outlier "
            "probation + p95-delay request hedging. tail_p99 is the "
            "post-incident window; the defended leg must sit within "
            "2x the healthy baseline, the undefended leg must not."
        ),
    }
    return out


def _bench_router_scaling(cpu: bool) -> dict:  # noqa: ARG001 — pure host path
    """Goodput-vs-router-count on the scale-out router tier.

    Runs the ``fleet_scale`` scenario (hundreds of simulated mesh hosts
    in the published routing table, a large local replica pool, offered
    load far beyond one router's admission capacity) once per router
    count in BENCH_ROUTER_LEGS (default 1,2,4,8). Each router holds a
    locally cached epoch-stamped routing table and admits up to its
    inflight cap, so served goodput is capacity-bound PER ROUTER and
    must scale near-linearly with router count until the offered load
    is fully served — ``goodput_scaling_4x_vs_1 >= 3.0`` is the
    acceptance gate. ``router_loss`` rides along as the availability
    leg: one of three routers SIGKILL'd mid-traffic must lose zero
    idempotent requests (clients hop to a sibling on the typed
    RouterClosedError). ``per_request_overhead_us`` pins what a request
    pays for the router seam itself: serial p50/p99 through an
    in-process controller handle vs a table-synced StandaloneRouter
    handle over the same replica pool (the request_overhead stage's
    perf_counter_ns methodology).

    Env: BENCH_ROUTER_LEGS / BENCH_ROUTER_SEED / BENCH_ROUTER_PROBE_N.
    """
    import asyncio
    import dataclasses

    from bioengine_tpu.testing.scenarios import (
        FLEET_SCALE,
        ROUTER_LOSS,
        run_scenario_async,
    )

    seed = int(os.environ.get("BENCH_ROUTER_SEED", "7"))
    legs_spec = os.environ.get("BENCH_ROUTER_LEGS", "1,2,4,8")
    router_counts = [
        int(tok) for tok in legs_spec.split(",") if tok.strip()
    ]
    probe_n = int(os.environ.get("BENCH_ROUTER_PROBE_N", "300"))

    async def scaling_legs() -> dict:
        legs: dict[str, dict] = {}
        for n in router_counts:
            scenario = dataclasses.replace(FLEET_SCALE, n_routers=n)
            r = await run_scenario_async(scenario, seed=seed)
            served = r["routers"]["raw_ok"]
            legs[str(n)] = {
                "routers": n,
                "offered": r["requests"],
                "served": served,
                "wall_s": r["wall_s"],
                "goodput_rps": round(served / max(r["wall_s"], 1e-9), 1),
                "table_staleness_max_s": r["routers"]["staleness_max_s"],
                "invariants_ok": r["passed"],
            }
        return legs

    async def loss_leg() -> dict:
        r = await run_scenario_async(ROUTER_LOSS, seed=seed)
        failed = sum(
            n for out, n in r["counts"].items() if out != "ok"
        )
        return {
            "requests": r["requests"],
            "failed_idempotent": failed,
            "client_failovers": r["routers"]["client_failovers"],
            "killed": r["routers"]["killed"],
            "table_staleness_max_s": r["routers"]["staleness_max_s"],
            "invariants_ok": r["passed"],
        }

    async def overhead_probe() -> dict:
        from bioengine_tpu.cluster.state import ClusterState
        from bioengine_tpu.serving import (
            DeploymentSpec,
            ServeController,
            StandaloneRouter,
            shared_object_resolver,
        )

        class _Echo:
            async def work(self, a: int = 0, b: int = 0):
                return {"sum": a + b}

        controller = ServeController(
            ClusterState(), health_check_period=3600
        )
        await controller.deploy(
            "probe-app",
            [
                DeploymentSpec(
                    name="dep",
                    instance_factory=_Echo,
                    num_replicas=4,
                    min_replicas=4,
                    max_replicas=4,
                    autoscale=False,
                )
            ],
        )
        router = StandaloneRouter(
            "probe", shared_object_resolver(controller)
        )
        router.sync_from(controller)

        async def leg(core) -> dict:
            handle = core.get_handle("probe-app", "dep")
            for _ in range(50):
                await handle.call("work", 1, 2)
            lat_us: list = []
            for _ in range(probe_n):
                t0 = time.perf_counter_ns()
                await handle.call("work", 1, 2)
                lat_us.append((time.perf_counter_ns() - t0) / 1e3)
            lat_us.sort()
            return {
                "p50_us": round(lat_us[len(lat_us) // 2], 1),
                "p99_us": round(lat_us[int(len(lat_us) * 0.99)], 1),
            }

        try:
            via_controller = await leg(controller)
            via_router = await leg(router)
        finally:
            router.kill()
            await controller.stop()
        return {
            "controller": via_controller,
            "router": via_router,
            "router_delta_us_p50": round(
                via_router["p50_us"] - via_controller["p50_us"], 1
            ),
        }

    async def run():
        return (
            await scaling_legs(),
            await loss_leg(),
            await overhead_probe(),
        )

    legs, loss, probe = asyncio.run(run())

    scaling = None
    if "1" in legs and "4" in legs:
        scaling = round(
            legs["4"]["goodput_rps"]
            / max(legs["1"]["goodput_rps"], 1e-9),
            2,
        )
    out = {
        "scenario": FLEET_SCALE.name,
        "seed": seed,
        "legs": legs,
        "goodput_scaling_4x_vs_1": scaling,
        "router_loss": loss,
        "per_request_overhead_us": probe,
        "ok": (
            all(leg["invariants_ok"] for leg in legs.values())
            and loss["invariants_ok"]
            and loss["failed_idempotent"] == 0
            and (scaling is None or scaling >= 3.0)
        ),
        "note": (
            "goodput is ADMISSION-capacity-bound per router (inflight "
            "cap x service time), which is what scales out when each "
            "router is its own process; all legs here share one "
            "interpreter, so per-request CPU does not scale and the "
            "absolute goodput numbers are not a throughput claim. "
            "router_loss is the availability leg: a SIGKILL'd router "
            "mid-traffic, zero idempotent loss via sibling failover."
        ),
    }
    return out


def _bench_token_streaming(cpu: bool) -> dict:  # noqa: ARG001 — toy decoder is cpu-native
    """Decode-path serving economics over the real DecodeEngine (paged
    KV cache, bucketed compiles) driven by the step-level continuous
    batcher (serving/decode.py).

    Three legs: ``throughput`` co-batches BENCH_TS_STREAMS bulk
    generations and reports tokens/s, tokens/s/chip and the mean batch
    occupancy (THE efficiency number of continuous batching);
    ``inter_token`` measures a solo interactive stream's time-to-first-
    token and inter-token gap distribution (the latency the
    ``inter_token_ms`` SLO governs); ``join_mid_batch`` is the
    no-head-of-line-blocking proof — a short interactive generation is
    admitted into a RUNNING long-generation batch (``joined_mid_batch``
    = 1), gets its first token in ``mid_batch_ttft_ms``, and finishes
    while the long generation is still going (``long_still_running`` =
    1) — the leg a request-level batcher structurally cannot pass.

    Every leg runs once untimed first so the timed pass measures
    steady-state decode, not bucket compiles.

    Env: BENCH_TS_STREAMS (default 8), BENCH_TS_TOKENS (default 48)."""
    import asyncio

    from bioengine_tpu.runtime.decode_engine import DecodeEngine
    from bioengine_tpu.serving.decode import DecodeLoop

    n_streams = int(os.environ.get("BENCH_TS_STREAMS", "8"))
    n_tokens = int(os.environ.get("BENCH_TS_TOKENS", "48"))
    prompt = [ord(c) % 256 for c in "the cell divides and grows"][:16]

    engine = DecodeEngine()
    engine.warmup(prompt_lens=(len(prompt),), batches=(1, n_streams))

    async def drain(stream) -> dict:
        toks: list = []
        gaps: list = []
        ttft = 0.0
        t_sub = time.perf_counter()
        t_prev = None
        async for tok in stream.tokens():
            now = time.perf_counter()
            if t_prev is None:
                ttft = now - t_sub
            else:
                gaps.append(now - t_prev)
            t_prev = now
            toks.append(tok)
        return {"tokens": toks, "ttft_s": ttft, "gaps": gaps}

    def _q(vals: list, q: float) -> float:
        s = sorted(vals)
        return s[min(int(len(s) * q), len(s) - 1)] if s else 0.0

    async def throughput_leg() -> dict:
        # reserve disabled: this is the bulk-only capacity leg, and the
        # interactive reserve would (correctly) hold one slot empty
        loop = DecodeLoop(
            engine, name="bench-tp", max_active=n_streams,
            interactive_reserve=0,
        )
        t0 = time.perf_counter()
        outs = await asyncio.gather(
            *[
                drain(loop.submit(prompt, n_tokens, klass="bulk"))
                for _ in range(n_streams)
            ]
        )
        wall = time.perf_counter() - t0
        stats = loop.stats
        await loop.close()
        total = sum(len(o["tokens"]) for o in outs)
        return {
            "streams": n_streams,
            "new_tokens_each": n_tokens,
            "tokens_per_sec": round(total / wall, 1),
            "tokens_per_sec_per_chip": round(
                total / wall / engine.chip_width, 1
            ),
            "batch_occupancy": round(stats["occupancy"]["mean"], 2),
            "steps": stats["steps"],
            "wall_s": round(wall, 3),
        }

    async def inter_token_leg() -> dict:
        loop = DecodeLoop(engine, name="bench-it", max_active=2)
        out = await drain(loop.submit(prompt, n_tokens, klass="interactive"))
        await loop.close()
        gaps_ms = [1000.0 * g for g in out["gaps"]]
        return {
            "ttft_ms": round(1000.0 * out["ttft_s"], 3),
            "inter_token_p50_ms": round(_q(gaps_ms, 0.5), 3),
            "inter_token_p99_ms": round(_q(gaps_ms, 0.99), 3),
        }

    async def join_leg() -> dict:
        loop = DecodeLoop(
            engine, name="bench-join", max_active=4, interactive_reserve=1
        )
        long_stream = loop.submit(prompt, 2 * n_tokens, klass="bulk")
        long_task = asyncio.create_task(drain(long_stream))
        # wait until the long generation is demonstrably mid-batch
        while loop.stats["tokens"] < 8:
            await asyncio.sleep(0.001)
        t0 = time.perf_counter()
        short_stream = loop.submit(prompt, 8, klass="interactive")
        short = await drain(short_stream)
        short_wall = time.perf_counter() - t0
        long_still_running = int(not long_task.done())
        long_out = await long_task
        await loop.close()
        return {
            "joined_mid_batch": int(short_stream.joined_mid_batch),
            "mid_batch_ttft_ms": round(1000.0 * short["ttft_s"], 3),
            "short_wall_ms": round(1000.0 * short_wall, 3),
            "long_still_running": long_still_running,
            "long_tokens": len(long_out["tokens"]),
        }

    async def run() -> dict:
        # untimed pass: compile every (batch bucket, KV bucket) the
        # timed legs will touch
        await throughput_leg()
        await join_leg()
        return {
            "throughput": await throughput_leg(),
            "inter_token": await inter_token_leg(),
            "join_mid_batch": await join_leg(),
            "engine": {
                "n_devices": engine.chip_width,
                "kv_block_size": engine.kv.block_size,
            },
        }

    return asyncio.run(run())


def worker_main() -> int:
    cpu = os.environ.get("BENCH_PLATFORM", "").lower() == "cpu"
    if cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    # repeat compiles (second attempt, next run on this machine) become
    # disk reads
    from bioengine_tpu.utils.compile_cache import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    budget = float(os.environ.get("BENCH_WORKER_BUDGET", "1e9"))
    start = time.perf_counter()

    # Stage 1: probe — trivial op end-to-end before burning compile time.
    t0 = time.perf_counter()
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        devices = jax.devices()
        if not cpu and devices[0].platform != "tpu":
            raise RuntimeError(
                f"no TPU: jax came up on '{devices[0].platform}' "
                "(BENCH_PLATFORM=cpu asks for the CPU on purpose)"
            )
        val = float(np.asarray(jnp.ones((8, 8)).sum()))
        assert val == 64.0, f"probe op returned {val}"
        _emit(
            {
                "stage": "probe",
                "ok": True,
                "platform": devices[0].platform,
                "device_kind": devices[0].device_kind,
                "n_devices": len(devices),
                "seconds": round(time.perf_counter() - t0, 2),
            }
        )
    except Exception as exc:  # noqa: BLE001 — report, don't crash
        _emit(
            {
                "stage": "probe",
                "ok": False,
                "error": f"{type(exc).__name__}: {exc}"[:2000],
                "seconds": round(time.perf_counter() - t0, 2),
            }
        )
        return 2

    # Stage 2: configs — each reports independently so partial results
    # survive a later-config failure or a deadline kill.
    configs = {
        "vit": _bench_vit,
        "unet": _bench_unet,
        "sharded_serving": _bench_sharded_serving,
        "multihost_mesh": _bench_multihost_mesh,
        "cold_start": _bench_cold_start,
        "pipeline_overlap": _bench_pipeline_overlap,
        "unet3d": _bench_unet3d,
        "cellpose": _bench_cellpose,
        "search": _bench_search,
        "observability_overhead": _bench_observability,
        "scheduler_goodput": _bench_scheduler,
        "gray_failure": _bench_gray_failure,
        "flash": _bench_flash,
        "ivfpq": _bench_ivfpq,
        "pqflat": _bench_pqflat,
        "rpc_transport": _bench_rpc_transport,
        "request_overhead": _bench_request_overhead,
        "router_scaling": _bench_router_scaling,
        "token_streaming": _bench_token_streaming,
    }
    if os.environ.get("BENCH_SLEEP_S"):
        # test-only stage (tests/test_bench.py): a deterministic
        # mid-stage hang so the stall/SIGTERM guarantees are asserted
        # without depending on real compile latency
        def _sleep_stage(cpu):  # noqa: ARG001
            time.sleep(float(os.environ["BENCH_SLEEP_S"]))
            return {"slept": True}

        configs["sleep"] = _sleep_stage
    wanted = [
        n.strip()
        for n in os.environ.get(
            "BENCH_CONFIGS", ",".join(DEFAULT_CONFIGS)
        ).split(",")
    ]
    any_fail = False
    for name in wanted:
        fn = configs.get(name)
        if fn is None:
            continue
        remaining = budget - (time.perf_counter() - start)
        est = STAGE_COSTS.get(name, 60) * (0.3 if cpu else 1.0)
        if remaining < est:
            _emit(
                {
                    "stage": name,
                    "ok": False,
                    "skipped": True,
                    "reason": f"budget: {remaining:.0f}s left < ~{est:.0f}s "
                    "estimated — run standalone via BENCH_CONFIGS="
                    f"{name}",
                }
            )
            continue
        t0 = time.perf_counter()
        try:
            result = fn(cpu)
            _emit(
                {
                    "stage": name,
                    "ok": True,
                    **result,
                    "seconds": round(time.perf_counter() - t0, 2),
                }
            )
        except Exception as exc:  # noqa: BLE001
            any_fail = True
            _emit(
                {
                    "stage": name,
                    "ok": False,
                    "error": f"{type(exc).__name__}: {exc}"[:2000],
                    "seconds": round(time.perf_counter() - t0, 2),
                }
            )
    return 1 if any_fail else 0


# ---------------------------------------------------------------------------
# Orchestrator: a runner thread streams worker stdout into shared state;
# the MAIN thread is a watchdog that guarantees the final JSON line
# before BENCH_DEADLINE no matter what the runner/worker are doing.
# ---------------------------------------------------------------------------


class _Shared:
    def __init__(self) -> None:
        # reentrant: the SIGTERM handler runs ON the main thread and
        # calls _final_json — with a plain Lock, a signal landing while
        # the main thread holds the lock would self-deadlock and the
        # artifact would never print
        self.lock = threading.RLock()
        self.stages: dict[str, dict] = {}
        self.skipped: dict[str, str] = {}
        self.diagnostics: list[dict] = []
        self.attempts = 0
        self.proc: subprocess.Popen | None = None
        self.done = threading.Event()


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        pass


def _runner(shared: _Shared, deadline: float) -> None:
    attempts = int(os.environ.get("BENCH_ATTEMPTS", "2"))
    per_attempt_cap = float(os.environ.get("BENCH_TIMEOUT", "1e9"))
    # a worker that stops emitting stage lines for this long is hung
    # mid-stage (the budget check only runs BETWEEN stages); killing it
    # preserves deadline headroom for a retry of the remaining stages
    stall_s = float(os.environ.get("BENCH_STALL", "240"))
    wanted_all = _wanted_stages()

    for attempt in range(1, attempts + 1):
        with shared.lock:
            remaining_stages = [
                s for s in wanted_all if not shared.stages.get(s, {}).get("ok")
            ]
        if not remaining_stages:
            return
        budget = deadline - time.monotonic() - 10.0
        if budget < 20.0:
            return
        env = dict(os.environ)
        env["BENCH_CONFIGS"] = ",".join(remaining_stages)
        env["BENCH_WORKER_BUDGET"] = str(min(budget, per_attempt_cap))
        with shared.lock:
            shared.attempts = attempt
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker"],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            start_new_session=True,
        )
        with shared.lock:
            shared.proc = proc

        stderr_buf: list[str] = []
        stderr_t = threading.Thread(
            target=lambda: stderr_buf.append(proc.stderr.read()),
            daemon=True,
        )
        stderr_t.start()
        attempt_deadline = min(
            deadline - 8.0, time.monotonic() + per_attempt_cap
        )
        last_line = [time.monotonic()]
        stalled = [False]

        def hang_watch() -> None:
            while proc.poll() is None:
                now = time.monotonic()
                if now - last_line[0] > stall_s or now > attempt_deadline:
                    stalled[0] = now - last_line[0] > stall_s
                    _kill_group(proc)
                    return
                time.sleep(2)

        watch_t = threading.Thread(target=hang_watch, daemon=True)
        watch_t.start()
        # stream stage lines as they land so a deadline kill mid-attempt
        # keeps everything completed so far
        for line in proc.stdout:
            last_line[0] = time.monotonic()
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            stage = rec.pop("stage", None)
            if stage is None:
                continue
            with shared.lock:
                if rec.get("skipped"):
                    shared.skipped[stage] = rec.get("reason", "")
                elif rec.get("ok") or stage not in shared.stages:
                    shared.stages[stage] = rec
                    if rec.get("ok"):
                        # a stage skipped on an earlier attempt and
                        # completed now must not linger in the artifact
                        # as both skipped and measured
                        shared.skipped.pop(stage, None)
        rc = proc.wait()
        stderr_t.join(timeout=5)
        with shared.lock:
            shared.proc = None
            # success = every wanted stage completed ok. A worker-side
            # budget skip leaves its stage un-ok (retried next attempt).
            if rc == 0 and _all_ok(shared, wanted_all):
                return
            tail = (stderr_buf[0][-1500:] if stderr_buf else "")
            diag = {"attempt": attempt, "rc": rc, "stderr_tail": tail}
            if stalled[0]:
                diag["killed"] = (
                    f"no stage output for >{stall_s:.0f}s — hung "
                    "mid-stage, killed to preserve retry headroom"
                )
            shared.diagnostics.append(diag)
        if attempt < attempts and deadline - time.monotonic() > 60:
            time.sleep(10)


def _wanted_stages() -> list[str]:
    return [
        s.strip()
        for s in os.environ.get(
            "BENCH_CONFIGS", ",".join(DEFAULT_CONFIGS)
        ).split(",")
        if s.strip()
    ]


def _all_ok(shared: _Shared, wanted: list[str]) -> bool:
    return all(shared.stages.get(s, {}).get("ok") for s in wanted)


def _exit_code(shared: _Shared) -> int:
    """0 only when the worker's probe ran on a TPU (or the CPU was asked
    for with BENCH_PLATFORM=cpu) and every wanted stage completed ok."""
    cpu = os.environ.get("BENCH_PLATFORM", "").lower() == "cpu"
    with shared.lock:
        probe = shared.stages.get("probe") or {}
        on_device = probe.get("ok") and (
            cpu or probe.get("platform") == "tpu"
        )
        return 0 if on_device and _all_ok(shared, _wanted_stages()) else 1


def _final_json(shared: _Shared, deadline_hit: bool) -> str:
    with shared.lock:
        vit = shared.stages.get("vit", {})
        value = float(vit.get("images_per_sec") or 0.0)
        extra = {
            "probe": shared.stages.get("probe"),
            "unet256": shared.stages.get("unet"),
            "sharded_serving": shared.stages.get("sharded_serving"),
            "multihost_mesh": shared.stages.get("multihost_mesh"),
            "cold_start": shared.stages.get("cold_start"),
            "pipeline_overlap": shared.stages.get("pipeline_overlap"),
            "unet3d": shared.stages.get("unet3d"),
            "search_latency": shared.stages.get("search"),
            "ivfpq_1m": shared.stages.get("ivfpq"),
            "pqflat_tpu_1m": shared.stages.get("pqflat"),
            "flash_attention": shared.stages.get("flash"),
            "rpc_transport": shared.stages.get("rpc_transport"),
            "request_overhead": shared.stages.get("request_overhead"),
            "router_scaling": shared.stages.get("router_scaling"),
            "token_streaming": shared.stages.get("token_streaming"),
            "observability_overhead": shared.stages.get(
                "observability_overhead"
            ),
            "scheduler_goodput": shared.stages.get("scheduler_goodput"),
            "gray_failure": shared.stages.get("gray_failure"),
            "cellpose_finetune": shared.stages.get("cellpose"),
            "attempts": shared.attempts,
        }
        if shared.skipped:
            extra["skipped"] = dict(shared.skipped)
        if deadline_hit:
            extra["deadline_hit"] = True
        if shared.diagnostics:
            extra["diagnostics"] = shared.diagnostics[-2:]
    return json.dumps(
        {
            "metric": "dinov2_vitb14_embed_images_per_sec_per_chip",
            "value": value,
            "unit": "images/sec",
            "vs_baseline": round(value / BASELINE_VIT_IMG_PER_SEC, 3),
            "extra": extra,
        }
    )


# ---------------------------------------------------------------------------
# --compare: regression-diff two bench artifacts (the tracked gate the
# empty bench trajectory becomes — CI/driver can fail a PR on a perf
# regression instead of eyeballing JSON)
# ---------------------------------------------------------------------------

# direction inference by key substring: which way is better. Checked in
# order (higher-is-better first: "images_per_sec" must not match "_s").
_COMPARE_HIGHER = (
    "per_sec", "per_chip", "speedup", "goodput", "efficiency", "recall",
    "slo_met", "occupancy", "mb_per_sec", "hit_rate",
)
_COMPARE_LOWER = (
    "_ms", "_us", "p50", "p95", "p99", "latency", "overhead", "seconds",
    "_s", "bytes",
)

_COMPARE_SKIP_KEYS = {
    "attempts", "diagnostics", "skipped", "note", "probe", "requests_per_leg",
    "deadline_hit", "workload", "depth", "batch", "n_devices", "image_hw",
    "sizes_mb", "telem_interval_s",
}


def _compare_direction(key: str):
    """'higher' | 'lower' | None (informational-only metric)."""
    k = key.lower()
    for frag in _COMPARE_HIGHER:
        if frag in k:
            return "higher"
    for frag in _COMPARE_LOWER:
        if frag in k:
            return "lower"
    return None


def _numeric_leaves(obj, prefix: str = "") -> dict:
    """Flatten a stage record to dotted-path -> float, skipping
    bookkeeping keys and non-numeric values."""
    out: dict = {}
    if not isinstance(obj, dict):
        return out
    for key, value in obj.items():
        if key in _COMPARE_SKIP_KEYS or key == "ok":
            continue
        path = f"{prefix}.{key}" if prefix else key
        if isinstance(value, bool):
            continue
        if isinstance(value, (int, float)):
            out[path] = float(value)
        elif isinstance(value, dict):
            out.update(_numeric_leaves(value, path))
    return out


def compare_main(argv) -> int:
    """``bench.py --compare A.json B.json [--tolerance-pct N]``:
    regression-diff two bench artifacts (A = baseline, B = candidate).
    Per shared stage, every numeric metric with an inferable direction
    gets a delta; a metric worse by more than the tolerance flags a
    regression and the exit code goes non-zero. Prints exactly one
    JSON line (the same contract as a measuring run)."""
    args = [a for a in argv[1:] if a != "--compare"]
    tolerance = 10.0
    if "--tolerance-pct" in args:
        i = args.index("--tolerance-pct")
        tolerance = float(args[i + 1])
        del args[i : i + 2]
    if len(args) != 2:
        print(
            json.dumps(
                {
                    "ok": False,
                    "error": "usage: bench.py --compare A.json B.json "
                    "[--tolerance-pct N]",
                }
            )
        )
        return 2
    with open(args[0]) as f:
        a = json.load(f)
    with open(args[1]) as f:
        b = json.load(f)

    def stages(artifact) -> dict:
        out = {}
        for name, rec in (artifact.get("extra") or {}).items():
            if isinstance(rec, dict) and rec.get("ok"):
                out[name] = rec
        if artifact.get("value"):
            out["headline"] = {
                "images_per_sec_per_chip": float(artifact["value"])
            }
        return out

    sa, sb = stages(a), stages(b)
    report: dict = {}
    regressions: list = []
    improvements: list = []
    for stage in sorted(set(sa) & set(sb)):
        la, lb = _numeric_leaves(sa[stage]), _numeric_leaves(sb[stage])
        stage_out: dict = {}
        for metric in sorted(set(la) & set(lb)):
            va, vb = la[metric], lb[metric]
            direction = _compare_direction(metric)
            delta_pct = (
                round(100.0 * (vb - va) / abs(va), 2) if va else None
            )
            entry = {
                "a": va,
                "b": vb,
                "delta_pct": delta_pct,
                "direction": direction,
            }
            if direction is not None and delta_pct is not None:
                worse = (
                    delta_pct < -tolerance
                    if direction == "higher"
                    else delta_pct > tolerance
                )
                better = (
                    delta_pct > tolerance
                    if direction == "higher"
                    else delta_pct < -tolerance
                )
                entry["regression"] = worse
                ref = f"{stage}.{metric}"
                if worse:
                    regressions.append(
                        {"metric": ref, "delta_pct": delta_pct, **entry}
                    )
                elif better:
                    improvements.append({"metric": ref, "delta_pct": delta_pct})
            stage_out[metric] = entry
        if stage_out:
            report[stage] = stage_out
    result = {
        "mode": "compare",
        "a": args[0],
        "b": args[1],
        "tolerance_pct": tolerance,
        "stages_compared": sorted(report),
        "stages_only_a": sorted(set(sa) - set(sb)),
        "stages_only_b": sorted(set(sb) - set(sa)),
        "regressions": regressions,
        "improvements": improvements,
        "stages": report,
        "ok": not regressions,
    }
    print(json.dumps(result))
    return 1 if regressions else 0


def main() -> int:
    if "--worker" in sys.argv:
        return worker_main()
    if "--sharded-worker" in sys.argv:
        return sharded_worker_main()
    if "--multihost-worker" in sys.argv:
        return multihost_worker_main()
    if "--cold-start-worker" in sys.argv:
        return cold_start_worker_main()
    if "--compare" in sys.argv:
        return compare_main(sys.argv)

    total = float(os.environ.get("BENCH_DEADLINE", "480"))
    deadline = time.monotonic() + total
    shared = _Shared()

    def on_term(signum, frame):  # noqa: ARG001
        # the driver's own timeout: emit the artifact NOW and take the
        # detached worker (its own session) down with us
        with shared.lock:
            proc = shared.proc
        if proc is not None:
            _kill_group(proc)
        print(_final_json(shared, deadline_hit=True), flush=True)
        os._exit(_exit_code(shared))

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)

    def run() -> None:
        try:
            _runner(shared, deadline)
        finally:
            shared.done.set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    # Watchdog: the final JSON prints before the deadline NO MATTER WHAT
    # the runner thread or worker subprocess are doing (even an
    # unkillable child cannot stop os._exit).
    shared.done.wait(timeout=max(deadline - time.monotonic() - 5.0, 1.0))
    deadline_hit = not shared.done.is_set()
    if deadline_hit:
        with shared.lock:
            proc = shared.proc
        if proc is not None:
            _kill_group(proc)
        shared.done.wait(timeout=2.0)  # let the runner flush last lines
    out = _final_json(shared, deadline_hit)
    print(out, flush=True)
    if deadline_hit:
        # never let a stuck thread turn into the driver's axe
        os._exit(_exit_code(shared))
    return _exit_code(shared)


if __name__ == "__main__":
    sys.exit(main())
