"""The XLA inference engine.

Replaces the reference's prediction pipeline (ref apps/model-runner/
runtime_deployment.py:234-312: bioimageio.core torch pipeline, CUDA-OOM
normalization, optional blockwise/tiled prediction) with a TPU design:

request -> shape bucket -> compiled-program cache -> padded batch on
device -> jitted forward -> crop back. Images larger than ``max_tile``
run tiled with overlap and linear blend stitching (the reference's
blockwise path, but vectorized: all tiles form one batch).

Every prediction joins the engine's one tile stream
(runtime/pipeline.py ``TileStream``): a cut thread cuts the tiles of
the requests in hand, in arrival order, into reusable staging buffers,
the issuing thread — the only one that talks to the device — puts and
dispatches chunk k and reads chunk k-depth+1 back (a bounded in-flight
window, ``EngineConfig.pipeline_depth``, riding XLA's async dispatch),
and a stitch thread blends. The stream does not end between requests:
a chunk is filled across the requests in hand, so one request's padding
rows carry the next one's tiles, and a request is billed its rows'
share of each chunk it rode. Each request has a thread of its own for
its host work (``submit``), so requests in hand overlap. Programs are
compiled with ``donate_argnums`` so each chunk's input HBM buffer is
recycled into its output. ``predict_serial`` keeps the strictly serial
path as the parity baseline: every reply of the stream is bit-identical
to it, whoever shared its chunks.

Multi-chip serving: an engine constructed with the replica's leased
chip group (``devices=[...]`` or ``device_ids=[...]``) builds a named
mesh over it (parallel/mesh.py) and runs every bucketed forward
sharded — the batch split over the ``dp`` axis (params replicated),
optionally the weights Megatron-sharded over a ``tp`` axis
(parallel/tensor_parallel.py rules) for models whose matrices outgrow
one chip's HBM. Batches are padded to a dp multiple
(buckets.bucket_batch ``multiple_of``) so every shard is equal, and
compiled programs are cached per (bucket, mesh-shape). A 1-chip engine
takes exactly the legacy single-device path, so its results are
bit-identical to pre-mesh behavior.
"""

from __future__ import annotations

import contextvars
import dataclasses
import functools
import itertools
import os
import re
import threading
import time
import warnings
import weakref
from typing import Any, Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bioengine_tpu.runtime.buckets import (
    DEFAULT_LADDER,
    bucket_batch,
    bucket_dim,
    crop_to,
    fill_bucketed,
    pad_to,
)
from bioengine_tpu.runtime.pipeline import (
    DispatchExecutor,
    InFlight,
    PipelineStats,
    StagingPool,
    TileJob,
    TileStream,
)
from bioengine_tpu.runtime.program_cache import (
    CompiledProgramCache,
    default_program_cache,
)
from bioengine_tpu.utils import tracing


def resolve_devices(
    device_ids: Optional[Sequence[int]],
) -> list[jax.Device]:
    """Map a replica's leased chip ids onto jax devices.

    Matches by ``Device.id``. When NONE of the lease ids exist AND the
    local backend is the CPU host platform (a TpuTopology-numbered
    lease exercised on the forced host-device test mesh), falls back to
    the first ``len(device_ids)`` local devices so the mesh WIDTH — the
    property the lease actually encodes — is preserved. On a real
    accelerator backend ANY unmatched id raises: silently remapping
    would stack disjoint leases onto the same chips while the
    controller's accounting shows them separate."""
    local = list(jax.local_devices())
    if not device_ids:
        return local[:1]
    by_id = {d.id: d for d in local}
    matched = [i for i in device_ids if i in by_id]
    if len(matched) == len(device_ids):
        return [by_id[i] for i in device_ids]
    if matched:
        raise ValueError(
            f"lease ids {list(device_ids)} only partially match local "
            f"device ids {sorted(by_id)} — chip numbering conflict"
        )
    if any(d.platform != "cpu" for d in local):
        raise ValueError(
            f"lease ids {list(device_ids)} match no local device ids "
            f"{sorted(by_id)} on a {local[0].platform} backend — "
            "refusing to remap (disjoint leases would stack onto the "
            "same chips); the width-preserving fallback is CPU-only"
        )
    if len(device_ids) > len(local):
        raise ValueError(
            f"lease names {len(device_ids)} chips but only "
            f"{len(local)} local devices exist"
        )
    return local[: len(device_ids)]


def _weakly(method: Callable) -> Callable:
    """``method`` of an object this reference does not keep alive."""
    ref = weakref.WeakMethod(method)
    return lambda *args, **kwargs: ref()(*args, **kwargs)


class _Prediction:
    """The prediction the current context is in, which ``predict`` sets:
    the device seconds billed to it, which whatever runs its chunks adds
    to (the issuing thread through its copy of the context), and the
    attrs of its ``engine.predict`` stage."""

    __slots__ = ("seconds", "attrs")

    def __init__(self, attrs: dict):
        self.seconds = 0.0
        self.attrs = attrs


_prediction: contextvars.ContextVar[Optional[_Prediction]] = (
    contextvars.ContextVar("bioengine_engine_prediction", default=None)
)


def mesh_cache_tag(dp: int, tp: int = 1) -> str:
    """The ONE definition of mesh-shape identity in cache keys:
    compiled programs (InferenceEngine._mesh_key) and model-runner
    pipeline entries both encode the chip-group shape with this —
    '1dev' for the legacy single-device path, 'dp4', 'dp2xtp2'. Two
    engines with different shapes must never share an executable or
    co-batch. Program-cache keys further qualify this with the concrete
    device group (InferenceEngine._placement_key): same shape on
    different chips is a different executable."""
    dp, tp = max(int(dp), 1), max(int(tp), 1)
    if dp * tp == 1:
        return "1dev"
    return f"dp{dp}" + (f"xtp{tp}" if tp > 1 else "")


@dataclasses.dataclass
class EngineConfig:
    max_tile: int = 1024          # images above this tile-and-stitch
    tile: int = 512
    tile_overlap: int = 64
    ladder: tuple = DEFAULT_LADDER
    # tiled predictions run their tiles through the device in chunks of
    # this many — an unbounded tile batch would OOM on large stacks
    tile_batch: int = 16
    # volumetric (B, D, H, W, C) inputs: z gets its own, smaller ladder
    # (stacks are usually far thinner than wide) and its own tile size
    max_tile_z: int = 64          # volumes deeper than this tile in z too
    tile_z: int = 32
    tile_overlap_z: int = 8
    ladder_z: tuple = (8, 16, 24, 32, 48, 64, 96, 128)
    # ---- the tile stream ----------------------------------------------------
    # chunks dispatched to the device but not yet read back; each holds
    # one (tile_batch, *bucket) HBM buffer, so depth bounds device
    # memory. 2 = double buffering; at least 1.
    pipeline_depth: int = 2
    # compile with donate_argnums so each chunk's input buffer is
    # recycled into its output instead of allocating fresh HBM per
    # chunk. Donation never changes results; XLA falls back silently
    # when input/output layouts can't alias (e.g. global outputs).
    donate_buffers: bool = True


class InferenceEngine:
    """Wraps one model (apply_fn + params) behind bucketed jit programs.

    ``apply_fn(params, images)``: (B, H, W, C) -> (B, H, W, C_out), i.e.
    dense spatial outputs; volumetric models take (B, D, H, W, C) and
    route through the z-aware bucket/tile path. Global-output models
    (embedders returning (B, D)) must be fed exact-bucket-sized inputs —
    zero-padding would silently change a global embedding, so the engine
    raises instead (embedding workloads resize crops to a fixed size
    anyway, ref apps/cell-image-search/embedder.py uses fixed 224x224).

    Zero-padding to buckets matches the bioimageio tiling convention but
    does perturb models whose normalization uses spatially-global
    statistics (GroupNorm/InstanceNorm): padded zeros enter the moments.
    Borders are already approximate under tiling; feed exact bucket
    sizes when bit-faithful outputs matter.

    Engine instances are cheap; compiled programs live in the (shared)
    CompiledProgramCache keyed by (model_id, B, H, W, C, dtype).
    """

    def __init__(
        self,
        model_id: str,
        apply_fn: Callable[[Any, jax.Array], jax.Array],
        params: Any,
        divisor: int = 1,
        z_divisor: int = 1,
        config: Optional[EngineConfig] = None,
        cache: Optional[CompiledProgramCache] = None,
        device: Optional[jax.Device] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        device_ids: Optional[Sequence[int]] = None,
        tp: int = 1,
        tp_rules: Optional[Sequence] = None,
        mesh_axes: Optional[Mapping[str, int]] = None,
    ):
        self.model_id = model_id
        self.apply_fn = apply_fn
        self.divisor = divisor
        self.z_divisor = z_divisor
        self.config = config or EngineConfig()
        self.cache = cache if cache is not None else default_program_cache
        # ---- device group -> mesh -------------------------------------------
        # precedence: explicit device objects > lease ids > legacy single
        # ``device`` kwarg > jax.devices()[0]
        if devices is not None:
            self.devices = list(devices)
        elif device_ids:
            self.devices = resolve_devices(list(device_ids))
        else:
            self.devices = [device or jax.devices()[0]]
        n = len(self.devices)
        if mesh_axes is not None and int(tp) > 1:
            # two sources of truth for the tp width would silently
            # shadow each other (a caller asking tp=2 because the
            # params outgrow one chip must not get a dp-only engine)
            raise ValueError(
                "pass tp inside mesh_axes (e.g. {'dp': -1, 'tp': 2}) "
                "or as the tp= argument — not both"
            )
        if mesh_axes is not None:
            # virtual-device layer: a hardware-neutral axes spec
            # ({"dp": -1}, {"dp": -1, "tp": 2}, ...) resolved over
            # whatever chip group THIS engine actually got — the same
            # deployment spec compiles for a 1-chip lease, a v5e-8, or
            # a forced-host-device CPU mesh without code changes
            # (parallel/mesh.py VirtualMeshSpec.stage_axes is the same
            # resolution the cross-host planner applies per stage)
            from bioengine_tpu.parallel.mesh import MeshSpec

            sizes = MeshSpec(dict(mesh_axes)).resolve(n)
            unknown = sorted(set(sizes) - {"dp", "tp"})
            if unknown:
                raise ValueError(
                    f"mesh_axes names unsupported engine axes {unknown} "
                    "(an InferenceEngine shards batches over 'dp' and "
                    "weights over 'tp'; pipeline stages live ABOVE the "
                    "engine, in the cross-host plan)"
                )
            tp = sizes.get("tp", 1)
        self.tp = max(int(tp), 1)
        if n % self.tp:
            raise ValueError(
                f"tp={self.tp} does not divide the {n}-chip group"
            )
        if self.tp > 1 and not tp_rules:
            # tp exists to SHARD the weights; silently replicating them
            # instead would hand a caller who asked for tp (because the
            # params outgrow one chip's HBM) a full copy per chip and an
            # OOM with mesh_shape still claiming a tp axis
            raise ValueError(
                f"tp={self.tp} requested without tp_rules — pass GSPMD "
                "rules (e.g. parallel.tensor_parallel.VIT_TP_RULES) or "
                "drop the tp axis"
            )
        self.dp = n // self.tp
        self.device = self.devices[0]
        if n > 1:
            from bioengine_tpu.parallel.mesh import make_mesh

            axes = {"dp": self.dp}
            if self.tp > 1:
                axes["tp"] = self.tp
            self.mesh = make_mesh(axes, self.devices)
        else:
            # the degenerate 1-chip "mesh" IS the legacy single-device
            # path — same placement, same programs, bit-identical output
            self.mesh = None
        if self.mesh is not None and self.tp > 1 and tp_rules:
            from bioengine_tpu.parallel.tensor_parallel import shard_params

            self.params, self._param_shardings = shard_params(
                self.mesh, params, tp_rules
            )
        elif self.mesh is not None:
            self._param_shardings = NamedSharding(self.mesh, P())
            self.params = jax.device_put(params, self._param_shardings)
        else:
            self._param_shardings = None
            self.params = jax.device_put(params, self.device)
        self._tp_rules = tp_rules
        cfg = self.config
        self.pipeline_stats = PipelineStats(depth=max(int(cfg.pipeline_depth), 1))
        self._staging_pool = StagingPool()
        # what the tiled predictions of one spatial size share (tile
        # plan, ramp, blend slices, weight), the newest few
        self._blend_plans: dict[tuple, tuple] = {}
        self._dispatcher = DispatchExecutor(f"dispatch-{model_id}")
        self._stream = TileStream(
            f"dispatch-{model_id}",
            depth=cfg.pipeline_depth,
            tile_batch=cfg.tile_batch,
            chunk_rows=bucket_batch(
                max(int(cfg.tile_batch), 1), multiple_of=self.dp
            ),
            stats=self.pipeline_stats,
            pool=self._staging_pool,
            dispatch=_weakly(self._dispatch_chunk),
            force=_weakly(self._force_chunk),
        )
        # the stream's threads hold the stream, the stream holds the
        # engine weakly: an engine dropped without close() still ends them
        weakref.finalize(self, self._stream.close)
        # streamed weight loading (runtime/weight_stream.py): an engine
        # built over a manifest SKELETON compiles and warms immediately
        # while the real bytes land; prediction gates on this event so
        # no request ever runs against placeholder weights. The eager
        # path never touches it (set from construction).
        self._params_ready = threading.Event()
        self._params_ready.set()
        self._params_error: Optional[BaseException] = None

    # ---- mesh introspection -------------------------------------------------

    @property
    def mesh_shape(self) -> Optional[dict[str, int]]:
        """{"dp": N[, "tp": M]} for sharded engines, None on 1 chip."""
        return dict(self.mesh.shape) if self.mesh is not None else None

    @property
    def _mesh_key(self) -> str:
        # mesh is None exactly when dp*tp == 1, where mesh_cache_tag
        # already returns the legacy "1dev" tag
        return mesh_cache_tag(self.dp, self.tp)

    @property
    def _placement_key(self) -> str:
        """Program identity: mesh shape AND the concrete device group.
        The shape tag alone is not enough for a shared program cache —
        two same-width engines over disjoint chip groups (replica A on
        chips 0-3, replica B on 4-7 in one 8-chip host process) build
        unequal Meshes, so A's warmed executable is a silent
        retrace+recompile inside B's first hot request."""
        ids = ",".join(str(d.id) for d in self.devices)
        return f"{self._mesh_key}@{ids}"

    # ---- streamed weight loading --------------------------------------------

    def begin_param_streaming(self) -> None:
        """Mark the current params as a manifest skeleton: programs may
        compile/warm against them (same shapes, same executables), but
        prediction blocks until :meth:`complete_param_streaming`."""
        self._params_error = None
        self._params_ready.clear()

    def complete_param_streaming(self, params: Any) -> None:
        """Swap the real checkpoint in (placed exactly as the skeleton
        was — same shardings, so warmed executables stay valid) and
        release gated predictions."""
        if self.mesh is not None and self.tp > 1 and self._tp_rules:
            from bioengine_tpu.parallel.tensor_parallel import shard_params

            self.params, self._param_shardings = shard_params(
                self.mesh, params, self._tp_rules
            )
        elif self.mesh is not None:
            self.params = jax.device_put(params, self._param_shardings)
        else:
            self.params = jax.device_put(params, self.device)
        self._params_ready.set()

    def fail_param_streaming(self, exc: BaseException) -> None:
        """Loader died: release waiters with the error instead of
        letting first requests hang to the timeout."""
        self._params_error = exc
        self._params_ready.set()

    @property
    def params_resident(self) -> bool:
        return self._params_ready.is_set() and self._params_error is None

    _weight_stream_timeout_s: Optional[float] = None

    def _wait_params_ready(self) -> None:
        if self._params_ready.is_set() and self._params_error is None:
            return
        # memoized env read: _wait_params_ready sits on the predict hot
        # path, and the knob only matters before first readiness anyway
        timeout = InferenceEngine._weight_stream_timeout_s
        if timeout is None:
            timeout = InferenceEngine._weight_stream_timeout_s = float(
                os.environ.get("BIOENGINE_WEIGHT_STREAM_TIMEOUT_S", "600")
            )
        if not self._params_ready.wait(timeout):
            raise RuntimeError(
                f"model '{self.model_id}': streamed weights not resident "
                f"after {timeout}s"
            )
        if self._params_error is not None:
            raise RuntimeError(
                f"model '{self.model_id}': streamed weight load failed: "
                f"{self._params_error}"
            ) from self._params_error

    def _batch_sharding(self, ndim: int) -> NamedSharding:
        """Leading dim over ``dp``, everything else replicated (tp
        sharding lives in the params; GSPMD propagates it)."""
        return NamedSharding(self.mesh, P("dp", *([None] * (ndim - 1))))

    def _put(self, host: np.ndarray):
        """Place a staged host batch: single-device put on 1 chip,
        dp-sharded scatter on a mesh. The batch dim is always a dp
        multiple (bucket_batch ``multiple_of``), so shards are equal."""
        if self.mesh is None:
            return jax.device_put(host, self.device)
        return jax.device_put(host, self._batch_sharding(host.ndim))

    def describe(self) -> dict:
        """Mesh + per-chip utilization for Replica.describe /
        get_app_status (memory_stats is best-effort: the CPU backend
        has none)."""
        per_chip = {}
        for d in self.devices:
            entry: dict[str, Any] = {"platform": d.platform}
            try:
                stats = d.memory_stats() or {}
                entry["bytes_in_use"] = stats.get("bytes_in_use")
                entry["bytes_limit"] = stats.get("bytes_limit")
            except Exception:  # noqa: BLE001 — stats never break status
                pass
            per_chip[str(d.id)] = entry
        # per-program compile cost: this engine's slice of the (shared)
        # program cache — entries are keyed by model_id, so filter to
        # ours. The lifetime totals live on cache.stats / the
        # program_cache_* metrics; this is the per-program breakdown an
        # operator reads next to HBM residency when profiling one
        # replica of a live deployment.
        mine = {
            k: v
            for k, v in self.cache.compile_info_snapshot().items()
            if k.startswith(f"('{self.model_id}'")
        }
        cache_stats = self.cache.stats_dict()
        real_compiles = [
            v["seconds"] for v in mine.values() if not v["cache_hit"]
        ]
        return {
            "device_ids": [d.id for d in self.devices],
            "n_devices": len(self.devices),
            "mesh": self.mesh_shape,
            "per_chip": per_chip,
            "params_resident": self.params_resident,
            # the stream's accounting: ``chunks_shared`` beside ``chunks``
            # says how often requests shared a chunk
            "pipeline": self.pipeline_stats.as_dict(),
            "programs": {
                "live": len(mine),
                "compile_seconds": {
                    k: round(v["seconds"], 3) for k, v in mine.items()
                },
                # which of this engine's "compiles" were persistent/tier
                # cache hits (near-zero build with the disk cache on) —
                # a warm replica's program list reads hit/hit/hit, a
                # cold one's carries the real 20-40 s entries
                "cache_hits": {k: v["cache_hit"] for k, v in mine.items()},
                # attention calls traced into each program, by path and
                # N: a served cpsam program reads {"packed:1024": 24}
                "attention_paths": {
                    k: v["attention_paths"] for k, v in mine.items()
                },
                # and the block MLPs, by path and rows: {"fused:16384": 24}
                "mlp_paths": {k: v["mlp_paths"] for k, v in mine.items()},
                "persistent_hits": sum(
                    1 for v in mine.values() if v["cache_hit"]
                ),
                "real_compiles": len(real_compiles),
                "real_compile_seconds": round(sum(real_compiles), 3),
                "cache_hit_rate": cache_stats["hit_rate"],
            },
        }

    def close(self) -> None:
        """End the request threads and the stream's (idempotent);
        predictions not yet answered fail with a retryable error."""
        self._dispatcher.close()
        self._stream.close()

    def submit(self, fn: Callable, *args: Any, **kwargs: Any):
        """Run ``fn`` on one of the engine's request threads; returns a
        ``concurrent.futures.Future``. The building block behind
        ``predict_async`` for callers that wrap extra host work
        (pre/post processing) around the engine: that work runs on the
        request's own thread, beside the device, and every ``predict``
        inside joins the one tile stream that talks to it.

        The task runs in a copy of the submitter's context (a sampled
        trace and the chip-seconds accumulator cross with it). The wait
        for a request thread is the ``engine.queue`` stage, the task
        whole the ``engine.request`` stage: while one is open the engine
        has that request in hand. Requests in hand overlap."""
        submitted = time.time_ns()
        stats = self.pipeline_stats

        def task():
            tracing.begin_request()
            queued = tracing.record_stage(
                "engine.queue", submitted, time.time_ns()
            )
            stats.add(requests=1, queue_seconds=queued)
            with tracing.stage("engine.request"):
                return fn(*args, **kwargs)

        return self._dispatcher.submit(contextvars.copy_context().run, task)

    # ---- program management -------------------------------------------------

    def _program(self, shape: tuple[int, ...], dtype) -> Callable:
        donate = bool(self.config.donate_buffers)
        # the mesh shape AND device group are part of program identity:
        # the same bucket compiled for dp=4 is a different executable
        # (sharded layouts, SPMD collectives) than the 1-chip program,
        # and the same dp=4 shape on a different chip group is a
        # different placement — a shared cache serving several engines
        # must never mix any of them (each entry's warmup must run on
        # its own engine's placement, see build() below)
        key = (
            self.model_id, *shape, np.dtype(dtype).name, donate,
            self._placement_key,
        )

        def build():
            # jit names a program after its function: one name per model
            # and shape, so a profiler's "XLA Modules" line tells this
            # engine's programs apart (they were all ``jit__lambda_``)
            def forward(params, images):
                return self.apply_fn(params, images)

            forward.__name__ = forward.__qualname__ = re.sub(
                r"\W", "_",
                f"engine_{self.model_id}_{'x'.join(map(str, shape))}",
            )
            fn = (
                jax.jit(forward, donate_argnums=(1,))
                if donate
                else jax.jit(forward)
            )
            # Trigger compilation now so the first request doesn't pay it
            # inside the hot path accounting. The dummy must be COMMITTED
            # with the hot path's placement — the hot path feeds
            # ``_put`` arrays (single-device or dp-sharded), and a
            # differently-placed warmup arg compiles a different
            # executable (the hot path would silently recompile on its
            # first call). Donation is best-effort: XLA warns when no
            # output can alias the input (e.g. a global-output model)
            # and runs undonated — not actionable.
            dummy = self._put(np.zeros(shape, np.dtype(dtype)))
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message=".*donated buffers.*"
                )
                fn(self.params, dummy).block_until_ready()
            return fn

        return self.cache.get_or_compile(key, build)

    def warmup(self, shapes: list[tuple[int, ...]], dtype=np.float32):
        for shape in shapes:
            # normalize the batch dim exactly like the hot path does —
            # a dp-sharded _put of a non-dp-divisible dummy would raise
            B, *rest = shape
            self._program((bucket_batch(B, multiple_of=self.dp), *rest), dtype)

    # ---- prediction ---------------------------------------------------------

    def _axis_specs(self, ndim: int) -> list["_AxisSpec"]:
        """Per-spatial-axis tiling/bucketing parameters, in axis order.

        4D (B, H, W, C) -> [y, x]; 5D (B, D, H, W, C) -> [z, y, x] with
        z on its own ladder/tile sizes. One generic code path serves
        both — planar images are just volumes without a z axis.
        """
        cfg = self.config
        xy = _AxisSpec(
            cfg.tile, cfg.tile_overlap, cfg.ladder, self.divisor, cfg.max_tile
        )
        if ndim == 5:
            z = _AxisSpec(
                cfg.tile_z, cfg.tile_overlap_z, cfg.ladder_z,
                self.z_divisor, cfg.max_tile_z,
            )
            return [z, xy, xy]
        return [xy, xy]

    def _validate(self, images: np.ndarray) -> np.ndarray:
        images = np.asarray(images)
        if images.ndim not in (4, 5):
            raise ValueError(
                f"expected (B, H, W, C) or (B, D, H, W, C), got {images.shape}"
            )
        return images

    def _needs_tiling(self, images: np.ndarray, specs: list["_AxisSpec"]) -> bool:
        spatial = images.shape[1:-1]
        return any(
            size > spec.max_tile for size, spec in zip(spatial, specs)
        )

    def predict(self, images: np.ndarray) -> np.ndarray:
        """images: (B, H, W, C) or volumes (B, D, H, W, C), host array ->
        model output, cropped back to the original spatial size. Inputs
        larger than the per-axis ``max_tile`` run overlap-tiled with
        linear blend stitching (the reference's blockwise path, ref
        apps/model-runner/runtime_deployment.py:277-280), their tiles in
        the stream's chunks beside those of whatever else the engine
        has in hand; the others run whole on the stream's issuing
        thread, in their turn. The caller's thread builds the programs
        its tiles may run at (a compile never holds up other requests'
        chunks) and otherwise waits.

        The whole prediction is the ``engine.predict`` stage. Under a
        sampled request trace its span's attrs carry ``stage_seconds``,
        the sums of the stages of the chunks this request rode (cut /
        h2d put / dispatch / device_wait / d2h / stitch, ``readback`` =
        device_wait + d2h) and ``compute``, its rows' share of those
        chunks' device time — the device-side half of the request's
        latency breakdown — plus the prediction's ``chip_seconds``
        (``compute`` x mesh width). Chip-seconds ALSO feed the
        request-scoped accounting accumulator (utils/tracing.py) on
        every call, sampled or not: cost is exact, only spans are
        sampled. Summed over the requests served they are the device's
        busy time x mesh width, however the requests shared chunks.

        A tiled prediction's stage, sampled or not, carries its time in
        the tile stream: ``tiles``, the ``chunks`` its rows rode,
        ``stream_wait_s`` (enrolment to the dispatch of its first chunk)
        and ``stream_run_s`` (from there to its last row blended)."""
        width = len(self.devices)
        whole = tracing.stage(
            "engine.predict",
            model=self.model_id,
            batch=len(images),
            mesh=self._mesh_key,
            devices=width,
        )
        current = _Prediction(whole.attrs)
        token = _prediction.set(current)
        try:
            with whole:
                out = self._predict_impl(images)
                if whole.span is not None:
                    whole.attrs["stage_seconds"] = self._stage_seconds(
                        whole.span, current.seconds
                    )
            return out
        finally:
            _prediction.reset(token)
            chip_seconds = current.seconds * width
            if whole.span is not None:
                whole.attrs["chip_seconds"] = round(chip_seconds, 6)
            tracing.add_chip_seconds(chip_seconds)

    @staticmethod
    def _stage_seconds(span: dict, compute: float) -> dict:
        """``engine.predict``'s ``stage_seconds``: the ``engine.*``
        stages under this request's span summed by name, without the
        prefix."""
        own = tracing.child_stage_seconds(span)
        sums = {
            name: round(own.get(f"engine.{name}", 0.0), 6)
            for name in (
                "cut", "put", "dispatch", "device_wait", "d2h", "stitch"
            )
        }
        sums["readback"] = round(sums["device_wait"] + sums["d2h"], 6)
        sums["compute"] = round(compute, 6)
        return sums

    def _predict_impl(self, images: np.ndarray) -> np.ndarray:
        images = self._validate(images)
        specs = self._axis_specs(images.ndim)
        self.pipeline_stats.add(items=len(images))
        if self._needs_tiling(images, specs):
            job = _TiledJob(self, images, specs)
            # whatever this job's chunks run at is built here, on the
            # request's own thread: a compile would hold up every other
            # request's chunks on the issuing thread
            for rows in job.sizes:
                self._program((rows, *job.row_shape), job.dtype)
            self._stream.enrol(job).result()
            self._bill(job.compute_seconds)
            current = _prediction.get()
            if current is not None:
                current.attrs.update(
                    tiles=job.tiles,
                    chunks=job.chunks,
                    stream_wait_s=job.stream_wait_seconds,
                    stream_run_s=job.stream_run_seconds,
                )
            with tracing.stage("engine.stitch") as divide:
                out = job.acc / job.weight
            self.pipeline_stats.add(stitch_seconds=divide.seconds)
            return out
        # only the stream's issuing thread talks to the device
        return self._stream.run(
            functools.partial(self._predict_direct, images, specs)
        ).result()

    @staticmethod
    def _bill(compute_seconds: float) -> None:
        current = _prediction.get()
        if current is not None:
            current.seconds += compute_seconds

    def predict_serial(self, images: np.ndarray) -> np.ndarray:
        """The strictly serial path, on the caller's thread: one chunk
        cut, put, computed, read back, and stitched at a time, one batch
        item after another. Kept as the numeric parity baseline for the
        stream."""
        images = self._validate(images)
        specs = self._axis_specs(images.ndim)
        self.pipeline_stats.add(items=len(images))
        if self._needs_tiling(images, specs):
            return np.stack(
                [self._predict_tiled(item, specs) for item in images]
            )
        return self._predict_direct(images, specs)

    async def predict_async(self, images: np.ndarray) -> np.ndarray:
        """Async front door: run ``predict`` on one of the engine's
        request threads and await the result. Replicas and the
        continuous batcher drain into the stream through here without
        wrapping whole predictions in ``asyncio.to_thread`` (no thread
        spawned per call, no callers racing for one device: the
        stream's one issuing thread serializes device access while the
        requests' own threads and its cut and stitch threads overlap
        it)."""
        import asyncio

        # submit() runs the task in a copy of this context: a sampled
        # trace and the chip accounting cross with it
        return await asyncio.wrap_future(self.submit(self.predict, images))

    def _predict_direct(self, x: np.ndarray, specs: list["_AxisSpec"]) -> np.ndarray:
        """Bucket every spatial axis, pad into a reusable staging
        buffer, run the compiled program, crop back. One chunk of the
        same six stages as the stream's, one after the other (``cut``
        is the fill, ``stitch`` the crop), into the same
        ``PipelineStats`` fields."""
        B = x.shape[0]
        C = x.shape[-1]
        spatial = x.shape[1:-1]
        axes = tuple(range(1, x.ndim - 1))
        buckets = tuple(
            bucket_dim(size, spec.ladder, spec.divisor)
            for size, spec in zip(spatial, specs)
        )
        bb = bucket_batch(B, multiple_of=self.dp)
        staged = self._staging_pool.acquire((bb, *buckets, C), x.dtype)
        try:
            with tracing.stage("engine.cut") as cut:
                fill_bucketed(staged, x)
            flight = self._dispatch_chunk(staged, B)
            out = self._force_chunk(flight)
        finally:
            self._staging_pool.release(staged)
        with tracing.stage("engine.stitch") as crop:
            out = out[:B]
            if out.ndim == len(spatial) + 2:
                out = crop_to(out, spatial, axes=axes)
            elif buckets != spatial:
                raise ValueError(
                    f"model '{self.model_id}' returns a global output "
                    f"(shape {out.shape}) but the input {spatial} was "
                    f"padded to bucket {buckets} — padding corrupts global "
                    f"outputs. Resize inputs to a bucket size."
                )
        # serial: the device has the chunk from the end of the dispatch
        # to the end of the wait for it
        compute = (flight.ready_ns - flight.dispatched_ns) / 1e9
        self.pipeline_stats.add(
            chunks=1,
            cut_seconds=cut.seconds,
            stitch_seconds=crop.seconds,
            compute_seconds=compute,
            wall_seconds=(crop.end_ns - cut.start_ns) / 1e9,
        )
        self._bill(compute)
        return out

    # ---- one chunk on the device (every path's put/dispatch/wait/d2h) -------

    def _dispatch_chunk(self, buf: np.ndarray, n: int, **attrs: Any) -> InFlight:
        """Hand one staged chunk (``n`` useful rows) to the device
        WITHOUT blocking: ``engine.put`` is the ``device_put`` call (H2D
        enqueue and linearize), ``engine.dispatch`` the program lookup,
        the params gate and the jitted call; ``attrs`` (the stream's
        account of the chunk) go on the dispatch stage."""
        with tracing.stage("engine.put", bytes=buf.nbytes) as put:
            # staged host chunks become sharded arrays on a mesh engine
            # (single-device put on 1 chip)
            dev = self._put(buf)
        with tracing.stage("engine.dispatch", **attrs) as dispatch:
            program = self._program(buf.shape, buf.dtype)
            # the gate sits AFTER compile: under streamed loading the
            # first request's compile overlaps the weight transfer, and
            # only the real execution waits for residency (an eager
            # engine pays one Event.is_set() here)
            self._wait_params_ready()
            out = program(self.params, dev)
        self.pipeline_stats.add(
            put_seconds=put.seconds,
            dispatch_seconds=dispatch.seconds,
            h2d_bytes=buf.nbytes,
            rows_executed=buf.shape[0],
            rows_useful=n,
        )
        return InFlight(
            out, n, dispatch.end_ns, issue_ns=dispatch.end_ns - put.start_ns,
            stages=[put, dispatch],
        )

    def _force_chunk(self, flight: InFlight) -> np.ndarray:
        """Block until a dispatched chunk is on the host:
        ``engine.device_wait`` is the wait for the device,
        ``engine.d2h`` the copy of the ready result. Returns every row,
        padding included."""
        out = flight.out
        with tracing.stage("engine.device_wait") as wait:
            out.block_until_ready()
        with tracing.stage("engine.d2h", bytes=out.nbytes) as d2h:
            host = np.asarray(out)
        flight.ready_ns = wait.end_ns
        flight.waited_ns = wait.end_ns - wait.start_ns
        flight.stages += (wait, d2h)
        self.pipeline_stats.add(
            device_wait_seconds=wait.seconds,
            d2h_seconds=d2h.seconds,
            d2h_bytes=host.nbytes,
        )
        return host

    # ---- tiling geometry (shared by the serial path and the stream) ----------

    def _tile_plan(
        self, spatial: tuple[int, ...], specs: list["_AxisSpec"]
    ) -> "_TilePlan":
        tsizes = [min(s.tile, max(size, 1)) for s, size in zip(specs, spatial)]
        overlaps = [
            min(s.overlap, max(t - 1, 0)) for s, t in zip(specs, tsizes)
        ]
        starts_per_axis = [
            _tile_starts(size, t, o)
            for size, t, o in zip(spatial, tsizes, overlaps)
        ]
        coords = list(itertools.product(*starts_per_axis))
        buckets = tuple(
            bucket_dim(t, spec.ladder, spec.divisor)
            for t, spec in zip(tsizes, specs)
        )
        return _TilePlan(tsizes, overlaps, coords, buckets)

    def _predict_tiled(
        self, item: np.ndarray, specs: list["_AxisSpec"]
    ) -> np.ndarray:
        """Overlap-tile one (H, W, C) image or (D, H, W, C) stack and
        stitch with a separable linear ramp (the reference's
        Gaussian-blend stitching, ref apps/fibsem-mito-analysis/
        analysis_deployment.py:10-14). Tiles run through the bucketed
        direct path in chunks of ``tile_batch`` so a large stack never
        materializes as one giant device batch."""
        spatial = item.shape[:-1]
        plan = self._tile_plan(spatial, specs)
        tsizes, overlaps, coords = plan.tsizes, plan.overlaps, plan.coords
        spatial_axes = tuple(range(1, len(tsizes) + 1))

        def cut(start) -> np.ndarray:
            sl = tuple(slice(s0, s0 + t) for s0, t in zip(start, tsizes))
            return pad_to(item[sl][None], tuple(tsizes), axes=spatial_axes)[0]

        # tiles are cut, run, and stitched per chunk (never all at once)
        # so neither host nor device ever holds more than ``tile_batch``
        # tiles beyond the accumulator itself
        chunk = max(int(self.config.tile_batch), 1)
        ramp = _ramp_nd(tsizes, overlaps)
        acc = None
        weight = np.zeros((*spatial, 1), np.float32)
        for i in range(0, len(coords), chunk):
            with tracing.stage("engine.cut") as tiles:
                batch = np.stack([cut(s) for s in coords[i : i + chunk]])
            out = self._predict_direct(batch, specs)
            if out.ndim != len(spatial) + 2:
                raise ValueError(
                    f"tiled prediction requires dense spatial outputs, "
                    f"model '{self.model_id}' returned {out.shape}"
                )
            with tracing.stage("engine.stitch") as blend:
                if acc is None:
                    acc = np.zeros((*spatial, out.shape[-1]), np.float32)
                for tile_out, start in zip(out, coords[i : i + chunk]):
                    dst = tuple(
                        slice(s0, min(s0 + t, size))
                        for s0, t, size in zip(start, tsizes, spatial)
                    )
                    src = tuple(slice(0, s.stop - s.start) for s in dst)
                    acc[dst] += tile_out[src] * ramp[src]
                    weight[dst] += ramp[src]
            self.pipeline_stats.add(
                cut_seconds=tiles.seconds,
                stitch_seconds=blend.seconds,
                wall_seconds=tiles.seconds + blend.seconds,
            )
        return acc / np.maximum(weight, 1e-8)

    def _blend_plan(self, spatial: tuple[int, ...], specs: list["_AxisSpec"]):
        """What every tiled prediction of one spatial size shares: the
        tile plan, the ramp, each tile's (dst, src) slices and the blend
        weight, accumulated in tile order as the serial path does so the
        results stay bit-identical. Kept for the newest few sizes (two
        request threads may build the same one; either will do)."""
        cached = self._blend_plans.get(spatial)
        if cached is None:
            plan = self._tile_plan(spatial, specs)
            ramp = _ramp_nd(plan.tsizes, plan.overlaps)
            dst_src = []
            weight = np.zeros((*spatial, 1), np.float32)
            for start in plan.coords:
                dst = tuple(
                    slice(s0, min(s0 + t, size))
                    for s0, t, size in zip(start, plan.tsizes, spatial)
                )
                src = tuple(slice(0, s.stop - s.start) for s in dst)
                dst_src.append((dst, src))
                weight[dst] += ramp[src]
            cached = (plan, ramp, dst_src, np.maximum(weight, 1e-8))
            plans = dict(self._blend_plans)
            while len(plans) >= 8:
                del plans[next(iter(plans))]
            plans[spatial] = cached
            self._blend_plans = plans
        return cached


class _TiledJob(TileJob):
    """One tiled prediction as the tile stream sees it: all batch items'
    tiles, item after item in coordinate order, which is the serial
    path's order, so whatever chunks the rows ride the result
    (``acc / weight``) is bit-identical to ``predict_serial``."""

    def __init__(
        self, engine: InferenceEngine, images: np.ndarray,
        specs: list["_AxisSpec"],
    ):
        super().__init__()
        self.model_id = engine.model_id
        self.images = images
        self.spatial = spatial = images.shape[1:-1]
        self.plan, self.ramp, self.dst_src, self.weight = engine._blend_plan(
            spatial, specs
        )
        per_item = len(self.plan.coords)
        self.tiles = len(images) * per_item
        self.row_shape = (*self.plan.buckets, images.shape[-1])
        self.dtype = images.dtype
        self.key = (self.row_shape, images.dtype.str)
        # alone, an item's tiles run in chunks of ``tile_batch`` and one
        # tail, each padded up the batch ladder: the row counts the
        # stream may run this job's rows at
        chunk = max(int(engine.config.tile_batch), 1)
        alone = ({chunk} if per_item >= chunk else set()) | {per_item % chunk}
        self.sizes = frozenset(
            bucket_batch(n, multiple_of=engine.dp) for n in alone - {0}
        )
        self.acc: Optional[np.ndarray] = None

    def cut(self, first: int, rows: np.ndarray) -> None:
        tsizes, buckets, coords = (
            self.plan.tsizes, self.plan.buckets, self.plan.coords,
        )
        tile_region = tuple(slice(0, t) for t in tsizes)
        for j, row in enumerate(rows):
            b, i = divmod(first + j, len(coords))
            sl = tuple(slice(s0, s0 + t) for s0, t in zip(coords[i], tsizes))
            row[tile_region] = self.images[b][sl]
            # reused buffers hold stale data: zero the pad margin
            # between the tile extent and the bucket extent (a no-op
            # when the tile sits exactly on the ladder)
            for ax, (t, bkt) in enumerate(zip(tsizes, buckets)):
                if bkt > t:
                    idx = [slice(None)] * (len(buckets) + 1)
                    idx[ax] = slice(t, bkt)
                    row[tuple(idx)] = 0

    def blend(self, first: int, rows: np.ndarray) -> None:
        if rows.ndim != len(self.spatial) + 2:
            raise ValueError(
                f"tiled prediction requires dense spatial outputs, "
                f"model '{self.model_id}' returned {rows.shape}"
            )
        if self.acc is None:
            self.acc = np.zeros(
                (len(self.images), *self.spatial, rows.shape[-1]), np.float32
            )
        for j, tile_out in enumerate(rows):
            b, i = divmod(first + j, len(self.dst_src))
            dst, src = self.dst_src[i]
            self.acc[b][dst] += tile_out[src] * self.ramp[src]


@dataclasses.dataclass(frozen=True)
class _AxisSpec:
    """Tiling/bucketing parameters for one spatial axis."""

    tile: int
    overlap: int
    ladder: tuple
    divisor: int
    max_tile: int


@dataclasses.dataclass(frozen=True)
class _TilePlan:
    """Shared tiling geometry: clamped tile sizes/overlaps, tile start
    coordinates (row-major), and the spatial bucket the tiles pad to."""

    tsizes: list[int]
    overlaps: list[int]
    coords: list[tuple[int, ...]]
    buckets: tuple[int, ...]


def _tile_starts(size: int, tile: int, overlap: int) -> list[int]:
    """Start offsets covering [0, size) with ``overlap`` between tiles;
    the last tile is clamped so it ends exactly at ``size``."""
    stride = max(tile - overlap, 1)
    starts = {
        min(s, max(size - tile, 0))
        for s in range(0, max(size - overlap, 1), stride)
    }
    return sorted(starts)


def _ramp_1d(tile: int, overlap: int) -> np.ndarray:
    """Linear edge ramp of length ``tile``, 1.0 in the interior."""
    r = np.ones(tile, np.float32)
    if overlap > 0:
        edge = np.linspace(1.0 / (overlap + 1), 1.0, overlap, dtype=np.float32)
        r[:overlap] = edge
        r[-overlap:] = edge[::-1]
    return r


def _ramp_nd(tiles: list[int], overlaps: list[int]) -> np.ndarray:
    """Separable blend ramp over N spatial axes, shape (*tiles, 1)."""
    ramp = np.ones((), np.float32)
    for t, o in zip(tiles, overlaps):
        ramp = ramp[..., None] * _ramp_1d(t, o)
    return ramp[..., None]
