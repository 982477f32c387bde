"""One run of one cell: set up, warm, measure, check, report.

Everything is one process that holds the chip. The worker is the object
``python -m bioengine_tpu.worker --mode single-machine`` builds, the app
is the one the configuration names, deployed as shipped, and every
request goes over a real client connection (the start/deploy/counter
code began as copies of ``chip_smoke.py``'s phases). From the program
the harness takes the system under test and its counters (the profiler
it starts itself: ``start_trace``); the traffic, the weights, the plain
reference, the work counts, the peaks and the trace reduction are the
benchmark's own.

What one served path does lives in ``benchmarks/paths/<name>.py``, the
module the configuration names under ``deployment.path``: the package
it serves, how the app is deployed, the programs a mix can form and the
lone request that runs each, one request (unary or streamed), what a
request's work is in the path's own unit, and the comparison with the
plain reference. What every run does is here.

Order of a run (``run_cell``):

  gate      the platform asked for, with enough chips, or raise
  package   the path's: weights made on the device from the seed in one
            jitted call, written as the app wants them
  start     worker (port 0), one client connection per client of the plan
  deploy    ``deploy_app(local_path=...)``, waited to HEALTHY
  warm      one request per program the mix can form, then the clients
            loop for the mix's lead-in; ``setup_s`` ends where the
            window opens on them
  window    the generator drives the clients for ``seconds`` more; with
            ``trace`` the profiler records a few seconds in its middle
  close     counters read, memory peak read, worker stopped (frees the
            program's state)
  check     the plain reference over the last replies of a sample of
            the deck's requests, drawn from the seed, the largest in it
"""

from __future__ import annotations

import asyncio
import dataclasses
import importlib
import json
import os
import shutil
import sys
import threading
import time
import traceback
from pathlib import Path
from typing import Any, Optional

import numpy as np

from benchmarks import window_metrics

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a --trace 1 run records this much of the middle of the window, and
# starts the profiler this long before (starting it stalls the process
# for a second or two, and the clients have to find their stride again)
TRACED_SECONDS = 8.0
TRACE_LEAD_SECONDS = 3.0
# a reply of the wrong shape or with a non-finite value (JSON has no inf)
NOT_COMPARABLE = 1e30


def log(message: str) -> None:
    print(f"[bench] {message}", file=sys.stderr, flush=True)


# ---- what a cell is ----------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[str]
    per_layer: list[str]
    units: dict[str, str]
    path: Any                     # the module of the served path


def load_manifest(root: Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def path_module(config: dict):
    """The served path a configuration names. There is no default: a
    configuration that names none is refused."""
    name = (config.get("deployment") or {}).get("path")
    if not name:
        raise ValueError(
            f"configuration {config.get('name')!r} names no served path: set "
            "deployment.path to a module of benchmarks/paths/"
        )
    return importlib.import_module(f"benchmarks.paths.{name}")


def load_cell(name: str, root: Path = REPO) -> Cell:
    manifest = load_manifest(root)
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r}; have {sorted(by_name)}")
    workload = by_name[name]
    config_entry = next(
        c for c in manifest["configs"] if c["name"] == workload["config"]
    )
    config = json.loads((root / config_entry["file"]).read_text())
    traffic = json.loads(
        (root / BENCH.name / "traffic" / f"{workload['traffic']}.json").read_text()
    )
    metrics = manifest["end_to_end"] + manifest["per_layer"]

    def reported(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return Cell(
        name=name,
        chips=int(workload["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m["name"] for m in manifest["end_to_end"] if reported(m)],
        per_layer=[m["name"] for m in manifest["per_layer"] if reported(m)],
        units={m["name"]: m["unit"] for m in metrics},
        path=path_module(config),
    )


def operator_env(cell: Cell) -> dict[str, str]:
    """The operator variables the configuration sets, each of which its
    path has to list (and ``docs/OPERATIONS.md`` to name)."""
    env = cell.config["deployment"].get("env") or {}
    for key in env:
        if key not in cell.path.OPERATOR_ENV:
            raise ValueError(
                f"configuration {cell.config['name']!r} sets {key}; its path "
                f"{cell.path.__name__.rsplit('.', 1)[-1]!r} lets a "
                f"configuration set {list(cell.path.OPERATOR_ENV)} and nothing else"
            )
    return {key: str(value) for key, value in env.items()}


def work_module(config: dict):
    return importlib.import_module(f"benchmarks.work.{config['work']}")


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in benchmarks/peaks.json")
    return table[kind]


# ---- compile counter ---------------------------------------------------------


class CompileCounter:
    """How often jit had to obtain an executable (jax records one
    backend-compile event each time, persistent-cache hit or not)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            self.count += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_event)


# ---- gate --------------------------------------------------------------------


def device_gate(platform: str, chips: int) -> dict:
    import jax

    from bioengine_tpu.utils.compile_cache import (
        enable_persistent_compilation_cache,
    )

    cache_dir = enable_persistent_compilation_cache()
    # a size cap from the environment (JAX_COMPILATION_CACHE_MAX_SIZE)
    # evicts one cell's programs to make room for the next one's, and
    # every run then compiles again: no cap on the benchmark's cache
    jax.config.update("jax_compilation_cache_max_size", -1)
    entries = [f.stat().st_size for f in Path(cache_dir).glob("*-cache")]
    log(f"compile cache {cache_dir}: {len(entries)} entries, "
        f"{sum(entries) / 2**20:.0f} MiB, max_size "
        f"{jax.config.jax_compilation_cache_max_size}")
    devices = jax.devices()
    if devices[0].platform != platform:
        raise RuntimeError(
            f"jax.devices()[0].platform is {devices[0].platform!r}, "
            f"need {platform!r}: no result without the accelerator"
        )
    if len(devices) < chips:
        raise RuntimeError(f"cell needs {chips} chip(s), JAX sees {len(devices)}")
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peaks() -> tuple[int, int]:
    """(peak bytes in use, peak bytes reserved) on the fullest chip, as
    ``memory_stats()`` gives them. On this runtime the buffers of arrays
    are "in use"; the temporaries of an executing program are
    "reserved" and not part of the first number."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return (
        int(max(s.get("peak_bytes_in_use", 0) for s in stats)),
        int(max(s.get("peak_bytes_reserved", 0) for s in stats)),
    )


# ---- worker ------------------------------------------------------------------


async def start_worker(platform: str, workspace: Path, n_clients: int):
    """Returns (worker, admin connection, client connections, worker sid)."""
    from bioengine_tpu.native import store as native_store
    from bioengine_tpu.rpc.client import connect_to_server
    from bioengine_tpu.worker.__main__ import (
        create_parser,
        worker_kwargs_from_args,
    )
    from bioengine_tpu.worker.worker import BioEngineWorker

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
    if topology["platform"] != platform:
        raise RuntimeError(f"cluster topology is {topology['platform']}")
    if not native_store.native_available():
        raise RuntimeError("native object store did not build (shm fast path)")
    server = {
        "server_url": endpoints["rpc_url"],
        "token": (workspace / "admin_token").read_text(),
    }
    admin = await connect_to_server(server)
    clients = [await connect_to_server(server) for _ in range(n_clients)]
    return worker, admin, clients, endpoints["service_id"]


def shm_store_name() -> str:
    import hashlib

    return "bench-" + hashlib.sha1(str(REPO).encode()).hexdigest()[:12]


def remove_shm_store() -> None:
    Path("/dev/shm", shm_store_name()).unlink(missing_ok=True)


async def deploy(admin, worker_sid: str, app_dir: Path, deployment_kwargs: dict):
    """Returns (app_id, app service id). The app is deployed as shipped;
    ``deployment_kwargs`` are the path's (where its package lies)."""
    result = await admin.call(
        worker_sid,
        "deploy_app",
        local_path=str(app_dir),
        deployment_kwargs=deployment_kwargs,
    )
    app_id = result["app_id"]
    deadline = time.monotonic() + 300
    while True:
        status = await admin.call(worker_sid, "get_app_status", app_id)
        states = {
            name: [r["state"] for r in dep["replicas"]]
            for name, dep in status["deployments"].items()
        }
        if all(s and set(s) == {"HEALTHY"} for s in states.values()):
            return app_id, result["service_id"]
        if time.monotonic() > deadline:
            raise RuntimeError(f"deployment not HEALTHY: {states}")
        await asyncio.sleep(0.1)


async def engine_replicas(admin, worker_sid: str, app_id: str, engines: str) -> list:
    """The replicas of the deployment that holds the engines, as the
    path names it."""
    status = await admin.call(worker_sid, "get_app_status", app_id)
    return status["deployments"][engines]["replicas"]


async def read_counters(admin, worker_sid: str, app_id: str, engines: str) -> dict:
    """The registry's families and the engines' pipeline stats, summed
    over the replicas that hold the engines."""
    families = await admin.call(worker_sid, "get_metrics")
    pipeline: dict[str, float] = {}
    for replica in await engine_replicas(admin, worker_sid, app_id, engines):
        for stats in (replica.get("pipeline_stats") or {}).values():
            for key, value in stats.items():
                if isinstance(value, (int, float)):
                    pipeline[key] = pipeline.get(key, 0) + value
    return {"families": families, "pipeline": pipeline, "at": time.perf_counter()}


async def program_facts(admin, worker_sid: str, app_id: str, engines: str) -> dict:
    """Seconds each engine program took to obtain, and whether the
    persistent cache had it (information for ``setup_s``)."""
    facts = {}
    for replica in await engine_replicas(admin, worker_sid, app_id, engines):
        for engine in (replica.get("mesh") or {}).get("engines", {}).values():
            programs = engine.get("programs") or {}
            for key, seconds in (programs.get("compile_seconds") or {}).items():
                facts[key] = [seconds, bool(programs["cache_hits"][key])]
    return facts


def family_sum(families: dict, name: str, **labels) -> Optional[float]:
    series = [
        s for s in families.get(name, {}).get("series", [])
        if all(s.get("labels", {}).get(k) == v for k, v in labels.items())
        and "value" in s
    ]
    return sum(s["value"] for s in series) if series else None


# ---- profiler ----------------------------------------------------------------


def start_trace(trace_dir: Path, host_level: int) -> None:
    """The process's one profiler (the worker's ``start_profiling`` verb
    starts the same one, with its defaults). The Python tracer is off:
    hooking every Python call of a Python server slows the very window
    it measures. ``host_level`` 0 records the device alone and is what
    the measured window gets: at level 2 the runtime logged 1.3 million
    host events in 8 s, and even at level 1 the host's transfer code
    (``XlaLinearize``) ran three times slower than untraced, which cost
    the device 15 points of busy share. The labelling traces of lone
    requests, outside the window, take level 1 and name the host's work."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = host_level
    trace_dir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


# ---- the run -----------------------------------------------------------------


@dataclasses.dataclass
class RunData:
    """What the per-layer readers read."""

    cell: Cell
    seconds: float
    window: tuple[float, float]
    requests: list[dict]                 # every request of the run
    counters: dict[str, dict]            # "start" / "end" of the window
    compiles_in_window: int
    trace: Optional[dict] = None         # see ``traced``

    @property
    def in_window(self) -> list[dict]:
        """Requests that completed inside the window, and those sent
        before its close that failed, the lead-in's too, whenever the
        failure showed."""
        lo, hi = self.window
        return [
            r for r in self.requests
            if lo <= r["end"] <= hi or (not r["ok"] and r["start"] <= hi)
        ]

    def counter_delta(self, name: str) -> Optional[float]:
        a = family_sum(self.counters["start"]["families"], name)
        b = family_sum(self.counters["end"]["families"], name)
        return None if a is None or b is None else b - a

    def pipeline_delta(self, key: str) -> Optional[float]:
        a = self.counters["start"]["pipeline"].get(key)
        b = self.counters["end"]["pipeline"].get(key)
        return None if a is None or b is None else b - a


def per_layer(run: RunData) -> dict[str, float]:
    out = {}
    for name in run.cell.per_layer:
        reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        value = reader.read(run)
        if value is not None:
            out[name] = float(value)
    return out


# ---- correctness -------------------------------------------------------------


def draw_sample(plan, per_size: int, seed: int) -> list[tuple[int, int]]:
    """The requests ``correct`` compares: ``per_size`` of every kind,
    each a (client, position in its deck) drawn from the seed before the
    run. Every kind is in it, so the largest is; and two runs of one seed
    compare the same inputs, however their replies were timed (the last
    reply a client got may be of another request in a faster run)."""
    rng = np.random.default_rng(abs(int(seed)) + 1)
    items: dict[Any, list[tuple[int, int]]] = {}
    for c, deck in enumerate(plan.clients):
        for position, request in enumerate(deck):
            items.setdefault(request.kind, []).append((c, position))
    return [
        items[kind][i]
        for kind in sorted(items)
        for i in rng.permutation(len(items[kind]))[:per_size]
    ]


def not_comparable(limits: dict[str, float]) -> dict[str, float]:
    """What a check reads where nothing can be compared: a reply of the
    wrong shape, a value that is not finite, no reply at all."""
    return dict.fromkeys(limits, NOT_COMPARABLE)


def judge(readings: dict[str, float], limits: dict[str, float], n: int) -> dict:
    """name -> [number, limit]; ``compared`` -> how many replies."""
    checks: dict[str, Any] = {
        name: [readings[name], limits[name]] for name in sorted(limits)
    }
    checks["compared"] = n
    return checks


def is_correct(checks: dict) -> bool:
    pairs = [v for v in checks.values() if isinstance(v, list)]
    return checks["compared"] > 0 and all(
        np.isfinite(value) and value <= limit for value, limit in pairs
    )


# ---- what held a run up -------------------------------------------------------


class StallWatch(threading.Thread):
    """Tells a stall of the event loop from one of the whole process or
    machine. The loop's heartbeat stamps ``beat``; this thread wakes
    every 100 ms. Where it wakes late itself, everything stood still;
    where only the loop's stamp is old, the loop's thread is busy and
    its stack says with what. Nothing is printed before the window has
    closed."""

    PERIOD = 0.1
    LATE = 0.3

    def __init__(self, loop_thread: int) -> None:
        super().__init__(name="bench-stall-watch", daemon=True)
        self.loop_thread = loop_thread
        self.beat = time.perf_counter()
        self.found: list[tuple[float, str, float, str]] = []
        self._halt = threading.Event()

    def run(self) -> None:
        last = time.perf_counter()
        seen = 0.0
        while not self._halt.wait(self.PERIOD):
            now = time.perf_counter()
            if now - last - self.PERIOD > self.LATE:
                self.found.append((last, "process", now - last - self.PERIOD, ""))
            elif self.beat == seen:
                # still the stall found before: it lasts this long by now
                self.found[-1] = (seen, "loop", now - seen, self.found[-1][3])
            elif now - self.beat > self.LATE + 0.05:
                seen = self.beat
                frame = sys._current_frames().get(self.loop_thread)
                stack = " < ".join(
                    f"{Path(f.filename).name}:{f.lineno} {f.name}"
                    for f in reversed(traceback.extract_stack(frame)[-6:])
                ) if frame else ""
                self.found.append((seen, "loop", now - seen, stack))
            last = now

    def close(self) -> list[tuple[float, str, float, str]]:
        self._halt.set()
        self.join()
        return self.found[:12]


# ---- one run -----------------------------------------------------------------


async def serve_window(
    cell: Cell, seed: int, seconds: float, trace: bool, platform: str,
    out_dir: Path, t_process_start: float, compile_counter: CompileCounter,
) -> tuple[RunData, float, list, dict, tuple[int, int]]:
    """Set-up, warm-up and the measured window. Returns (run data,
    setup_s, the last reply of each request of the sample, ``None``
    where none came, the input pool, memory peaks)."""
    path = cell.path
    generator = importlib.import_module(
        f"benchmarks.generators.{cell.traffic['generator']}"
    )
    os.environ.update(operator_env(cell))
    # the RPC plane's shared-memory segment outlives its process and the
    # next one attaches to it as it was left: a name of this checkout's
    # own, removed before the run and after it
    os.environ["BIOENGINE_RPC_STORE_NAME"] = shm_store_name()
    remove_shm_store()
    package = path.make_package(cell.config, seed, out_dir / "package")
    plan = generator.plan(cell.traffic, cell.config, seed)
    log(f"package written, plan drawn at {time.perf_counter() - t_process_start:.1f}s")

    worker, admin, clients, worker_sid = await start_worker(
        platform, out_dir / "workspace", len(plan.clients)
    )
    requests: list[dict] = []
    # the last reply of each request of the sample, by (client, position)
    drawn = draw_sample(plan, int(cell.traffic["check_per_size"]), seed)
    kept: dict[tuple[int, int], dict] = {}
    try:
        app_id, app_sid = await deploy(
            admin, worker_sid, REPO / cell.config["deployment"]["app"],
            path.deployment_kwargs(package),
        )
        log(f"deployed at {time.perf_counter() - t_process_start:.1f}s")

        def perform(conn, payload, sample_id: str):
            return path.perform(conn, app_sid, package, plan, payload, sample_id)

        async def send(c: int, n: int, request) -> dict:
            payload = path.payload(plan, request)
            start = time.perf_counter()
            # the path's own keys first: its unit of work among them
            record = {
                **path.describe(plan, request),
                "client": c, "kind": request.kind, "start": start, "ok": False,
            }
            try:
                # an answer that comes late is late, not lost: wait a
                # minute past the close for it, then count it as failed
                reply = await asyncio.wait_for(
                    perform(clients[c], payload, f"c{c}-{n}"), seconds + 60.0
                )
                output = reply.pop("output")
                # end, ok, server_ms, and what a streamed path stamped
                record.update(reply)
                item = (c, n % len(plan.clients[c]))
                if item in drawn:
                    kept[item] = {
                        "kind": request.kind, "request": request, "output": output,
                    }
            except Exception as exc:  # noqa: BLE001 — counted, never hidden
                record["end"] = time.perf_counter()
                record["error"] = f"{type(exc).__name__}: {exc}"[:300]
                log(f"request c{c}-{n} failed: {record['error']}")
            record["latency_ms"] = (record["end"] - start) * 1000.0
            requests.append(record)
            return record

        # warm-up: one lone request per program the mix can form; the
        # clients' lead-in, before the window opens, is the rest of it
        programs = path.programs(cell)
        rng = np.random.default_rng(abs(int(seed)) + 2)
        lone = {}
        for key, request in programs.items():
            lone[key] = path.lone_payload(cell, key, request, rng)
            reply = await perform(admin, lone[key], f"warm-{key[0]}")
            if not reply["ok"]:
                raise RuntimeError(f"the warm-up request of program {key} failed")
        log(f"programs warm {sorted(programs)} at "
            f"{time.perf_counter() - t_process_start:.1f}s: "
            + json.dumps(await program_facts(admin, worker_sid, app_id, path.ENGINES)))
        traced: Optional[dict] = None

        async def profile_part() -> None:
            """Traces a span in the middle of the window. The span is
            taken on the host's clocks: ``perf_counter`` for the
            requests, and the wall clock, which the trace's own start is
            stamped with, for the device's operations."""
            nonlocal traced
            span = min(TRACED_SECONDS, seconds / 2)
            lead = min(TRACE_LEAD_SECONDS, seconds / 8)
            await asyncio.sleep((seconds - span) / 2 - lead)
            await asyncio.to_thread(
                start_trace, out_dir / "trace" / "window", 0
            )
            await asyncio.sleep(lead)
            t0, wall0 = time.perf_counter(), time.time_ns()
            await asyncio.sleep(span)
            t1, wall1 = time.perf_counter(), time.time_ns()
            await asyncio.to_thread(stop_trace)
            traced = {
                "dir": out_dir / "trace" / "window",
                "host_window": (t0, t1), "wall_window": (wall0, wall1),
            }

        late = (0.0, 0.0)

        async def heartbeat() -> None:
            """How late this process's event loop (the clients' and the
            worker's) woke from a 50 ms sleep, at worst, and when: tells
            a stall of the whole process from one of the engine or the
            device."""
            nonlocal late
            while True:
                t = watch.beat = time.perf_counter()
                await asyncio.sleep(0.05)
                late = max(late, (time.perf_counter() - t - 0.05, t))

        watch = StallWatch(threading.get_ident())
        opened: dict[str, Any] = {}

        async def open_window() -> None:
            opened["compiles"] = compile_counter.count
            opened["beat"] = asyncio.create_task(heartbeat())
            watch.start()
            # only the chip has a device plane to trace; the CPU
            # rehearsal still reads the counters
            if trace and platform == "tpu":
                opened["profiler"] = asyncio.create_task(profile_part())
            opened["counters"] = await read_counters(
                admin, worker_sid, app_id, path.ENGINES
            )

        window = await generator.drive(plan, send, seconds, open_window)
        opened["beat"].cancel()
        stalls = watch.close()
        if "profiler" in opened:
            await opened["profiler"]
        setup_s = window[0] - t_process_start
        ends = sorted(r["end"] for r in requests if window[0] <= r["end"] <= window[1])
        gap, at = max(
            zip(np.diff([window[0], *ends, window[1]]), [window[0], *ends])
        )
        log(f"window: longest time without a reply {gap:.2f}s from "
            f"{at - window[0]:.1f}s; event loop at most {late[0] * 1e3:.0f} ms "
            f"late, at {late[1] - window[0]:.1f}s "
            f"({late[1] - t_process_start:.1f}s after the process started)")
        for at, what, held, stack in stalls:
            log(f"window: {what} stood still {held:.2f}s from "
                f"{at - window[0]:.1f}s {stack}")
        end_counters = await read_counters(admin, worker_sid, app_id, path.ENGINES)
        compiles = compile_counter.count - opened["compiles"]
        peak = memory_peaks()

        if traced is not None:
            # which program is which: one lone request per program, each
            # in a trace of its own, names read off its "XLA Modules"
            from benchmarks import trace_reduce

            names: dict[str, tuple] = {}
            for key in programs:
                label_dir = out_dir / "trace" / ("label-" + "x".join(map(str, key)))
                start_trace(label_dir, host_level=1)
                sent = time.time_ns()
                await perform(admin, lone[key], "label")
                replied = time.time_ns()
                stop_trace()
                reduced = trace_reduce.reduce(trace_reduce.find_xplane(label_dir))
                # the two clocks against each other: the device's work
                # for a lone request lies between its send and its reply
                log(f"clocks: lone request sent at {reduced.at(sent) / 1e6:.1f} ms "
                    f"of its trace, device busy {reduced.lo / 1e6:.1f} to "
                    f"{reduced.hi / 1e6:.1f} ms, replied at "
                    f"{reduced.at(replied) / 1e6:.1f} ms")
                for name in reduced.module_seconds():
                    # a name two programs share tells nothing
                    names[name] = () if name in names else key
                # the largest program's lone request names the host's work
                if key == max(programs):
                    traced["lone_request"] = reduced
            traced["programs"] = {n: k for n, k in names.items() if k}
    finally:
        for conn in [admin, *clients]:
            await conn.disconnect()
        await worker.stop()
        remove_shm_store()

    run = RunData(
        cell=cell, seconds=seconds, window=window,
        requests=requests,
        counters={"start": opened["counters"], "end": end_counters},
        compiles_in_window=int(compiles), trace=traced,
    )
    return run, setup_s, [kept.get(item) for item in drawn], plan.pool, peak


def reduce_window_trace(run: RunData, device: dict) -> dict:
    """Reduces the window's trace into ``run.trace`` (for the readers),
    adds ``busy_s``/``window_s`` to ``device`` and returns the breakdown."""
    from benchmarks import trace_reduce

    t_reduce = time.perf_counter()
    reduced = trace_reduce.reduce(trace_reduce.find_xplane(run.trace["dir"]))
    # the profiler runs from some seconds before the traced span until
    # its stop has gone through: count what the device did inside the
    # span the host took, laid onto the trace by the wall clock
    wall0, wall1 = run.trace["wall_window"]
    span = (reduced.at(wall0), reduced.at(wall1))
    run.trace.update(reduced=reduced, span=span, peaks=peaks_for(device["kind"]))
    device["busy_s"] = reduced.busy_s(span)
    device["window_s"] = (wall1 - wall0) / 1e9
    # the window's trace holds no host events (level 0), so its idle
    # seconds stand whole; beside them, what the host spent on one lone
    # request of the largest program, by the runtime's own event names
    # (nested events each count their own time)
    lone = run.trace["lone_request"]
    breakdown = {
        "device_ops": reduced.top_ops(10, span),
        "idle_gaps": (
            [["window: " + name, s] for name, s in reduced.idle_gaps(1, span)]
            + [["lone request, host: " + name, s] for name, s in lone.host_seconds(9)]
        ),
    }
    log(f"trace reduced in {time.perf_counter() - t_reduce:.1f}s")
    return breakdown


def run_cell(
    cell: Cell, seed: int, seconds: float, trace: bool,
    platform: str = "tpu", out_dir: Optional[Path] = None,
    t_process_start: Optional[float] = None,
) -> dict:
    """The whole run; returns the result line as a dict. ``platform`` is
    for the CPU rehearsal in the tests: the command line has no such
    argument."""
    t_process_start = t_process_start or time.perf_counter()
    out_dir = out_dir or REPO / ".cache" / "bench" / cell.name
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    device = device_gate(platform, cell.chips)
    compile_counter = CompileCounter()
    try:
        run, setup_s, replies, pool, peak = asyncio.run(
            serve_window(
                cell, seed, seconds, trace, platform, out_dir,
                t_process_start, compile_counter,
            )
        )
    finally:
        compile_counter.close()
    device = {
        **device, "memory_peak_bytes": peak[0],
        "memory_reserved_peak_bytes": peak[1],
    }
    done = run.in_window
    log(f"window closed: {len(done)} requests, setup {setup_s:.1f}s, "
        f"peak {peak[0] / 1e9:.2f} GB in use + {peak[1] / 1e9:.2f} GB reserved, "
        f"compiles in window {run.compiles_in_window}")

    result: dict[str, Any] = {
        "attempted": len(done),
        "failed": sum(not r["ok"] for r in done),
    }
    breakdown = None
    if run.trace is not None:
        breakdown = reduce_window_trace(run, device)
    if trace:
        metrics = per_layer(run)
    else:
        metrics = {
            k: v for k, v in window_metrics.end_to_end(run, setup_s).items()
            if k in cell.end_to_end
        }
    result["metrics"] = {
        name: {"value": value, "unit": cell.units.get(name, "")}
        for name, value in metrics.items()
    }
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    shutil.rmtree(out_dir / "package", ignore_errors=True)

    # the program's state is freed (worker stopped): now the reference
    t_check = time.perf_counter()
    sample = [entry for entry in replies if entry is not None]
    limits = cell.config["limits"]
    # a request of the sample that was never answered is not comparable
    readings = (
        cell.path.compare(cell, seed, sample, pool)
        if sample and len(sample) == len(replies)
        else not_comparable(limits)
    )
    checks = judge(readings, limits, len(sample))
    log(f"reference over {len(sample)} replies took "
        f"{time.perf_counter() - t_check:.1f}s")
    ordered = {"correct": is_correct(checks), **result, "checks": checks}
    log("checks " + json.dumps(checks))
    return ordered


def lingering_threads() -> list[str]:
    return [
        t.name
        for t in threading.enumerate()
        if t is not threading.main_thread() and not t.daemon and t.is_alive()
    ]
