"""Compiled-program cache — the TPU equivalent of the reference's
multiplexed prediction-pipeline cache (ref apps/model-runner/
runtime_deployment.py:160-232, which LRU-caches torch pipelines keyed on
an md5 of model kwargs).

Here the cached object is an XLA executable: ``jit(fn)`` lowered and
compiled for a concrete (shape-bucket, dtype, mesh) key. Keys are
explicit so eviction, stats, and warm-up are controllable — unlike
jax's implicit compilation cache, whose entries can't be enumerated or
evicted per-model.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Hashable, Optional

from bioengine_tpu.ops import attention as _attention, mlp as _mlp
from bioengine_tpu.utils import flight, metrics
from bioengine_tpu.utils import compile_cache as _compile_cache


def _persistent_cache_on() -> bool:
    return _compile_cache.enabled_dir() is not None


def _hit_threshold_s() -> float:
    """Sanity bound on the hit verdict: even when build() wrote no new
    persistent-cache entry, a build slower than this is reported as a
    real compile. The primary signal is the entry write (a real compile
    persists a new file, a disk/tier hit writes nothing), so this only
    needs to exclude pathological cases — default 5 s sits far under a
    TPU compile (20-40 s) and far over a disk hit (<1 s)."""
    import os

    return float(os.environ.get("BIOENGINE_COMPILE_HIT_THRESHOLD_S", "5"))


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    # misses whose build() came back near-instantly while the jax
    # persistent compilation cache was enabled: a disk/tier hit, not a
    # real compile — without the tag a warm replica's "compile" and a
    # cold one's are indistinguishable in describe()/flight
    persistent_hits: int = 0
    # per-key compile time for LIVE entries only — evicted programs'
    # entries are dropped with them (a long-lived replica cycling
    # through shapes would otherwise grow this dict forever)
    compile_seconds: dict = field(default_factory=dict)
    # per-key cache_hit verdict, same lifecycle as compile_seconds
    cache_hit: dict = field(default_factory=dict)
    # per-key attention calls traced into the program while it was
    # built, by path (``{"packed:1024": 24}``: attention_traced_total's
    # rise over build()), same lifecycle. A served cpsam program that
    # reads ``xla`` on a TPU is running without its kernel.
    attention_paths: dict = field(default_factory=dict)
    # the same for the block MLPs (``{"fused:16384": 24}``:
    # mlp_traced_total's rise over build()), same lifecycle
    mlp_paths: dict = field(default_factory=dict)
    # lifetime total, survives evictions
    cumulative_compile_seconds: float = 0.0

    def forget(self, key: str) -> None:
        """Drop an evicted program's per-key entries."""
        for per_key in (
            self.compile_seconds, self.cache_hit,
            self.attention_paths, self.mlp_paths,
        ):
            per_key.pop(key, None)

    def as_dict(self) -> dict:
        total = self.hits + self.misses
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "persistent_hits": self.persistent_hits,
            "hit_rate": self.hits / total if total else 0.0,
            "total_compile_seconds": self.cumulative_compile_seconds,
            "live_compile_seconds": sum(self.compile_seconds.values()),
        }


def _collect_program_caches(instances: list) -> list:
    """Scrape-time fold of live program caches into process metrics:
    compile time is the cold-start cost (ROADMAP item 3) and the reason
    a request's p99 suddenly grows a 30 s tail — it belongs on the
    dashboard next to the latency histograms it explains."""
    hits = misses = evictions = persistent = 0
    compile_s = 0.0
    live = 0
    for c in instances:
        s = c.stats
        hits += s.hits
        misses += s.misses
        evictions += s.evictions
        persistent += s.persistent_hits
        compile_s += s.cumulative_compile_seconds
        live += len(c)
    return [
        metrics.Sample(
            "program_cache_hits_total", hits, kind="counter",
            help="compiled-program cache hits",
        ),
        metrics.Sample(
            "program_cache_misses_total", misses, kind="counter",
            help="compiled-program cache misses (each cost a compile)",
        ),
        metrics.Sample(
            "program_cache_evictions_total", evictions, kind="counter",
            help="compiled programs evicted (a re-request recompiles)",
        ),
        metrics.Sample(
            "program_cache_compile_seconds_total", round(compile_s, 6),
            kind="counter",
            help="lifetime XLA compile seconds across caches",
        ),
        metrics.Sample(
            "program_cache_persistent_hits_total", persistent,
            kind="counter",
            help="misses satisfied by the persistent/tier cache "
            "(near-zero compile), not a real XLA compile",
        ),
        metrics.Sample(
            "program_cache_live_programs", live,
            help="compiled programs currently cached",
        ),
    ]


_PROGRAM_CACHES = metrics.InstanceSet(
    "program_cache", _collect_program_caches
)


class CompiledProgramCache:
    """Bounded LRU of compiled XLA programs.

    ``get_or_compile(key, build)`` — ``build()`` must return the callable
    to cache (typically ``jax.jit(fn).lower(*args).compile()`` or a plain
    jitted fn). Thread-safe: concurrent misses on the same key compile
    once; other callers wait.
    """

    def __init__(self, max_programs: int = 32):
        self.max_programs = max_programs
        self._programs: OrderedDict[Hashable, Any] = OrderedDict()
        self._building: dict[Hashable, threading.Event] = {}
        self._lock = threading.Lock()
        self.stats = CacheStats()
        _PROGRAM_CACHES.add(self)

    def get_or_compile(self, key: Hashable, build: Callable[[], Any]) -> Any:
        while True:
            with self._lock:
                if key in self._programs:
                    self._programs.move_to_end(key)
                    self.stats.hits += 1
                    return self._programs[key]
                ev = self._building.get(key)
                if ev is None:
                    self._building[key] = threading.Event()
                    break
            ev.wait()
        try:
            cache_dir = _compile_cache.enabled_dir()
            before = (
                set(_compile_cache.list_entries(cache_dir))
                if cache_dir
                else None
            )
            traced_before = (_attention.traced_paths(), _mlp.traced_paths())
            t0 = time.perf_counter()
            program = build()
            dt = time.perf_counter() - t0
            # another thread tracing a model meanwhile would be counted
            # here too; builds are rare enough for that to be an anomaly
            # worth seeing, not one worth a lock around tracing
            attention = _attention.traced_paths(since=traced_before[0])
            mlps = _mlp.traced_paths(since=traced_before[1])
            # Tag disk/tier hits apart from real compiles. Primary
            # signal: a REAL compile persists a new cache entry while a
            # hit writes nothing (wall time alone can't separate them —
            # a loaded CPU traces slower than a TPU disk-reads). The
            # env-tunable threshold is only a sanity bound on top;
            # foreign entries written concurrently by another engine
            # can at worst demote a hit to "real" (conservative).
            if before is not None:
                wrote_new = bool(
                    set(_compile_cache.list_entries(cache_dir)) - before
                )
                cache_hit = not wrote_new and dt < _hit_threshold_s()
            else:
                cache_hit = False
            evicted = []
            with self._lock:
                self.stats.misses += 1
                if cache_hit:
                    self.stats.persistent_hits += 1
                self.stats.compile_seconds[str(key)] = dt
                self.stats.cache_hit[str(key)] = cache_hit
                self.stats.attention_paths[str(key)] = attention
                self.stats.mlp_paths[str(key)] = mlps
                self.stats.cumulative_compile_seconds += dt
                self._programs[key] = program
                self._programs.move_to_end(key)
                while len(self._programs) > self.max_programs:
                    victim, _ = self._programs.popitem(last=False)
                    self.stats.forget(str(victim))
                    self.stats.evictions += 1
                    evicted.append(victim)
            flight.record(
                "program.compile",
                key=str(key),
                seconds=round(dt, 3),
                cache_hit=cache_hit,
                attention_paths=attention,
                mlp_paths=mlps,
            )
            for victim in evicted:
                flight.record("program.evict", key=str(victim))
            return program
        finally:
            with self._lock:
                self._building.pop(key).set()

    def compile_seconds_snapshot(self) -> dict:
        """Copy of per-key compile seconds under the cache lock —
        readers (engine.describe) must not iterate the live dict while
        a compile on the dispatch thread inserts/evicts."""
        with self._lock:
            return dict(self.stats.compile_seconds)

    def compile_info_snapshot(self) -> dict:
        """Per-key ``{"seconds": s, "cache_hit": bool, "attention_paths":
        {...}, "mlp_paths": {...}}`` under the cache lock — the
        describe() view that tells a tier/disk hit apart from a real
        compile, and a program with its fused kernels from one
        without."""
        with self._lock:
            return {
                k: {
                    "seconds": v,
                    "cache_hit": bool(self.stats.cache_hit.get(k, False)),
                    "attention_paths": dict(
                        self.stats.attention_paths.get(k, {})
                    ),
                    "mlp_paths": dict(self.stats.mlp_paths.get(k, {})),
                }
                for k, v in self.stats.compile_seconds.items()
            }

    def stats_dict(self) -> dict:
        """``stats.as_dict()`` under the cache lock (it sums the live
        compile_seconds dict, which mutates under this lock)."""
        with self._lock:
            return self.stats.as_dict()

    def get(self, key: Hashable) -> Optional[Any]:
        with self._lock:
            if key in self._programs:
                self._programs.move_to_end(key)
                self.stats.hits += 1
                return self._programs[key]
        return None

    def evict(self, predicate: Callable[[Hashable], bool]) -> int:
        """Evict all entries whose key matches (e.g. one model's programs)."""
        with self._lock:
            victims = [k for k in self._programs if predicate(k)]
            for k in victims:
                del self._programs[k]
                self.stats.forget(str(k))
            self.stats.evictions += len(victims)
        for k in victims:
            flight.record("program.evict", key=str(k))
        return len(victims)

    def keys(self) -> list[Hashable]:
        with self._lock:
            return list(self._programs)

    def __len__(self) -> int:
        with self._lock:
            return len(self._programs)


# Process-wide default, shared by inference engines in one replica.
default_program_cache = CompiledProgramCache()
