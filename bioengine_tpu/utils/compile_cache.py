"""Persistent XLA compilation cache + the shared compile-cache tier.

XLA compiles of the production models cost 20-40 s each on TPU — the
dominant cold-start cost for serving replicas and the dominant wall
cost of the benchmark (SURVEY.md: the reference's torch path has no
analog; compiled-program caching is a TPU-specific concern). JAX ships
a persistent cache keyed on (HLO, compiler version, device kind);
enabling it makes every repeat compile — a replica restart, the
benchmark's second run on the same machine — a disk read instead of a
compile.

The cache directory is per-machine. At production churn (autoscale,
preempted TPUs) a FRESH host has an empty directory and pays the full
compile anyway — so this module also speaks the **shared tier**
protocol: entry files (named exactly as jax names them,
``jit_<fn>-<key>-cache``) are enumerated, read, and written atomically
so a worker host can fetch the fleet's already-compiled programs from
the controller's tier at join time and publish its own compiles back
(worker_host.py drives the RPC side; serving/compile_tier.py holds the
controller-side store). Only ``*-cache`` payload files ride the tier —
``*-atime`` bookkeeping files are local-only.

Where the cache lives is decided from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
module sets no directory at all; otherwise the cache sits at one fixed
path inside the checkout (``<repo>/.cache/xla``, git-ignored). The path
is part of what makes a cache reusable across runs, so it is never
built from a temp name, a pid or a time. A cache that cannot be enabled
raises — a worker that silently recompiles every program on every
start is not a degraded mode worth hiding.
"""

from __future__ import annotations

import logging
import os
import tempfile
from pathlib import Path
from typing import Optional

from bioengine_tpu.utils import metrics

logger = logging.getLogger(__name__)

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".cache" / "xla"
_enabled_dir: str | None = None

# the suffix jax gives entry payload files; its sibling "-atime" files
# are local LRU bookkeeping and never ride the tier
CACHE_SUFFIX = "-cache"

TIER_FETCHES = metrics.counter(
    "compile_tier_fetches_total",
    "compile-cache entries fetched from the shared tier",
)
TIER_PUBLISHES = metrics.counter(
    "compile_tier_publishes_total",
    "compile-cache entries published to the shared tier",
)
TIER_FETCH_BYTES = metrics.counter(
    "compile_tier_fetch_bytes_total",
    "bytes of compiled programs fetched from the shared tier",
)
TIER_PUBLISH_BYTES = metrics.counter(
    "compile_tier_publish_bytes_total",
    "bytes of compiled programs published to the shared tier",
)


def enable_persistent_compilation_cache(path: str | None = None) -> str:
    """Turn on jax's persistent compilation cache and return its
    directory. ``$JAX_COMPILATION_CACHE_DIR`` wins over ``path`` wins
    over the in-checkout default; with the variable set this function
    sets no directory (jax already read it). Idempotent: the first
    call's directory is the process's directory. Raises ``OSError``
    when the directory cannot be created.
    """
    global _enabled_dir
    if _enabled_dir is not None:
        return _enabled_dir
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    target = Path(env or path or DEFAULT_CACHE_DIR).expanduser()
    target.mkdir(parents=True, exist_ok=True)
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(target))
        # jax latches "is the cache used" at the process's first
        # compile; one that ran before this call latched "no"
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    # default min-compile-time (1 s) skips exactly the small jits a
    # serving replica re-traces most; cache everything non-trivial
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    # jax colocates XLA's GPU autotune cache under the compilation
    # cache dir by default — and that PATH lands in the compile-cache
    # key, so two hosts with different local dirs compute different
    # keys for the same program and the shared tier can never hit.
    # Disable the colocated GPU sub-caches (irrelevant on TPU/CPU) so
    # keys are path-independent.
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    _enabled_dir = str(target)
    logger.info("persistent XLA compilation cache at %s", target)
    return _enabled_dir


def enabled_dir() -> Optional[str]:
    """The active cache dir, or None before the cache is enabled."""
    return _enabled_dir


def reset_for_tests() -> None:
    """Forget the enabled directory so a test can enable another."""
    global _enabled_dir
    _enabled_dir = None


# ---- tier entry I/O (file-level; the RPC side lives in worker_host /
# serving/compile_tier.py) -------------------------------------------------


def list_entries(directory: str | Path | None = None) -> dict[str, int]:
    """``{entry_name: size_bytes}`` of the cache payload files under
    ``directory`` (default: the enabled cache dir). Entry names are
    exactly jax's on-disk keys, so two hosts agree on identity without
    any re-hashing."""
    d = Path(directory) if directory else (
        Path(_enabled_dir) if _enabled_dir else None
    )
    if d is None or not d.is_dir():
        return {}
    out: dict[str, int] = {}
    try:
        for p in d.iterdir():
            if p.name.endswith(CACHE_SUFFIX) and p.is_file():
                out[p.name] = p.stat().st_size
    except OSError:
        return {}
    return out


def read_entry(name: str, directory: str | Path | None = None) -> Optional[bytes]:
    """Read one cache entry's bytes, or None when absent/unreadable.
    ``name`` must be a bare entry filename (path components rejected —
    these names cross the RPC plane)."""
    d = Path(directory) if directory else (
        Path(_enabled_dir) if _enabled_dir else None
    )
    if d is None or not _safe_entry_name(name):
        return None
    p = d / name
    try:
        return p.read_bytes()
    except OSError:
        return None


def write_entry(
    name: str, blob: bytes, directory: str | Path | None = None
) -> bool:
    """Atomically install one fetched cache entry (temp file + rename,
    so jax never reads a half-written program). Returns False when the
    entry already exists, the name is unsafe, or the FS refuses."""
    d = Path(directory) if directory else (
        Path(_enabled_dir) if _enabled_dir else None
    )
    if d is None or not _safe_entry_name(name):
        return False
    target = d / name
    if target.exists():
        return False
    try:
        d.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=str(d), prefix=".tier-")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, target)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return True
    except OSError as exc:
        logger.debug("tier entry %s not installed: %s", name, exc)
        return False


def _safe_entry_name(name: str) -> bool:
    """Entry names cross the RPC plane: refuse anything that is not a
    bare jax cache filename (no separators, no dotfiles, right suffix)."""
    return (
        bool(name)
        and "/" not in name
        and "\\" not in name
        and not name.startswith(".")
        and name.endswith(CACHE_SUFFIX)
        and len(name) < 512
    )
