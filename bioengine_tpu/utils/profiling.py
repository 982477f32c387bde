"""jax.profiler wrappers shared by the worker and worker-host verbs.

One copy of the guard / mkdir / start_trace / stop_trace /
device-memory-snapshot logic — the two serving surfaces differ only in
permission checks and response stamping (host_id). jax.profiler is
process-global: one trace at a time per process.
"""

from __future__ import annotations

import time
from pathlib import Path
from typing import Optional


def start_trace(
    workspace_dir,
    trace_dir: Optional[str],
    active: Optional[str],
    host_tracer_level: int = 1,
    python_tracer_level: int = 0,
) -> str:
    """Start a jax.profiler trace; returns the trace dir. ``active``
    is the caller's currently-active dir (None when idle) — a second
    start raises instead of silently nesting.

    The defaults keep a serving process serving: host level 1 holds the
    program's own stage annotations (``engine.*``, ``runtime.*``,
    utils/tracing.py) and the runtime's transfer events; the Python
    tracer is off. jax's own defaults (host level 2, Python tracer on)
    recorded 1.3 million host events in 8 s on a serving v5e and cost a
    quarter of its requests. Level 0 traces the device alone."""
    import jax

    if active:
        raise RuntimeError(f"profiling already active -> {active}")
    trace_dir = trace_dir or str(
        Path(workspace_dir) / "profiles" / time.strftime("%Y%m%d-%H%M%S")
    )
    Path(trace_dir).mkdir(parents=True, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = int(host_tracer_level)
    options.python_tracer_level = int(python_tracer_level)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    return trace_dir


def stop_trace(active: Optional[str]) -> str:
    """Stop the active trace; returns its dir (raises when idle)."""
    import jax

    if not active:
        raise RuntimeError("profiling is not active")
    jax.profiler.stop_trace()
    return active


def device_memory_snapshot() -> dict:
    """Device-memory snapshot: pprof-format bytes (base64) plus each
    local device's live memory stats — HBM residency on demand."""
    import base64

    import jax

    prof = jax.profiler.device_memory_profile()
    return {
        "pprof_b64": base64.b64encode(prof).decode(),
        "devices": [
            {
                "id": d.id,
                "kind": d.device_kind,
                "memory_stats": d.memory_stats() or {},
            }
            for d in jax.local_devices()
        ],
    }
