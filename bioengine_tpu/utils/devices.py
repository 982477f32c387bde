"""The rule that keeps the CPU from standing in for a chip unnoticed.

With ``jax_platforms`` unset, JAX falls back to its CPU backend with one
warning when the accelerator fails to initialise, and everything built
on top (topology, engines, Pallas wrappers) would carry on at CPU speed
while reporting healthy. Code that is about to accept a CPU device asks
here first: CPU is fine when it was asked for by name — the first entry
of ``JAX_PLATFORMS`` / ``jax_platforms`` is ``cpu``, as the test suite
and ``worker_host --platform cpu`` set it — and an error otherwise.
"""

from __future__ import annotations


class NoAcceleratorError(RuntimeError):
    """JAX came up on the CPU although CPU was not asked for."""


def cpu_requested() -> bool:
    """True when the CPU platform was asked for explicitly."""
    import jax

    platforms = jax.config.jax_platforms or ""
    return platforms.split(",")[0].strip().lower() == "cpu"


def require_accelerator(platform: str, what: str) -> None:
    """Raise unless ``platform`` is an accelerator or CPU was asked for
    by name. ``what`` names the caller for the message."""
    if platform == "cpu" and not cpu_requested():
        import jax

        raise NoAcceleratorError(
            f"{what}: JAX came up on the CPU but jax_platforms is "
            f"{jax.config.jax_platforms!r} — the accelerator did not "
            "initialise. Fix the device, or set JAX_PLATFORMS=cpu to run "
            "on the CPU on purpose."
        )
