"""Compilations inside the measured window: jax's backend-compile
events plus the delta of ``program_cache_misses_total``. Expected 0."""

from __future__ import annotations


def read(run):
    misses = run.counter_delta("program_cache_misses_total")
    return run.compiles_in_window + (misses or 0.0)
