"""Mean milliseconds to copy one chunk's ready result to the host:
delta of the engines' ``d2h_seconds`` (the ``engine.d2h`` stage:
``np.asarray`` of an output that ``block_until_ready`` has returned
for, so no wait for the device is in it) over delta of ``chunks``
across the window. Nothing to read from a program that does not split
its readback, nor off the chip (a CPU copies nothing)."""

from __future__ import annotations


def read(run):
    if not run.trace:
        return None
    chunks = run.pipeline_delta("chunks")
    seconds = run.pipeline_delta("d2h_seconds")
    if not chunks or seconds is None:
        return None
    return 1000.0 * seconds / chunks
