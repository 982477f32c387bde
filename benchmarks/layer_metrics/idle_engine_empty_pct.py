"""Share of the traced span in which device 0 ran no operation and the
engine had NO request in hand: the idle gaps outside every
``engine.request`` stage, over the span. The serving layers above the
engine did not feed it. With ``idle_in_request_pct`` it sums to
``device_idle_pct``. Nothing to read without a device trace or without
the program's stage timeline."""

from __future__ import annotations

from benchmarks.layer_metrics import _stages


def read(run):
    split = _stages.idle_split(run)
    return None if split is None else split["engine_empty_pct"]
