"""Share of the traced span in which device 0 ran no operation WHILE
the engine had a request in hand: the idle gaps of the device's "XLA
Ops" line inside the union of the program's ``engine.request`` stages
(``tracing.get_stages``, laid onto the trace by the wall clock), over
the span. Host work inside a request that nothing overlaps. With
``idle_engine_empty_pct`` it sums to ``device_idle_pct``. Nothing to
read without a device trace, without the program's stage timeline, or
where no request reached the engine in the span."""

from __future__ import annotations

from benchmarks.layer_metrics import _stages


def read(run):
    split = _stages.idle_split(run)
    return None if split is None else split["in_request_pct"]
