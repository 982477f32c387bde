"""The engine's compiled forward programs against the chip's roofline:
the least time the chip could take for the batches executed in the
traced window (the larger of operations over peak FLOP/s and least
bytes over peak bytes/s, from the program's key, ``benchmarks/work``)
over the summed device durations of those programs on the trace's "XLA
Modules" line. Which executions ran which program is known from the
labelling traces (one lone request per program key of the path). Nothing
to read without a trace, without peaks, where no execution could be told
apart, or where the work module has no count for a key."""

from __future__ import annotations

from benchmarks.harness import work_module


def least_seconds(run, key):
    """(seconds, which peak bounds them), or ``None`` where the work
    module counts nothing for this program."""
    work = work_module(run.cell.config)
    peaks = run.trace["peaks"]
    flops = work.program_flops(key, run.cell.config)
    if flops is None:
        return None
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = work.program_min_bytes(key, run.cell.config) / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "compute" if by_flops >= by_bytes else "memory"


def read(run):
    trace = run.trace
    if not trace or not trace.get("peaks") or not trace.get("programs"):
        return None
    least = spent = 0.0
    for name, durations in trace["reduced"].module_seconds(trace["span"]).items():
        key = trace["programs"].get(name)
        bound = least_seconds(run, key) if key else None
        if bound:
            least += bound[0] * len(durations)
            spent += sum(durations)
    return 100.0 * least / spent if spent > 0 else None
