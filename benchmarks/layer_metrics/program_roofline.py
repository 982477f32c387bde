"""The engine's compiled forward programs against the chip's roofline:
the least time the chip could take for the batches executed in the
traced window (the larger of operations over peak FLOP/s and least
bytes over peak bytes/s, from shapes, ``benchmarks/work``) over the
summed device durations of those programs on the trace's "XLA Modules"
line. Which executions ran which shape is known from the labelling
traces (one lone request per program). Nothing to read without a trace,
without peaks, or where no execution could be told apart."""

from __future__ import annotations

from benchmarks.harness import model_kwargs, work_module


def least_seconds(run, shape) -> tuple[float, str]:
    work = work_module(run.cell.config)
    kwargs = model_kwargs(run.cell.config)
    peaks = run.trace["peaks"]
    by_flops = work.flops(shape, kwargs) / peaks["bf16_flops_per_s"]
    by_bytes = work.min_bytes(shape, kwargs) / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "compute" if by_flops >= by_bytes else "memory"


def read(run):
    trace = run.trace
    if not trace or not trace.get("peaks") or not trace.get("programs"):
        return None
    least = spent = 0.0
    for name, durations in trace["reduced"].module_seconds(trace["span"]).items():
        shape = trace["programs"].get(name)
        if shape:
            least += least_seconds(run, shape)[0] * len(durations)
            spent += sum(durations)
    return 100.0 * least / spent if spent > 0 else None
