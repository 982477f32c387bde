#!/usr/bin/env python3
"""``python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json`` on the
machine this is started on. The last line of standard output is the
result; without a TPU there is none and the exit code is not 0."""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace),
        t_process_start=T_PROCESS_START,
    )
    print(json.dumps(result), flush=True)
    left = harness.lingering_threads()
    if left:
        print(f"[bench] threads outlived worker.stop(): {left}", file=sys.stderr)
        sys.stderr.flush()
        os._exit(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
