#!/usr/bin/env python3
"""The control of ``correct``, read on the chip at a cell's own sizes.

    python3 benchmarks/control.py --workload <cell> --seeds 11 12 13

For each seed: the cell's weights and inputs from the seed, one request
of every kind in the mix, and the reference put in the program's place
with every contraction's operands rounded one step below the precision
the configuration states (bf16 -> fp8 e4m3; bf16 itself is printed for
information). Prints the two numbers ``correct`` compares, beside the
cell's limits. The control has come out as not correct when at least one
number passes its limit on every seed. The benchmark's own runs never
run this; ``PERF.md`` keeps the readings the limits were set from.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--precisions", nargs="+", default=["fp8", "bf16"])
    args = parser.parse_args(argv)

    import importlib

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    generator = importlib.import_module(
        f"benchmarks.generators.{cell.traffic['generator']}"
    )
    device = harness.device_gate("tpu", cell.chips)
    limits = cell.config["limits"]
    for seed in args.seeds:
        plan = generator.plan(cell.traffic, cell.config, seed)
        sample = cell.path.control_sample(cell, plan)
        for precision in args.precisions:
            t0 = time.perf_counter()
            readings = cell.path.compare(cell, seed, sample, plan.pool, precision)
            checks = harness.judge(readings, limits, len(sample))
            print(json.dumps({
                "workload": cell.name, "seed": seed, "precision": precision,
                "correct": harness.is_correct(checks), "checks": checks,
                "seconds": time.perf_counter() - t0, "device": device,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
