"""The toy token path of ``toy_path/``, laid out as the files a
``model_config`` PR for a new served path would add: a path module, a
generator kind, a traffic mix, a configuration, its reference and work
count, three per-layer readers, a cell's expectations, and the entries
of ``BENCHMARK.json``. It is scaffolding for the harness's seam and in
no cell.

``mount()`` makes the overlay's modules importable beside the real ones
(it extends the search path of ``benchmarks.paths`` and its siblings), so
that a test can run the toy cell in this process. ``merged_tree(dst)``
copies the real benchmark to ``dst`` and adds the overlay as NEW files
and NEW entries only: the proof that such a PR edits nothing.

    python3 benchmarks/tests/toy_overlay.py --platform tpu --trace 1

runs the toy cell once through ``harness.run_cell`` on the machine it is
started on and prints the result line (a chip run of the seam by hand;
the benchmark's command never reaches it).
"""

from __future__ import annotations

import importlib
import json
import shutil
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
REPO = BENCH.parent
OVERLAY = TESTS / "toy_path"
CELL = "toy-decoder.chat"
PACKAGES = ("paths", "generators", "references", "work", "layer_metrics")


def mount() -> None:
    for sub in PACKAGES:
        package = importlib.import_module(f"benchmarks.{sub}")
        extra = str(OVERLAY / "benchmarks" / sub)
        if extra not in package.__path__:
            package.__path__.append(extra)


def toy_cell():
    from benchmarks import harness

    mount()
    return harness.load_cell(CELL, root=OVERLAY)


def merged_manifest(real: dict, added: dict) -> dict:
    """``real`` with the overlay's configuration, cell and new metrics
    appended, and the cell's name appended to the ``workloads`` of every
    metric that is there and that the cell reports."""
    out = json.loads(json.dumps(real))
    out["configs"] += added["configs"]
    out["workloads"] += added["workloads"]
    for group in ("end_to_end", "per_layer"):
        have = {m["name"]: m for m in out[group]}
        for metric in added[group]:
            if metric["name"] not in have:
                out[group].append(metric)
            elif "workloads" in have[metric["name"]]:
                have[metric["name"]]["workloads"] += metric["workloads"]
    return out


def merged_tree(dst: Path) -> list[str]:
    """Copies ``benchmarks/``, ``BENCHMARK.json`` and ``PERF.md`` to
    ``dst`` and adds the overlay. Returns the files added; raises where
    one of them would overwrite a file that is there."""
    shutil.copytree(
        BENCH, dst / BENCH.name,
        ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache"),
    )
    shutil.copy(REPO / "PERF.md", dst / "PERF.md")
    added = []
    for source in sorted((OVERLAY / "benchmarks").rglob("*")):
        if source.is_file() and "__pycache__" not in source.parts:
            target = dst / source.relative_to(OVERLAY)
            if target.exists():
                raise FileExistsError(f"the overlay would edit {target}")
            target.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(source, target)
            added.append(str(source.relative_to(OVERLAY)))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    overlay = json.loads((OVERLAY / "BENCHMARK.json").read_text())
    (dst / "BENCHMARK.json").write_text(
        json.dumps(merged_manifest(real, overlay), indent=1)
    )
    return added


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--platform", default="tpu")
    parser.add_argument("--seed", type=int, default=2**31 + 28)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    from benchmarks import harness

    line = harness.run_cell(
        toy_cell(), args.seed, args.seconds, bool(args.trace), platform=args.platform,
        out_dir=REPO / ".cache" / "bench" / CELL,
    )
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(REPO))
    sys.exit(main())
