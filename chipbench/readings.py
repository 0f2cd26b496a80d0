"""The readings that the limits of ``correct`` are set from, on the chip
at the cell's own size, in one process.

    python chipbench/readings.py --workload e2hrl_ppo --seeds 1-12 \\
        --sides program,control,half_batch,altered

For each seed and side it prints one JSON line
``{"workload", "seed", "side", "numbers"}``.  ``program`` is the
program's own reading; the other sides are those the cell's driver
lists under ``SIDES``: the control (the reference in a lower precision,
or the program's own lower-precision path) and the planted faults.
A limit lies above the largest program reading and below the smallest
reading of the control and of each fault that separates.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def seeds(spec: str):
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12,40")
    ap.add_argument("--sides", default="program")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.check_chip(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    driver = harness.import_file(BENCH / "drivers" / f"{cell.driver}.py")
    for side in args.sides.split(","):
        if side not in driver.SIDES:
            raise SystemExit(f"{cell.driver} has no side {side!r} "
                             f"(have {driver.SIDES})")
        for seed in seeds(args.seeds):
            numbers = driver.reading(cell, seed, side, devs)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "side": side, "numbers": numbers}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
