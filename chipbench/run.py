"""Run one benchmark cell once, on the chip, and print its result line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by name (see ``harness.py``), refuses any
platform but ``tpu`` and fewer chips than the cell asks for, builds
and warms the program (set-up), measures for ``--seconds``, checks
what the timed path produced against the configuration's plain
reference, and prints one JSON object as the last line of standard
output.  ``--trace 1`` adds a profiler trace after the window and
reports the cell's per-layer metrics in place of its end-to-end ones.
The numbers ``correct`` is decided on, each beside its limit, are the
last lines of standard error and the last key of the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        raise SystemExit("--seed is a whole number >= 0")
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.check_chip(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    driver = harness.import_file(BENCH / "drivers" / f"{cell.driver}.py")
    out = driver.run(cell, args.seed, args.seconds, bool(args.trace), devs,
                     T_START)
    line = harness.result_line(cell, out, bool(args.trace))
    harness.report_checks(line, out.compared)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
