"""Cells of ``BENCHMARK.json`` cut to sizes a CPU test run can hold.

Each driver gives its own CPU size under ``TINY``; a cell whose driver
gives none fails its own tests, and no other cell's or module's."""
import time

import jax

import harness


def driver(c: harness.Cell):
    return harness.import_file(harness.BENCH / "drivers" / f"{c.driver}.py")


def cell(name: str) -> harness.Cell:
    c = harness.load_cell(name)
    size = getattr(driver(c), "TINY", None)
    if size is None:
        raise LookupError(f"driver {c.driver!r} of cell {name!r} gives no "
                          f"TINY size for the CPU tests")
    c.traffic.update(size)
    return c


def run(c: harness.Cell, seed: int = 2**31 + 5, seconds: float = 0.5,
        **kw):
    devs = harness.check_chip(c.chips, platform="cpu")
    out = driver(c).run(c, seed, seconds, False, devs, time.perf_counter(),
                        **kw)
    line = harness.result_line(c, out, False) if c.limits else None
    return out, line


def workloads():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    return [w["name"] for w in bench["workloads"]]


def offering(attr: str):
    """The cells whose driver offers ``attr`` (``SIDES``: the control
    and the planted faults), found without sizing them."""
    return [w for w in workloads()
            if hasattr(driver(harness.load_cell(w)), attr)]


def devices():
    return jax.devices("cpu")
