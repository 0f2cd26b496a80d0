"""Cells of ``BENCHMARK.json`` cut to sizes a CPU test run can hold."""
import time

import jax

import harness

TINY = {
    "train_onpolicy": {"n_envs": 64, "rollout_len": 16},
}


def cell(name: str) -> harness.Cell:
    c = harness.load_cell(name)
    c.traffic.update(TINY[c.driver])
    return c


def driver(c: harness.Cell):
    return harness.import_file(harness.BENCH / "drivers" / f"{c.driver}.py")


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


def devices():
    return jax.devices("cpu")
