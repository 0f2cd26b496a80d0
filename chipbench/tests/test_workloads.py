"""Every cell of ``BENCHMARK.json`` resolves its files by name, keeps to
the file's format, and runs end to end at a small size on the CPU with
``correct`` true and every number it was compared on beside its limit."""
import math
import re

import pytest

import harness
import tiny

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
        for w in m["workloads"]:
            cell = harness.load_cell(w)
            assert m["moves"] in [x["name"] for x in cell.end_to_end]
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["source"] == c["source"]


@pytest.mark.parametrize("name", tiny.workloads())
def test_cell_files(name):
    cell = harness.load_cell(name)
    assert (harness.BENCH / "drivers" / f"{cell.driver}.py").is_file()
    assert (harness.BENCH / "configs" / f"{cell.config_name}.py").is_file()
    assert (harness.BENCH / "work" / f"{cell.config_name}.py").is_file()
    assert cell.limits["limits"]
    sides = set(getattr(tiny.driver(cell), "SIDES", ())) - {"program"}
    # the control and every fault fail one of the cell's numbers, or
    # PERF.md says why not: a cell's limits catch at least one side
    assert cell.limits["caught"]
    assert set(cell.limits["caught"]) <= sides
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("name", tiny.workloads())
def test_cell_runs_correct(name):
    cell = tiny.cell(name)
    out, line = tiny.run(cell)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == set(cell.limits["limits"])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"


def test_refuses_cpu():
    with pytest.raises(harness.NoChip):
        harness.check_chip(1)


@pytest.mark.parametrize("name", tiny.workloads())
def test_per_layer_readers_without_trace(name):
    """A reader that finds nothing to read returns None."""
    cell = harness.load_cell(name)
    assert harness.read_per_layer(cell, {}) == {}
