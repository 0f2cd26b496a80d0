"""The trace reduction: busy and idle time, device time per XLA module,
and the breakdown, on a hand-built trace and on one recorded on a TPU
v5e (``data/tiny_trace.xplane.pb``: a jitted ``tanh(x @ x.T)`` run
three times, each call inside a ``step`` annotation and followed by a
2 ms sleep inside an ``env`` annotation)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import harness

trace = harness.trace_reducer()
DATA = Path(__file__).resolve().parent / "data" / "tiny_trace.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def fake():
    ops = [ev("fusion.1", 100, 50), ev("fusion.2", 140, 30),
           ev("convolution.3", 300, 100)]
    mods = [ev("jit_step(7)", 100, 70), ev("jit_step(7)", 300, 100)]
    device = NS(name="/device:TPU:0",
                lines=[NS(name="XLA Ops", events=ops),
                       NS(name="XLA Modules", events=mods)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("step", 90, 100), ev("env", 190, 100), ev("step", 290, 150),
        ev("unrelated", 0, 1000)])])
    return NS(planes=[host, device])


def test_merge_and_gaps():
    assert trace.merge([(5, 9), (1, 3), (2, 4)]) == [(1, 4), (5, 9)]
    assert trace.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]


def test_self_times_leave_out_nested_ops():
    got = dict(trace.self_times([("while", 0, 100), ("a", 10, 30),
                                 ("b", 30, 90), ("c", 40, 50),
                                 ("d", 120, 130)]))
    assert got == {"while": 20, "a": 20, "b": 50, "c": 10, "d": 10}


def test_reduce_hand_built():
    r = trace.reduce(fake(), spans=("step", "env"))
    assert r["window_s"] == pytest.approx(350e-9)        # 90 .. 440
    assert r["busy_s"] == pytest.approx(170e-9)         # 100-170, 300-400
    assert r["modules"]["jit_step"]["count"] == 2
    assert r["modules"]["jit_step"]["time_s"] == pytest.approx(170e-9)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["convolution.3"] == pytest.approx(100e-9)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "env" and gaps[0][1] == pytest.approx(130e-9)
    assert len(gaps) <= 10 and len(r["breakdown"]["device_ops"]) <= 10


def test_no_device_plane_is_an_error():
    pd = fake()
    pd.planes = pd.planes[:1]
    with pytest.raises(ValueError):
        trace.reduce(pd, spans=("step",))


@pytest.mark.skipif(not DATA.is_file(), reason="no recorded TPU trace")
def test_recorded_tpu_trace():
    r = trace.reduce(trace.load(DATA), spans=("step", "env"))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # three calls of the jitted function, each a few microseconds
    mods = {k: v for k, v in r["modules"].items() if k.startswith("jit_")}
    assert sum(m["count"] for m in mods.values()) == 3
    # the sleeps leave the device idle inside the env spans
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "env" and gaps[0][1] > 1.5e-3
    assert r["window_s"] > 3 * 2e-3
