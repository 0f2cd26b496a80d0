"""Reduce a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* ``busy_s``: the union of the intervals in which an operation ran on
  a device, averaged over the devices;
* ``window_s``: the traced window, from the first to the last event of
  the harness's host spans (``TraceAnnotation``) and the devices' ops
  (the device clock may stand a millisecond off the host's, so the
  window takes in both);
* ``modules``: device time and call count per XLA module (jitted
  program), averaged over the devices;
* ``breakdown``: the ten device operations that took most time (each
  op's own time, less the ops nested in it), and
  the ten longest idle gaps, each named by the host span that covered
  it (``other`` where the harness was in none).

Read with ``jax.profiler.ProfileData`` and nothing else.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(d: Path) -> Path:
    found = sorted(Path(d).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    return found[-1]


def load(path: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def _events(line) -> List[Tuple[str, int, int]]:
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def device_lines(pd) -> Dict[str, Dict[str, list]]:
    """{device plane: {line name: events}} for planes that ran XLA ops."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: _events(ln) for ln in plane.lines}
        if lines.get(OPS_LINE):
            out[plane.name] = lines
    return out


def host_spans(pd, names: Iterable[str]) -> List[Tuple[str, int, int]]:
    """The harness's annotations on the host, in time order."""
    names = set(names)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            spans.extend(e for e in _events(ln) if e[0] in names)
    return sorted(spans, key=lambda e: e[1])


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def self_times(events) -> List[Tuple[str, int]]:
    """Each op's time less the ops nested in it (a ``while`` holds its
    body's ops on the same line)."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] - e[1] for e in events]
    stack: List[int] = []
    for i, (_, a, b) in enumerate(events):
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][2]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return [(e[0], t) for e, t in zip(events, own, strict=True)]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
    """``jit_iteration(123)`` -> ``jit_iteration``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(pd, spans: Sequence[str]) -> dict:
    devices = device_lines(pd)
    if not devices:
        raise ValueError("the trace holds no device plane with XLA ops")
    host = host_spans(pd, spans)
    all_ops = [e for lines in devices.values() for e in lines[OPS_LINE]]
    lo = min(e[1] for e in host + all_ops)
    hi = max(e[2] for e in host + all_ops)
    n_dev = len(devices)
    busy_ns = 0
    op_time: Dict[str, float] = defaultdict(float)
    modules: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"time_s": 0.0, "count": 0.0})
    idle: List[Tuple[str, float]] = []
    for lines in devices.values():
        busy = merge((a, b) for _, a, b in lines[OPS_LINE])
        busy_ns += sum(b - a for a, b in busy)
        for name, t in self_times(lines[OPS_LINE]):
            op_time[_op_name(name)] += t / 1e9 / n_dev
        for name, a, b in lines.get(MODULES_LINE, []):
            m = modules[_module_name(name)]
            m["time_s"] += (b - a) / 1e9 / n_dev
            m["count"] += 1.0 / n_dev
        for a, b in gaps(busy, lo, hi):
            mid = (a + b) // 2
            what = next((n for n, s, e in host if s <= mid < e), "other")
            idle.append((what, (b - a) / 1e9))
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    idle.sort(key=lambda kv: -kv[1])
    return {"busy_s": busy_ns / 1e9 / n_dev, "window_s": (hi - lo) / 1e9,
            "devices": n_dev, "modules": dict(modules),
            "breakdown": {"device_ops": [[n, t] for n, t in top_ops],
                          "idle_gaps": [[n, t] for n, t in idle[:10]]}}


def reduce_dir(d: Path, spans: Sequence[str]) -> dict:
    return reduce(load(find_xplane(d)), spans)
