"""Read a run's profiler trace by the program's own names.

* device time per named scope: each device op carries the path of the
  ``jax.named_scope`` it was traced under in its ``tf_op`` stat
  (``jit(iteration)/rollout/while/body/closed_call/actor/dot_general``;
  a fusion carries its root's).  A scope such as ``rollout/actor``
  matches the path's components in order, not as a prefix, so the
  scan's own components (``while/body/closed_call``) may lie between.
  Each op counts its own time less the ops nested in it
  (``trace.self_times``), averaged over the devices;
* the program's host spans (``train.pack``, ``train.dispatch``, ...),
  which ``repro.obs.SpanClock`` writes as profiler annotations;
* each idle gap of the devices, put down to the innermost program
  span (``train.*``) that covers its midpoint;
* the trace's start on the host's clock (``profile_start_time`` of the
  ``Task Environment`` plane, epoch nanoseconds).

``jax.profiler.ProfileData`` does not expose an event's metadata, so
``tf_ops`` decodes the ``.xplane.pb`` with a minimal protobuf reader
(the fields used are listed beside it).  ``current()`` finds this
run's trace, the newest under ``.out/trace``, and parses it once per
process.
"""
from __future__ import annotations

import functools
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

import harness

TRACE_DIR = harness.OUT / "trace"
OPS_LINE = "XLA Ops"
MODULE = "jit_iteration"
PROGRAM_SPANS = "train."

# ---- a minimal protobuf reader ----------------------------------------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 and
# stat_metadata = 5 (maps: key 1, value 2); XEventMetadata: name = 2,
# stats = 5; XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7.


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value): an int for varint and fixed-width
    fields, bytes for length-delimited ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = int.from_bytes(b[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(b[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield field, v


def _map_values(entries: List[bytes]) -> Iterator[bytes]:
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def _group(b: bytes) -> Dict[int, list]:
    out: Dict[int, list] = defaultdict(list)
    for f, v in _fields(b):
        out[f].append(v)
    return out


def tf_ops(data: bytes) -> Dict[str, str]:
    """{device op name: its ``tf_op`` path} over the device planes,
    the op type after the last ``:`` left off
    (``jit(<lambda>)/dot_general:`` -> ``jit(<lambda>)/dot_general``);
    an op with several paths keeps the first."""
    out: Dict[str, str] = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        p = _group(plane)
        if not p[2] or not p[2][0].decode().startswith("/device:"):
            continue
        stat_names = {}
        for sm in _map_values(p[5]):
            s = _group(sm)
            stat_names[s[1][0] if s[1] else 0] = (
                s[2][0].decode() if s[2] else "")
        tf_id = next((k for k, v in stat_names.items() if v == "tf_op"),
                     None)
        if tf_id is None:
            continue
        for em in _map_values(p[4]):
            e = _group(em)
            for stat in e[5]:
                st = _group(stat)
                if st[1] != [tf_id]:
                    continue
                if st[5]:
                    value = st[5][0].decode()
                elif st[7]:
                    value = stat_names.get(st[7][0], "")
                else:
                    continue
                # an op XLA merged from several carries each one's
                # path, joined by ``;``: it counts under the first
                first = value.split(";", 1)[0]
                out[e[2][0].decode()] = first.rsplit(":", 1)[0]
    return out


# ---- scopes ---------------------------------------------------------------------

def in_scope(tf_op: str, scope: str) -> bool:
    """Whether ``scope``'s components appear in order among the
    path's components (``rollout/actor`` matches
    ``jit(f)/rollout/while/body/closed_call/actor/dot_general``)."""
    want = scope.split("/")
    k = 0
    for part in tf_op.split("/"):
        if k < len(want) and part == want[k]:
            k += 1
    return k == len(want)


def innermost(spans: List[Tuple[str, int, int]], t: int) -> str:
    """The program span covering ``t`` that began last (spans nest),
    or ``other``."""
    best = None
    for name, a, b in spans:
        if a <= t < b and (best is None or a >= best[1]):
            best = (name, a)
    return best[0] if best else "other"


class Trace:
    """One parsed trace: ``ProfileData`` for the events and the
    decoded ``tf_op`` of each device op."""

    def __init__(self, pd, tf_op: Dict[str, str]):
        self._tr = harness.trace_reducer()
        self.pd = pd
        self.tf_op = tf_op
        self.devices = self._tr.device_lines(pd)
        self.start_ns = next(
            (int(v) for plane in pd.planes
             if plane.name == "Task Environment"
             for k, v in plane.stats if k == "profile_start_time"),
            None)

    @classmethod
    def load(cls, path: Path) -> "Trace":
        return cls(harness.trace_reducer().load(path),
                   tf_ops(Path(path).read_bytes()))

    def scope_s(self, scope: str) -> Optional[float]:
        """Device time of the ops under ``scope``, averaged over the
        devices; None where no op is."""
        if not self.devices:
            return None
        total, found = 0, False
        for lines in self.devices.values():
            for name, t in self._tr.self_times(lines[OPS_LINE]):
                path = self.tf_op.get(name)
                if path is not None and in_scope(path, scope):
                    total += t
                    found = True
        return total / 1e9 / len(self.devices) if found else None

    def host_spans(self, prefix: str = PROGRAM_SPANS
                   ) -> List[Tuple[str, int, int]]:
        """The program's host spans whose names start with
        ``prefix``, in time order."""
        spans = []
        for plane in self.pd.planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                             for e in line.events
                             if e.name.startswith(prefix))
        return sorted(spans, key=lambda s: s[1])

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Each idle gap of each device within the traced window, in
        seconds, named by the innermost program span at its midpoint;
        longest first."""
        spans = self.host_spans(PROGRAM_SPANS)
        ops = [e for lines in self.devices.values()
               for e in lines[OPS_LINE]]
        if not ops:
            return []
        lo = min(s[1] for s in spans + ops)
        hi = max(s[2] for s in spans + ops)
        out = []
        for lines in self.devices.values():
            busy = self._tr.merge((a, b) for _, a, b in lines[OPS_LINE])
            for a, b in self._tr.gaps(busy, lo, hi):
                out.append((innermost(spans, (a + b) // 2),
                            (b - a) / 1e9))
        return sorted(out, key=lambda g: -g[1])


def newest(d: Path = TRACE_DIR) -> Optional[Path]:
    found = sorted(Path(d).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


@functools.lru_cache(maxsize=1)
def _parse(path: str) -> Trace:
    return Trace.load(Path(path))


def current() -> Optional[Trace]:
    """This run's trace (the newest under ``.out/trace``), parsed once
    per process; None where the run made none."""
    path = newest()
    return _parse(str(path)) if path is not None else None


def per_call_ms(ctx: dict, scope: str) -> Optional[float]:
    """Device milliseconds under ``scope`` per call of the jitted
    training iteration (XLA module ``jit_iteration``) in the traced
    window; None where the trace has no op under the scope."""
    t = ctx.get("trace")
    m = (t or {}).get("modules", {}).get(MODULE)
    if ctx.get("kind") != "train" or not m or not m["count"]:
        return None
    tr = current()
    s = tr.scope_s(scope) if tr is not None else None
    return None if s is None else 1e3 * s / m["count"]
