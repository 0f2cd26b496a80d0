"""Host phase spans: wall seconds per step window, on the profiler's
clock too.

``SpanClock(prefix)`` is a context-manager stopwatch: entering
``clock("sync")`` starts the phase, leaving it adds the elapsed wall
seconds to that phase's window bucket; ``drain()`` hands the
accumulated ``{phase: seconds}`` map to the step record and resets
the window.  Each phase is also a ``jax.profiler.TraceAnnotation``
named ``<prefix>.<phase>`` (``train.sync``, ``serve.infer``), so a
profiler trace shows it beside the device's ops; keyword metadata
(the global step ``g``) rides on the annotation.  With no trace
active an annotation costs about a microsecond.

Phases nest, and the set of names is open: a phase entered inside
another drains under ``<outer>/<inner>`` (``sync/pack``,
``step/dispatch``), so the top-level keys still tile the loop's wall
time.  docs/observability.md lists the names the trainers and the
serve loop use.

Host-side only: never enter a span inside traced code (QF301 — the
clock read would bake into the program).
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List

import jax


class SpanClock:
    def __init__(self, prefix: str = "train"):
        self.prefix = prefix
        self._s: Dict[str, float] = {}
        self._open: List[str] = []

    @contextmanager
    def __call__(self, phase: str, **meta):
        key = "/".join(self._open + [phase])
        self._open.append(phase)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"{self.prefix}.{phase}",
                                              **meta):
                yield
        finally:
            dt = time.perf_counter() - t0
            self._open.pop()
            self._s[key] = self._s.get(key, 0.0) + dt

    def drain(self) -> Dict[str, float]:
        """Window flush: ``{phase: seconds}`` since the last drain."""
        out = dict(self._s)
        self._s.clear()
        return out
