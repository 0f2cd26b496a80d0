"""Compile counter: every trace, lowering and backend compile JAX does
in this process, with its function name and its start and end.

JAX reports each of the three stages of a jit's first call through
``jax.monitoring`` as a time span (start and end in ``time.time()``
seconds).  ``LOG`` keeps the newest ``MAX_EVENTS`` of them and counts
backend compiles, which include a load from the persistent compilation
cache.  ``install()`` registers the listener once per process;
importing :mod:`repro.obs` calls it.

Readers: ``Trainer.train`` writes the backend compiles of each step
window into its ``step`` record (``compiles``), so an operator sees
which step recompiled; the benchmark's ``setup.compile_s`` sums the
union of the spans that ended before its trace began.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import List, NamedTuple

import jax

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
EVENTS = (TRACE, LOWER, BACKEND)
MAX_EVENTS = 65536


class CompileEvent(NamedTuple):
    fun_name: str
    event: str
    start: float        # time.time() seconds
    end: float


class CompileLog:
    """The listener and what it heard (thread-safe: JAX may compile on
    any thread)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events = deque(maxlen=MAX_EVENTS)
        self._backend = 0

    def __call__(self, event: str, start: float, end: float, **kw):
        if event not in EVENTS:
            return
        ev = CompileEvent(str(kw.get("fun_name", "")), event,
                          float(start), float(end))
        with self._lock:
            self._events.append(ev)
            self._backend += event == BACKEND

    @property
    def backend_compiles(self) -> int:
        """Backend compiles (or cache loads) since the process began."""
        with self._lock:
            return self._backend

    def events(self) -> List[CompileEvent]:
        with self._lock:
            return list(self._events)


LOG = CompileLog()
_installed = False


def install() -> CompileLog:
    """Register ``LOG`` with ``jax.monitoring`` (once per process)."""
    global _installed
    if not _installed:
        jax.monitoring.register_event_time_span_listener(LOG)
        _installed = True
    return LOG
