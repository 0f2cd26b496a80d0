"""Seconds of set-up spent tracing, lowering and compiling (or loading
compiled programs from the persistent cache): the union of the
program's compile counter's spans (``repro.obs.compiles``) that ended
before the profiler trace began, which takes in set-up and the window
(the reference compiles after the trace)."""
import harness
import scopes


def read(ctx):
    try:
        from repro.obs.compiles import LOG
    except ImportError:
        return None
    tr = scopes.current()
    if tr is None or tr.start_ns is None:
        return None
    start = tr.start_ns / 1e9
    spans = [(e.start, e.end) for e in LOG.events() if e.end < start]
    if not spans:
        return None
    merged = harness.trace_reducer().merge(spans)
    return sum(b - a for a, b in merged)
