"""jax.profiler capture around a configurable step window.

``ProfileWindow(dir, start, steps)`` arms a trace that starts when the
global step first reaches ``start`` and stops ``steps`` iterations
later (or at ``stop()``, whichever comes first).  The trainer calls
``tick(g)`` once per iteration from host code; the window is inclusive
of ``start`` and captures exactly the jitted programs dispatched in
between, which is the supported way to see inside the fused
collect+learn step: its device ops carry their phase's named scope
(``rollout/...``, ``learner/...``) and the host spans are annotations
on the same clock.

The capture is TensorBoard-loadable (``tensorboard --logdir <dir>``)
or openable with ``xprof``.  A ``profile`` record is reported through
the telemetry sink when one is attached, so a JSONL run documents its
own traces.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax


class ProfileWindow:
    def __init__(self, profile_dir: str, start: int = 0,
                 steps: int = 1):
        if steps < 1:
            raise ValueError(f"profile window needs steps >= 1, got {steps}")
        self.dir = profile_dir
        self.start = int(start)
        self.steps = int(steps)
        self.active = False
        self.done = False
        self._window: Optional[Tuple[int, int]] = None

    def tick(self, g: int) -> Optional[Tuple[int, int]]:
        """Advance to global step ``g``.  Returns the captured
        ``(g0, g1)`` window on the tick that stops the trace, else
        ``None``."""
        if self.done:
            return None
        if not self.active and g >= self.start:
            os.makedirs(self.dir, exist_ok=True)
            jax.profiler.start_trace(self.dir)
            self.active = True
            self._window = (g, g)
        elif self.active:
            g0, _ = self._window
            self._window = (g0, g)
            if g - g0 >= self.steps:
                return self.stop()
        return None

    def stop(self) -> Optional[Tuple[int, int]]:
        """Stop an active trace (idempotent); returns its window."""
        if not self.active:
            return None
        jax.profiler.stop_trace()
        self.active = False
        self.done = True
        return self._window
