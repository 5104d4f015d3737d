"""A loop clock: one thread's time, partitioned into named phases.

A loop thread (the serving engine's) calls :meth:`LoopClock.enter` at
each boundary of its work. Entering a phase ends the one before, so
every instant between the first ``enter`` and :meth:`LoopClock.stop`
belongs to exactly one phase. A phase is two things at once:

- seconds added to the counter ``<family>{phase=...}`` on the bus. The
  clock keeps the sums in plain attributes (it is confined to its
  thread) and :meth:`flush` hands them over under one hold of the bus
  lock — once a pass of the loop, not once a phase;
- a ``jax.profiler.TraceAnnotation`` named ``<prefix><phase>`` on the
  calling thread. Under ``jax.profiler.start_trace`` the phases lie on
  the device trace's clock, so an idle gap of the device can be put
  down to what the host was doing in it. With no profiler attached an
  annotation is a level check (~0.5 µs measured on this container's CPU).

There is no switch: like the rest of the bus the clock is always on.
"""

from __future__ import annotations

import time
from typing import Any

from pygrid_tpu.telemetry import bus


class LoopClock:
    """Not thread-safe by design: one clock, one thread."""

    def __init__(self, family: str, prefix: str) -> None:
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._family = family
        self._prefix = prefix
        self._sums: dict[str, float] = {}
        self._phase: str | None = None
        self._since = 0.0
        self._span: Any = None

    def enter(self, phase: str, **args: Any) -> None:
        """End the open phase (if any) and open ``phase``; ``args`` go
        on the annotation (what the profile shows beside the name)."""
        now = time.perf_counter()
        self._close(now)
        self._phase, self._since = phase, now
        self._span = self._annotation(self._prefix + phase, **args)
        self._span.__enter__()

    def annotate(self, **args: Any) -> None:
        """More arguments for the open phase's annotation, known only
        after it began (a step's width, once the slots are read)."""
        self._span.set_metadata(**args)

    def flush(self) -> None:
        """Hand the closed phases' seconds to the bus: one lock."""
        if self._sums:
            bus.incr_many(self._family, "phase", self._sums)
            self._sums.clear()

    def stop(self) -> None:
        """End the open phase and flush: the thread leaves its loop."""
        self._close(time.perf_counter())
        self.flush()

    def _close(self, now: float) -> None:
        if self._span is None:
            return
        self._span.__exit__(None, None, None)
        self._span = None
        self._sums[self._phase] = (
            self._sums.get(self._phase, 0.0) + now - self._since
        )
