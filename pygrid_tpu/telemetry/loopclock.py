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

A sum cannot hold one long instance, and wall seconds cannot tell the
thread's own work from the time it stood waiting for the interpreter, a
lock or a CPU. So the same flush also hands over, for a family
``<stem>_seconds_total``:

- ``<stem>_phase_seconds{phase}``: a histogram with one observation an
  instance, kept in the clock's own buckets between flushes. Its sum is
  the wall counter; its highest bucket is the longest single stretch;
- ``<stem>_cpu_seconds_total{phase}``: the thread's CPU seconds
  (``time.thread_time``, read at the same boundaries). Where a phase
  waits by design (a device, a condition) wall less CPU says nothing;
  where it works, it is time the thread wanted to run and did not.
  **Only where the host's thread clock gives data at a price a boundary
  can pay** (:func:`cpu_clock`, probed once a process): a sandboxed
  kernel may charge CPU time in scheduler ticks of 10 ms and take ~6 us a
  read (a TPU v5e's host does), and a tick-charged clock read at the
  boundaries of a loop of about that period does not only blur, it
  aliases. There the boundaries read no second clock and the family is
  absent, not zero.

There is no switch: like the rest of the bus the clock is always on.
"""

from __future__ import annotations

import time
from bisect import bisect_left
from functools import cache
from typing import Any, Callable

from pygrid_tpu.telemetry import bus

#: a read of the thread's CPU clock may cost this much, in seconds: a
#: system call on a plain kernel takes 0.3-0.5 us, an emulated one ~6
DEAR = 2e-6


def probe_cpu_clock(
    read: Callable[[], float] = time.thread_time,
    wall: Callable[[], float] = time.perf_counter,
) -> Callable[[], float] | None:
    """``read`` if it is a clock a phase boundary can afford and learn
    from, else None. Three batches of 32 back-to-back readings: the
    cheapest batch prices a reading (a batch the machine interrupted
    reads dear, so the cheapest counts), and a clock that moves in ticks
    longer than a batch shows the same value over and over."""
    each, values = float("inf"), set()
    for _ in range(3):
        t0 = wall()
        values.update([read() for _ in range(32)])
        each = min(each, (wall() - t0) / 32)
    fine = len(values) > 32  # of 96; a ticking clock shows one to four
    return read if fine and each < DEAR else None


@cache
def cpu_clock() -> Callable[[], float] | None:
    """The process's answer of :func:`probe_cpu_clock`, asked once."""
    return probe_cpu_clock()


class LoopClock:
    """Not thread-safe by design: one clock, one thread."""

    def __init__(self, family: str, prefix: str) -> None:
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._family = family
        stem = family.removesuffix("_seconds_total")
        self._cpu_family = stem + "_cpu_seconds_total"
        self._instances = stem + "_phase_seconds"
        self._prefix = prefix
        #: what the phases closed since the last flush gathered, by
        #: phase: wall seconds, CPU seconds, and instances by bucket of
        #: ``bus.DEFAULT_SECONDS_BOUNDS``
        self._wall: dict[str, float] = {}
        self._cpu_s: dict[str, float] = {}
        self._gained: dict[str, dict[int, int]] = {}
        self._phase: str | None = None
        self._since = 0.0
        self._cpu_since = 0.0
        self._span: Any = None
        self._cpu = cpu_clock()

    def enter(self, phase: str, **args: Any) -> None:
        """End the open phase (if any) and open ``phase``; ``args`` go
        on the annotation (what the profile shows beside the name)."""
        now = time.perf_counter()
        cpu = self._cpu() if self._cpu else 0.0
        self._close(now, cpu)
        self._phase, self._since, self._cpu_since = phase, now, cpu
        self._span = self._annotation(self._prefix + phase, **args)
        self._span.__enter__()

    def annotate(self, **args: Any) -> None:
        """More arguments for the open phase's annotation, known only
        after it began (a step's width, once the slots are read)."""
        self._span.set_metadata(**args)

    def flush(self) -> None:
        """Hand what the closed phases gathered to the bus: one lock."""
        if not self._wall:
            return
        bus.incr_many(
            self._family, "phase", self._wall,
            more=((self._cpu_family, self._cpu_s),) if self._cpu else (),
            instances=(self._instances, self._gained),
        )
        self._wall.clear()
        self._cpu_s.clear()
        self._gained.clear()

    def stop(self) -> None:
        """End the open phase and flush: the thread leaves its loop."""
        self._close(time.perf_counter(), self._cpu() if self._cpu else 0.0)
        self.flush()

    def _close(self, now: float, cpu: float) -> None:
        if self._span is None:
            return
        self._span.__exit__(None, None, None)
        self._span = None
        phase, wall = self._phase, now - self._since
        self._wall[phase] = self._wall.get(phase, 0.0) + wall
        if self._cpu:
            self._cpu_s[phase] = (
                self._cpu_s.get(phase, 0.0) + cpu - self._cpu_since
            )
        gained = self._gained.get(phase)
        if gained is None:
            gained = self._gained[phase] = {}
        index = bisect_left(bus.DEFAULT_SECONDS_BOUNDS, wall)
        gained[index] = gained.get(index, 0) + 1
