"""The cyclic collector's pauses, process-wide.

A collection holds the GIL from its start to its end, so every thread of
the process stalls for it, whichever thread's allocation set it off: a
pause a phase of the engine's loop clock cannot name, because it is not
the engine's work. :func:`start` hangs one callback on ``gc.callbacks``
(the node does, with its other observers); each collection then becomes
one observation of ``process_gc_seconds{generation}``: the count is the
collections, the sum the seconds paused, the highest bucket the longest
pause. A collection of the oldest generation is also a
``jax.profiler.TraceAnnotation`` named ``gc.full`` on the thread that
ran it: under the profiler it lies on the device trace's clock, like the
loop clock's phases, so an idle gap of the device can be put down to it.

The callback takes no lock: young collections run thousands of times a
second. The collector runs one collection at a time and calls back on
the collecting thread, so the histograms have one writer; they live here
and ``telemetry.export`` writes them at scrape (:func:`snapshots`).
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any

from pygrid_tpu.telemetry.bus import Histogram

FAMILY = "process_gc_seconds"
#: the generation whose collection walks every tracked object
OLDEST = len(gc.get_threshold()) - 1

_install = threading.Lock()
_users = 0
#: generation -> its pauses; filled by :func:`start`, kept after
#: :func:`stop` (a counter does not go back)
_pauses: dict[int, Histogram] = {}
_since = 0.0
_span: Any = None
_annotation: Any = None


def _on_gc(phase: str, info: dict) -> None:
    global _since, _span
    if phase == "start":
        if info["generation"] == OLDEST:
            _span = _annotation("gc.full")
            _span.__enter__()
        _since = time.perf_counter()
        return
    paused = time.perf_counter() - _since
    if _span is not None:
        _span.__exit__(None, None, None)
        _span = None
    # a "stop" whose "start" came before the hook was hung has no length
    if _since:
        _pauses[info["generation"]].observe(paused)


def start() -> None:
    """Hang the callback (once, however many callers); pair with
    :func:`stop`."""
    global _users, _annotation, _since, _span
    with _install:
        _users += 1
        if _users > 1:
            return
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
        for generation in range(OLDEST + 1):
            _pauses.setdefault(generation, Histogram())
        # a collection the hook was taken down in the middle of never
        # sent its "stop": what it left open is nobody's
        _since, _span = 0.0, None
        gc.callbacks.append(_on_gc)


def stop() -> None:
    """Take the callback down when its last user leaves."""
    global _users, _span
    with _install:
        if _users == 0:
            return
        _users -= 1
        if _users == 0 and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
            _span = None


def snapshots() -> dict[int, dict]:
    """``{generation: Histogram.snapshot()}`` for the exporter, read with
    no lock while a collection may be observed."""
    return {g: hist.snapshot() for g, hist in sorted(_pauses.items())}
