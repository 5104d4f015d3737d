"""Profiling and timing utilities.

The reference has no profiling at all (SURVEY.md §5.1 — stdlib logging
only); the rebuild note there calls for real instrumentation via
``jax.profiler`` + ``block_until_ready`` timers. These are the shared
helpers: a sync-correct timer (device fetch, not dispatch, marks the end)
and a process-wide stats registry the node's ``/status`` surface can
report. A device profile is taken with ``jax.profiler`` from outside (the
benchmark's ``perfbench/lib/trace.py``); the serving engine's phases show
in it as ``engine.<phase>`` (``telemetry/loopclock.py``)."""

from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Iterator


@dataclass
class TimingStats:
    count: int = 0
    total_s: float = 0.0
    min_s: float = float("inf")
    max_s: float = 0.0

    def record(self, seconds: float) -> None:
        self.count += 1
        self.total_s += seconds
        self.min_s = min(self.min_s, seconds)
        self.max_s = max(self.max_s, seconds)

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0

    def to_dict(self) -> dict:
        return {
            "count": self.count,
            "total_s": round(self.total_s, 6),
            "mean_s": round(self.mean_s, 6),
            "min_s": round(self.min_s, 6) if self.count else None,
            "max_s": round(self.max_s, 6),
        }


class _Registry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._stats: dict[str, TimingStats] = defaultdict(TimingStats)

    def record(self, name: str, seconds: float) -> None:
        with self._lock:
            self._stats[name].record(seconds)

    def snapshot(self) -> dict[str, dict]:
        with self._lock:
            return {k: v.to_dict() for k, v in sorted(self._stats.items())}

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()


#: process-wide registry (exposed through the node /status route)
stats = _Registry()


@contextlib.contextmanager
def timed(name: str, sync: Any = None) -> Iterator[dict]:
    """Wall-clock a block; with ``sync`` (an array/pytree), end the timing
    only after the device work producing it is done (``block_until_ready``
    — dispatch returns early on accelerators)."""
    t0 = time.monotonic()
    box = {"seconds": None}
    try:
        yield box
    finally:
        target = box.get("sync", sync)
        if target is not None:
            import jax

            jax.block_until_ready(target)
        box["seconds"] = time.monotonic() - t0
        stats.record(name, box["seconds"])
