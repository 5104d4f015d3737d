"""Idle gaps of the device, put down to what the engine thread was doing.

The engine's loop clock opens one ``engine.<phase>`` annotation per phase
on the engine thread (``pygrid_tpu/telemetry/loopclock.py``); under the
profiler they lie on the clock of the device's operations. A gap between
two busy stretches of a device is split over the phases that were open
while it lasted (``rule="overlap"``), or put down whole to the phase open
when it began (``rule="start"``; on a path that is not pipelined that is
``fetch`` nearly always: the host learns that the device has finished only
once the tokens arrive).

Run by hand (``tests/gaps_by_phase.py``); ``lib/trace.py``'s ``reduce``
still names a gap by the operation before it.
"""

from __future__ import annotations

from bisect import bisect_right
from collections import defaultdict
from pathlib import Path

from lib.trace import DEVICE_PLANE, device_events, merge

#: annotations of the engine thread's phases
PREFIX = "engine."
#: gaps shorter than this are not attributed one by one
MIN_GAP_S = 1e-4
#: gap time under no annotation (before the thread's first phase)
UNATTRIBUTED = "unattributed"
#: the summed time of the gaps shorter than ``MIN_GAP_S``
SHORT = "short_gaps"


def engine_spans(xplane_path: str | Path) -> list[tuple[float, float, str]]:
    """``[(start_s, end_s, phase)]`` of every ``engine.<phase>``
    annotation on a host line of the trace, sorted by start."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(str(xplane_path)).planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    start = ev.start_ns * 1e-9
                    phase = ev.name[len(PREFIX):]
                    spans.append((start, start + ev.duration_ns * 1e-9, phase))
    return sorted(spans)


def split(gap: tuple[float, float], spans: list, rule: str) -> dict[str, float]:
    """One gap's seconds by phase."""
    g0, g1 = gap
    # the last span that starts at or before the gap does
    i = max(0, bisect_right(spans, (g0, float("inf"), "")) - 1)
    out: dict[str, float] = defaultdict(float)
    if rule == "start":
        if spans and spans[i][0] <= g0 < spans[i][1]:
            out[spans[i][2]] = g1 - g0
        else:
            out[UNATTRIBUTED] = g1 - g0
        return out
    covered = 0.0
    while i < len(spans) and spans[i][0] < g1:
        start, end, phase = spans[i]
        part = min(end, g1) - max(start, g0)
        if part > 0:
            out[phase] += part
            covered += part
        i += 1
    if g1 - g0 - covered > 1e-12:
        out[UNATTRIBUTED] += g1 - g0 - covered
    return out


def attribute(xplane_path: str | Path, rule: str = "overlap",
              min_gap_s: float = MIN_GAP_S) -> dict[str, float]:
    """``{phase: seconds}`` of device idle time between operations, summed
    over the trace's devices; ``short_gaps`` holds the gaps under
    ``min_gap_s`` and ``unattributed`` what no annotation covered. The
    values add up to the gap time ``reduce`` ranks under ``idle_gaps``."""
    if rule not in ("overlap", "start"):
        raise ValueError(f"rule {rule!r} is neither overlap nor start")
    spans = engine_spans(xplane_path)
    totals: dict[str, float] = defaultdict(float)
    for events in device_events(xplane_path).values():
        merged = merge(events)
        for (_, end, _), (start, _, _) in zip(merged, merged[1:]):
            if start - end < min_gap_s:
                totals[SHORT] += start - end
                continue
            for phase, seconds in split((end, start), spans, rule).items():
                totals[phase] += seconds
    return dict(totals)
