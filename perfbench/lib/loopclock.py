"""Readers of the engine thread's loop clock (``pygrid_tpu/telemetry/
loopclock.py``, ``serving/engine.py``): what the counters and the dispatch
histogram gained between the window's two scrapes of ``/metrics``
(``snap_w0``, ``snap_w1``), selected by label. Each ``metrics/engine_*``,
``prefill_stall_pct.*``, ``width_padding_pct.*`` and ``decode_w16_time_pct.*``
reader is a few lines over these.

Every function returns None for a training context and for a program that
has no such series (the parent of the PR that added the clock): the result
line then leaves the metric out.
"""

from __future__ import annotations

from lib.readers import hist_delta

LOOP = "serving_loop_seconds_total"
DISPATCH = "serving_dispatch_seconds"
ROWSTEPS = "serving_dispatch_rowsteps_total"
ADMITTED = "serving_admitted_total"
#: the phases in which the host works while the device may be waiting for it
HOST_PHASES = ("admit", "build", "emit")


def delta(ctx: dict, name: str, label: str = ""):
    """Growth over the window of the counter ``name`` (series whose label
    set contains ``label``); None where the closing scrape has none."""
    if ctx.get("kind") != "serve":
        return None

    def total(snap):
        found = [
            v for k, v in snap.items()
            if isinstance(v, float) and k.split("{")[0].endswith(name) and label in k
        ]
        return sum(found) if found else None

    end = total(ctx["snap_w1"])
    return None if end is None else end - (total(ctx["snap_w0"]) or 0.0)


def phase_seconds(ctx: dict, *phases: str):
    """Seconds the engine thread spent in ``phases`` over the window."""
    if delta(ctx, LOOP) is None:
        return None
    # a phase the window never entered has no series yet: that is 0 s
    return sum(delta(ctx, LOOP, f'phase="{p}"') or 0.0 for p in phases)


def phase_pct(ctx: dict, *phases: str):
    """Those seconds as a share of the window, 0-100."""
    seconds = phase_seconds(ctx, *phases)
    return None if seconds is None else 100.0 * seconds / ctx["seconds"]


def dispatches(ctx: dict, label: str = ""):
    """(seconds, count) the dispatch histogram gained over the window."""
    if ctx.get("kind") != "serve":
        return 0.0, 0.0
    return hist_delta(ctx["snap_w0"], ctx["snap_w1"], DISPATCH, label)


def phase_ms_per(ctx: dict, phase: str, count):
    """Milliseconds of ``phase`` per counted thing (a dispatch, an
    admitted row) over the window."""
    seconds = phase_seconds(ctx, phase)
    if seconds is None or not count:
        return None
    return 1e3 * seconds / count


def phase_ms_per_dispatch(ctx: dict, phase: str):
    return phase_ms_per(ctx, phase, dispatches(ctx)[1])


def width_padding_pct(ctx: dict):
    """Share of the decode row-steps the device computed that belonged to
    no occupied slot: the width bucket's padding, 0-100."""
    live = delta(ctx, ROWSTEPS, 'kind="live"')
    computed = delta(ctx, ROWSTEPS, 'kind="computed"')
    if live is None or not computed:
        return None
    return 100.0 * (1.0 - live / computed)


def width_time_pct(ctx: dict, width: int):
    """Share of the window's dispatch seconds spent in programs of
    ``width``, over all paths, 0-100."""
    total = dispatches(ctx)[0]
    if not total:
        return None
    return 100.0 * dispatches(ctx, f'width="{width}"')[0] / total
