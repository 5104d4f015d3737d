"""Readers of the engine thread's host account (``pygrid_tpu/telemetry/
loopclock.py``, ``telemetry/gcwatch.py``): every phase instance's length and
the collector's pauses, each a histogram, read as what it gained between the
window's two scrapes of ``/metrics`` (``snap_w0``, ``snap_w1``). Each
``metrics/engine_longest_phase_ms.*`` and ``gc_longest_ms.*`` reader is one
call of these.

The account's third series, the thread's CPU seconds a phase
(``serving_loop_cpu_seconds_total``), has no reader: the program leaves it
out where the host's thread clock ticks coarsely or reads dear, and the
chip's host is such a one (PERF.md, PR 41).

Every function returns None for a training context and for a program that
has no such series (the parent of the PR that added the account): the result
line then leaves the metric out.
"""

from __future__ import annotations

INSTANCES = "serving_loop_phase_seconds"
GC = "process_gc_seconds"
#: the phases in which the thread works; ``fetch`` and ``idle`` wait by design
WORK_PHASES = ("admit", "prefill", "build", "emit")


def longest_ms(ctx: dict, name: str, labels: tuple = ("",)):
    """Upper bound, in milliseconds, of the highest bucket of the histogram
    ``name`` that gained a count over the window, over the series whose
    label set contains one of ``labels``: the longest single observation, to
    the bucket. 0 where nothing was observed; the ``+Inf`` bucket reads as
    the window's length."""
    if ctx.get("kind") != "serve":
        return None
    before, after = ctx["snap_w0"], ctx["snap_w1"]
    series: dict = {}  # label set without le -> {le: growth of the cumulative count}
    for key, end in after.items():
        head, _, rest = key.partition("{")
        if not head.endswith(name + "_bucket") or not any(lab in rest for lab in labels):
            continue
        labelset, _, le = rest.rstrip("}").rpartition('le="')
        series.setdefault(labelset, {})[float(le.rstrip('"'))] = end - before.get(key, 0.0)
    if not series:
        return None
    longest = 0.0
    for grown in series.values():
        below = 0.0
        for le in sorted(grown):
            if grown[le] > below:
                longest = max(longest, le)
            below = grown[le]
    return 1e3 * min(longest, ctx["seconds"])


def longest_phase_ms(ctx: dict):
    """The longest single stretch of host work over the window."""
    return longest_ms(ctx, INSTANCES, tuple(f'phase="{p}"' for p in WORK_PHASES))


def gc_longest_ms(ctx: dict):
    """The collector's longest pause over the window, any generation."""
    return longest_ms(ctx, GC)
