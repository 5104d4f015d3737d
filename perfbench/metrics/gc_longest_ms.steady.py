"""The collector's longest pause over the window: the upper bound of the
highest bucket of ``process_gc_seconds`` that gained a count."""
from lib.hostclock import gc_longest_ms


def read(ctx):
    return gc_longest_ms(ctx)
