"""Engine-thread host time of admission (phase ``admit`` of
``serving_loop_seconds_total``: slot, pages, prefix lookup, padding, keys)
per admitted row (``serving_admitted_total``), over the window, ms."""
from lib.loopclock import ADMITTED, delta, phase_ms_per


def read(ctx):
    return phase_ms_per(ctx, "admit", delta(ctx, ADMITTED))
