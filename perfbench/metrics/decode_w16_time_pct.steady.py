"""Share of the window's decode dispatch time spent in width-16 programs:
100 x sum of ``serving_dispatch_seconds{width="16"}`` / its sum over all
widths and paths."""
from lib.loopclock import width_time_pct


def read(ctx):
    return width_time_pct(ctx, 16)
