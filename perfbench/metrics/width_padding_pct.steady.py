"""Share of the decode row-steps computed over the window that belonged to
no occupied slot: 100 x (1 - live / computed) of
``serving_dispatch_rowsteps_total``."""
from lib.loopclock import width_padding_pct


def read(ctx):
    return width_padding_pct(ctx)
