"""Share of the window's decode dispatches that were launched while an
earlier decode dispatch's tokens had not been fetched: 100 x
Δ``serving_dispatches_total{ahead="yes"}`` / Δ all of it (counted in the
engine's ``_note_dispatch``, as a dispatch is launched). Near 100 where
the engine plans by count and reads a dispatch late; 0 where the next
forward is built from this one's answer (a block family). None for a
program without the counter."""
from lib.loopclock import delta

COUNTER = "serving_dispatches_total"


def read(ctx):
    total = delta(ctx, COUNTER)
    if not total:
        return None
    return 100.0 * (delta(ctx, COUNTER, 'ahead="yes"') or 0.0) / total
