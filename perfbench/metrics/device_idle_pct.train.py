"""Share of the traced stretch of the window in which no operation ran on
the device: 1 - union of the device's operation intervals over its length,
0-100."""
from lib.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
