"""Share of the window the engine thread spent on host work between device
programs: 100 x (admit + build + emit seconds of
``serving_loop_seconds_total``) / window; read beside ``device_idle_pct``."""
from lib.loopclock import HOST_PHASES, phase_pct


def read(ctx):
    return phase_pct(ctx, *HOST_PHASES)
