"""Share of the window the engine thread spent on host work between device
programs: 100 x (admit + build + emit seconds of
``serving_loop_seconds_total``) / window: what ``engine_host_pct.sat``
reads, for this cell. At 64 rows a dispatch the host loop is the first
suspect; read beside ``device_idle_pct.sat``."""
from lib.loopclock import HOST_PHASES, phase_pct


def read(ctx):
    return phase_pct(ctx, *HOST_PHASES)
