"""Engine-thread time draining tokens into rows and finishing requests
(phase ``emit`` of ``serving_loop_seconds_total``) per decode dispatch
(count of ``serving_dispatch_seconds``), over the window, ms."""
from lib.loopclock import phase_ms_per_dispatch


def read(ctx):
    return phase_ms_per_dispatch(ctx, "emit")
