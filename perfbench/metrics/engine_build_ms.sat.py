"""Engine-thread time building a dispatch's inputs and enqueueing its
program (phase ``build`` of ``serving_loop_seconds_total``) per decode
dispatch (count of ``serving_dispatch_seconds``), over the window, ms."""
from lib.loopclock import phase_ms_per_dispatch


def read(ctx):
    return phase_ms_per_dispatch(ctx, "build")
