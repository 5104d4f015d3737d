"""Share of the window in which every live slot waited for somebody's
prefill: 100 x phase ``prefill`` of ``serving_loop_seconds_total`` (the
prefill program's call until its first token is on the host) / window."""
from lib.loopclock import phase_pct


def read(ctx):
    return phase_pct(ctx, "prefill")
