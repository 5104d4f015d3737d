"""The longest single stretch of host work over the window: the upper bound of
the highest bucket of ``serving_loop_phase_seconds{phase}`` (``admit``,
``prefill``, ``build``, ``emit``) that gained a count."""
from lib.hostclock import longest_phase_ms


def read(ctx):
    return longest_phase_ms(ctx)
