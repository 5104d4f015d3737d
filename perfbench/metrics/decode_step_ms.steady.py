"""Mean time of a decode step as the engine's ``serving_token_seconds``
histogram has it (host clock around the dispatch and the token fetch; a
fused scan's time is divided by its steps), over the window, ms."""
from lib.readers import hist_mean


def read(ctx):
    return hist_mean(ctx, "serving_token_seconds", 1e3)
