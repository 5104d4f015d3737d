"""Mean time of one prefill (``serving_prefill_seconds``) over the window,
ms."""
from lib.readers import hist_mean


def read(ctx):
    return hist_mean(ctx, "serving_prefill_seconds", 1e3)
