"""Mean wait from enqueue to admission (``serving_queue_wait_seconds``)
over the window, ms."""
from lib.readers import hist_mean


def read(ctx):
    return hist_mean(ctx, "serving_queue_wait_seconds", 1e3)
