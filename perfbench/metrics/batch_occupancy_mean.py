"""Mean live slots per decode dispatch (``serving_batch_occupancy``) over
the window."""
from lib.readers import hist_mean


def read(ctx):
    return hist_mean(ctx, "serving_batch_occupancy")
