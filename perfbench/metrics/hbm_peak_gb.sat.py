"""The device's memory peak after the window, before the reference runs:
``memory_stats()`` ``peak_bytes_in_use`` (arrays) plus ``peak_bytes_reserved``
(what the runtime set aside for compiled programs' temporaries), GB (1e9 bytes)."""
from lib.readers import hbm_peak_gb


def read(ctx):
    return hbm_peak_gb(ctx)
