"""Share of the window's row-forwards that were commit forwards (a whole
block run once more so that its K/V stand; it reveals nothing): 100 x
``serving_block_forwards_total{kind="commit"}`` over all kinds. What fusing
a commit into the next block's first forward would take out. None for a
program without the counter."""
from lib.loopclock import delta

FORWARDS = "serving_block_forwards_total"


def read(ctx):
    total = delta(ctx, FORWARDS)
    if not total:
        return None
    return 100.0 * (delta(ctx, FORWARDS, 'kind="commit"') or 0.0) / total
