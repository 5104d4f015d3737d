"""Prefill time per thousand true prompt tokens over the window: the sum
of ``serving_prefill_seconds`` over ``serving_prefill_tokens_total{kind=
true}``, ms. A prompt of 256-4,096 tokens runs whole: seven short
convolutions as shifted multiplies, two attention layers through the flash
kernel, a dense MLP and eight layers of 64 experts at some 16-260 rows an
expert. None for a program without the counter."""
from lib.loopclock import delta
from lib.readers import hist_delta


def read(ctx):
    tokens = delta(ctx, "serving_prefill_tokens_total", 'kind="true"')
    if not tokens:
        return None
    seconds = hist_delta(ctx["snap_w0"], ctx["snap_w1"], "serving_prefill_seconds")[0]
    return 1e3 * seconds / (tokens / 1e3)
