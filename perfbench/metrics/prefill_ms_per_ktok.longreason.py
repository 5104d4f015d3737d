"""Prefill time per thousand true prompt tokens over the window: the sum
of ``serving_prefill_seconds`` over ``serving_prefill_tokens_total{kind=
true}``, ms. A prompt of 512-4,096 tokens runs whole: every layer's
retention in chunks of 128 against the slot's state. None for a program
without the counter."""
from lib.loopclock import delta
from lib.readers import hist_delta


def read(ctx):
    tokens = delta(ctx, "serving_prefill_tokens_total", 'kind="true"')
    if not tokens:
        return None
    seconds = hist_delta(ctx["snap_w0"], ctx["snap_w1"], "serving_prefill_seconds")[0]
    return 1e3 * seconds / (tokens / 1e3)
