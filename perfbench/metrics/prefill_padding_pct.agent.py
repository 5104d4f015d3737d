"""Share of the positions the prefill programs computed that were their
bucket's padding: 100 x (1 - true / padded) of
``serving_prefill_tokens_total`` over the window. Buckets double (256 ..
4,096), so a prompt just over one pays for nearly twice its length in
every matrix product, the experts' among them. None for a program without
the counter."""
from lib.loopclock import delta

TOKENS = "serving_prefill_tokens_total"


def read(ctx):
    true = delta(ctx, TOKENS, 'kind="true"')
    padded = delta(ctx, TOKENS, 'kind="padded"')
    if true is None or not padded:
        return None
    return 100.0 * (1.0 - true / padded)
