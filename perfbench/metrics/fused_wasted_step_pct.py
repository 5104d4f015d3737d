"""Share of the window's decode row-steps that made no token: rows frozen
inside a fused scan (``serving_fused_wasted_steps_total``) over those plus
the tokens made (``serving_tokens_total``), 0-100."""
from lib.readers import counter_delta


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    wasted = counter_delta(ctx, "serving_fused_wasted_steps_total")
    tokens = counter_delta(ctx, "serving_tokens_total")
    return 100.0 * wasted / (wasted + tokens) if wasted + tokens else None
