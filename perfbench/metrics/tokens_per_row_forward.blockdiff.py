"""Tokens a row's forward yields: growth of ``serving_tokens_total`` over
growth of ``serving_block_forwards_total`` (row-forwards, denoising and
commit: ``serving/engine.py`` ``_block_inputs``) over the window. A block of
four costs ``denoising_steps + 1`` forwards, so the mix of 4, 4, 2, 1 steps
gives 1,024 / 960 = 1.07. None for a program without the counter."""
from lib.loopclock import delta


def read(ctx):
    forwards = delta(ctx, "serving_block_forwards_total")
    tokens = delta(ctx, "serving_tokens_total")
    if not forwards or tokens is None:
        return None
    return tokens / forwards
