"""Model FLOP/s utilisation: the operations a token's forward and backward
need (the architecture's adapter counts them, ``train_flops_per_token``;
recomputation not counted) times tokens per second, over the device's
published bf16 peak (``lib/peaks.py``), 0-100."""
from lib.peaks import peak


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    flops = ctx["flops_per_token"] * ctx["tokens_per_s"]
    return 100.0 * flops / peak(ctx["device_kind"])["bf16_flops_per_s"]
