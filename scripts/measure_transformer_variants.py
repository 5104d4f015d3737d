"""On-chip comparison of fed-transformer round builders (scratch).

Usage: python scripts/measure_transformer_variants.py [flagship|long]
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from bench import peak_tflops
from pygrid_tpu.models import transformer
from pygrid_tpu.parallel import make_fused_rounds, make_scanned_rounds
from pygrid_tpu.parallel.pallas_attention import flash_attention


def flops_round(cfg, Kc, Bc):
    L = cfg.max_len
    tokens = Kc * Bc * L
    n_matmul = cfg.n_layers * (
        4 * cfg.d_model**2 + 2 * cfg.d_model * cfg.d_ff
    ) + cfg.vocab * cfg.d_model
    return (
        6.0 * n_matmul * tokens
        + 12.0 * cfg.n_layers * L * cfg.d_model * tokens
    ), tokens


def measure(mk, params, X, y, lr, small, large, trials=5):
    fns = {n: mk(n) for n in (small, large)}
    for fn in fns.values():
        out = fn(params, X, y, lr)
        _ = float(out[1][-1])

    def run(n):
        t0 = time.perf_counter()
        out = fns[n](params, X, y, lr)
        _ = float(out[1][-1])
        return time.perf_counter() - t0

    t_s = min(run(small) for _ in range(trials))
    t_l = min(run(large) for _ in range(trials))
    return (t_l - t_s) / (large - small)


def report(name, per, fl, tokens):
    mfu = fl / per / (peak_tflops() * 1e12)
    print(
        f"{name}: {per*1e3:.2f} ms/round, {tokens/per:,.0f} tok/s, "
        f"MFU {mfu*100:.1f}%",
        file=sys.stderr,
    )


def flagship():
    cfg = transformer.TransformerConfig(
        vocab=8192, d_model=512, n_heads=4, n_layers=4, d_ff=2048,
        max_len=512,
    )
    Kc, Bc = 8, 4
    fl, tokens = flops_round(cfg, Kc, Bc)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    X = jax.random.randint(jax.random.PRNGKey(1), (Kc, Bc, cfg.max_len), 0, cfg.vocab)
    y = jnp.roll(X, -1, axis=-1)
    lr = jnp.float32(0.1)

    step = transformer.make_training_step(
        cfg, attn_fn=flash_attention, compute_dtype="bfloat16"
    )
    loss_fn = partial(
        transformer.loss_and_acc, cfg=cfg, attn_fn=flash_attention,
        compute_dtype="bfloat16",
    )
    per = measure(
        lambda n: make_scanned_rounds(step, n_rounds=n),
        params, X, y, lr, 2, 10,
    )
    report("opaque", per, fl, tokens)
    per = measure(
        lambda n: make_fused_rounds(loss_fn, n_rounds=n),
        params, X, y, lr, 2, 10,
    )
    report("fused ", per, fl, tokens)
    step_g = transformer.make_training_step(
        cfg, attn_fn=flash_attention, compute_dtype="bfloat16",
        ce_grad_dtype="bfloat16",
    )
    per = measure(
        lambda n: make_scanned_rounds(step_g, n_rounds=n),
        params, X, y, lr, 2, 10,
    )
    report("opaque ce_bf16bwd", per, fl, tokens)
    loss_fn_g = partial(
        transformer.loss_and_acc, cfg=cfg, attn_fn=flash_attention,
        compute_dtype="bfloat16", ce_grad_dtype="bfloat16",
    )
    per = measure(
        lambda n: make_fused_rounds(loss_fn_g, n_rounds=n),
        params, X, y, lr, 2, 10,
    )
    report("fused  ce_bf16bwd", per, fl, tokens)


def long_ctx():
    for L, Kc in ((4096, 8), (8192, 4)):
        cfg = transformer.TransformerConfig(
            vocab=8192, d_model=512, n_heads=4, n_layers=4, d_ff=2048,
            max_len=L,
        )
        fl, tokens = flops_round(cfg, Kc, 1)
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        X = jax.random.randint(jax.random.PRNGKey(1), (Kc, 1, L), 0, cfg.vocab)
        y = jnp.roll(X, -1, axis=-1)
        lr = jnp.float32(0.1)
        variants = {
            "remat=True ": dict(remat=True),
            "remat=True  ce_bf16": dict(remat=True, ce_grad_dtype="bfloat16"),
            "remat=dots  ce_bf16": dict(remat="dots", ce_grad_dtype="bfloat16"),
            "remat=False ce_bf16": dict(remat=False, ce_grad_dtype="bfloat16"),
        }
        for name, kw in variants.items():
            loss_fn = partial(
                transformer.loss_and_acc, cfg=cfg, attn_fn=flash_attention,
                compute_dtype="bfloat16", **kw,
            )
            try:
                per = measure(
                    lambda n: make_fused_rounds(loss_fn, n_rounds=n),
                    params, X, y, lr, 1, 4, trials=4,
                )
                report(f"L={L} fused {name}", per, fl, tokens)
            except Exception as e:
                print(f"L={L} fused {name}: FAILED {type(e).__name__}: "
                      f"{str(e)[:200]}", file=sys.stderr)
        # opaque remat=True reference (current bench path)
        step = transformer.make_training_step(
            cfg, attn_fn=flash_attention, compute_dtype="bfloat16",
            remat=True,
        )
        per = measure(
            lambda n: make_scanned_rounds(step, n_rounds=n),
            params, X, y, lr, 1, 4, trials=4,
        )
        report(f"L={L} opaque remat=True ", per, fl, tokens)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "flagship"
    print(f"device: {jax.devices()[0]}", file=sys.stderr)
    if which == "flagship":
        flagship()
    else:
        long_ctx()
