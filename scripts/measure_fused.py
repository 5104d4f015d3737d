"""On-chip comparison of the FedAvg round builders (scratch measurement).

Usage: python scripts/measure_fused.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from bench import BATCH, K, LR, SIZES, _flops_per_round, peak_tflops
from pygrid_tpu.models import mlp
from pygrid_tpu.parallel import make_fused_rounds, make_scanned_rounds


def flops_per_round(local_steps=1):
    return _flops_per_round() * local_steps


def measure(fn, params, X, y, lr, n_small, n_large, trials=6):
    def run(f):
        t0 = time.perf_counter()
        out = f(params, X, y, lr)
        _ = float(out[1][-1])
        return time.perf_counter() - t0

    t_s = min(run(fn[n_small]) for _ in range(trials))
    t_l = min(run(fn[n_large]) for _ in range(trials))
    return (t_l - t_s) / (n_large - n_small)


def main():
    print(f"device: {jax.devices()[0]}", file=sys.stderr)
    params = mlp.init(jax.random.PRNGKey(0), SIZES)
    X = jax.random.normal(jax.random.PRNGKey(1), (K, BATCH, SIZES[0]))
    labels = jax.random.randint(jax.random.PRNGKey(2), (K, BATCH), 0, SIZES[-1])
    y = jax.nn.one_hot(labels, SIZES[-1])
    lr = jnp.float32(LR)
    n_s, n_l = 10, 200

    cases = {
        "opaque N=1": lambda n: make_scanned_rounds(
            mlp.training_step, n, local_steps=1,
            matmul_precision="BF16_BF16_F32"),
        "fused  N=1": lambda n: make_fused_rounds(
            mlp.loss_and_acc, n, local_steps=1,
            matmul_precision="BF16_BF16_F32"),
        "folded N=1": lambda n: make_scanned_rounds(
            mlp.training_step, n, local_steps=1,
            matmul_precision="BF16_BF16_F32", fold_clients=True),
        "opaque N=4": lambda n: make_scanned_rounds(
            mlp.training_step, n, local_steps=4,
            matmul_precision="BF16_BF16_F32"),
        "fused  N=4": lambda n: make_fused_rounds(
            mlp.loss_and_acc, n, local_steps=4,
            matmul_precision="BF16_BF16_F32"),
        "fusedb N=4": lambda n: make_fused_rounds(
            mlp.loss_and_acc, n, local_steps=4,
            matmul_precision="BF16_BF16_F32", carry_dtype=jnp.bfloat16),
    }
    for name, mk in cases.items():
        steps = 4 if "N=4" in name else 1
        fns = {n: mk(n) for n in (n_s, n_l)}
        for f in fns.values():
            out = f(params, X, y, lr)
            _ = float(out[1][-1])
        dt = measure(fns, params, X, y, lr, n_s, n_l)
        mfu = flops_per_round(steps) / dt / (peak_tflops() * 1e12)
        print(
            f"{name}: {dt*1e3:.3f} ms/round  MFU {mfu*100:.1f}%",
            file=sys.stderr,
        )


if __name__ == "__main__":
    main()
