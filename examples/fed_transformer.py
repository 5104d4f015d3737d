"""Federated transformer training — the flagship composition.

No reference analog (the reference's model zoo stops at the MNIST
MLP/CNN, SURVEY.md §5.7): K simulated clients each run local SGD on a
decoder-only transformer — the Pallas flash-attention kernel inside
every client step, bf16 mixed precision on TPU — and FedAvg aggregates
the diffs, all in ONE compiled program per round
(``parallel.make_fused_rounds`` over ``models.transformer`` — the
round-5 fused-aggregation builder whose final-step weight grads fold
into one matmul per layer, plus the bf16 CE backward on TPU). The
same composition trains over a client-sharded device mesh in
``__graft_entry__.dryrun_multichip`` (scenarios 8 and 9) and is
benchmarked on the real chip by ``bench.py bench_fed_transformer``.

The task is tiny on purpose (copy-class sequences): the point is the
composition converging, not the corpus.
"""

from __future__ import annotations

import argparse
import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np

import jax
import jax.numpy as jnp

from pygrid_tpu.models import transformer
from pygrid_tpu.parallel import make_fused_rounds
from pygrid_tpu.parallel.pallas_attention import flash_attention

K, B, L = 4, 4, 32          # clients × per-client batch × sequence length
ROUNDS = 30


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--interpret", action="store_true",
        help="run the Pallas kernel in interpret mode and the matmuls in "
        "f32 — the way to run this example without a TPU "
        "(JAX_PLATFORMS=cpu); the default Mosaic-compiles the kernel "
        "and needs the chip",
    )
    interpret = parser.parse_args().interpret
    cfg = transformer.TransformerConfig(
        vocab=32, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=L
    )
    loss_fn = partial(
        transformer.loss_and_acc,
        cfg=cfg,
        attn_fn=partial(flash_attention, interpret=interpret),
        # mixed precision (and the bf16 CE backward) earn their keep on
        # the MXU; interpreted on a CPU they just slow the run down
        compute_dtype=None if interpret else "bfloat16",
        ce_grad_dtype=None if interpret else "bfloat16",
    )

    # task: one base corpus, each client holding ITS OWN token shift of
    # it — non-iid shards whose next-token rule is learnable only jointly
    rng = np.random.default_rng(0)
    corpus = rng.integers(0, cfg.vocab, (B, L + 1))
    base = (corpus[None] + np.arange(K)[:, None, None]) % cfg.vocab
    X = jnp.asarray(base[..., :-1])
    y = jnp.asarray(base[..., 1:])

    params = transformer.init(jax.random.PRNGKey(0), cfg)
    rounds = make_fused_rounds(loss_fn, n_rounds=ROUNDS)
    final, losses, accs = rounds(params, X, y, jnp.float32(0.3))
    first, last = float(losses[0]), float(losses[-1])
    print(
        f"federated transformer: {K} clients × {ROUNDS} rounds "
        f"(flash attention, {'interpreted, f32' if interpret else 'compiled, bf16'}"
        f" on {jax.devices()[0].platform}) — "
        f"loss {first:.3f} → {last:.3f}, acc {float(accs[-1]):.2f}"
    )
    if not last < first - 0.3:
        print("loss did not improve", file=sys.stderr)
        return 1

    # serve what you trained: KV-cache greedy decoding from the
    # federated params (models/decode.py; over a grid this same call
    # runs server-side via client.run_remote_generation)
    from pygrid_tpu.models import decode

    prompt = X[0, :1, :8]  # first 8 tokens of client 0's shard
    toks = decode.generate(final, prompt, 12, cfg)
    print(
        f"generated continuation of {list(map(int, prompt[0]))}: "
        f"{list(map(int, toks[0]))}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
