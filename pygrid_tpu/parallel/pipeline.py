"""Pipeline parallelism: stage-sharded layers, microbatched schedule.

The reference has no pipeline parallelism (SURVEY.md §2.5 — its models are
MNIST-sized), but a TPU-native framework must place deep models across
chips. GPipe-style schedule over a ``"stage"`` mesh axis via ``shard_map``:

- the stacked per-stage parameters live sharded on their leading axis —
  each device holds exactly its stage's weights;
- the batch is split into M microbatches; at schedule tick t, stage s
  works on microbatch t−s, so all stages run concurrently once the
  pipeline fills (bubble fraction (P−1)/(T) with T = M+P−1 ticks);
- activations hop stage→stage+1 each tick with ``lax.ppermute`` (one ICI
  neighbor hop — the cheapest collective there is);
- the tick loop is a ``lax.scan``, so reverse-mode AD differentiates the
  whole schedule (ppermute transposes to the reverse ring) — training,
  not just inference.

``stage_fn`` must be shape-preserving on the activation (standard for
transformer blocks); embed/head layers run outside the pipelined trunk.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stage_specs(stacked_params, axis: str = "stage"):
    """PartitionSpecs sharding each leaf's leading (stage) axis."""
    return jax.tree.map(
        lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), stacked_params
    )


def pipeline_apply(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "stage",
    n_microbatches: int | None = None,
) -> jax.Array:
    """Run ``x`` through P pipelined stages; exact vs. the sequential loop.

    ``stacked_params``: pytree whose leaves have leading axis P (one slice
    per stage). ``x``: [B, ...] with B divisible by ``n_microbatches``
    (default P). Returns the final-stage activations, replicated."""
    p_sz = mesh.shape[axis]
    M = n_microbatches or p_sz
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    x_micro = x.reshape(M, mb, *x.shape[1:])
    fwd = [(i, i + 1) for i in range(p_sz - 1)]  # stage s -> s+1 chain

    def inner(params, x_micro):
        params = jax.tree.map(lambda l: l[0], params)  # this device's stage
        s = lax.axis_index(axis)
        is_first, is_last = s == 0, s == p_sz - 1
        # fresh carries are replication-typed; mark them device-varying so
        # the scan carry matches the ppermute-varying activations
        act0 = lax.pcast(jnp.zeros_like(x_micro[0]), axis, to="varying")
        outs0 = lax.pcast(jnp.zeros_like(x_micro), axis, to="varying")

        def tick(carry, t):
            act, outs = carry
            recv = lax.ppermute(act, axis, fwd)
            inp = jnp.where(
                is_first, x_micro[jnp.clip(t, 0, M - 1)], recv
            )
            h = stage_fn(params, inp)
            active = (t >= s) & (t < s + M)
            h = jnp.where(active, h, jnp.zeros_like(h))
            emit_idx = jnp.clip(t - s, 0, M - 1)
            outs = outs.at[emit_idx].set(
                jnp.where(active & is_last, h, outs[emit_idx])
            )
            return (h, outs), None

        (_, outs), _ = lax.scan(
            tick, (act0, outs0), jnp.arange(M + p_sz - 1)
        )
        # only the last stage holds real outputs; broadcast over the ring
        return lax.psum(jnp.where(is_last, outs, 0.0), axis)

    spec_p = stage_specs(stacked_params, axis)
    out = shard_map(
        inner,
        mesh=mesh,
        in_specs=(spec_p, P()),
        out_specs=P(),
    )(stacked_params, x_micro)
    return out.reshape(B, *x.shape[1:])


def sequential_apply(stage_fn: Callable, stacked_params, x: jax.Array):
    """Single-device reference: fold the stages in order (what the pipeline
    must match bit-for-bit up to float reassociation)."""
    p_sz = jax.tree.leaves(stacked_params)[0].shape[0]
    h = x
    for s in range(p_sz):
        params_s = jax.tree.map(lambda l: l[s], stacked_params)
        h = stage_fn(params_s, h)
    return h


def make_pipeline_training_step(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh: Mesh,
    axis: str = "stage",
    n_microbatches: int | None = None,
):
    """SGD step on a pipelined trunk: value_and_grad through the schedule.

    ``loss_fn(y_hat, y) -> scalar``. Returns ``step(stacked_params, X, y,
    lr) -> (loss, new_stacked_params)`` — grads flow backward through the
    ppermute ring exactly as activations flowed forward."""
    apply = partial(
        pipeline_apply, stage_fn, mesh=mesh, axis=axis,
        n_microbatches=n_microbatches,
    )

    def objective(stacked_params, X, y):
        return loss_fn(apply(stacked_params, x=X), y)

    def step(stacked_params, X, y, lr):
        loss, grads = jax.value_and_grad(objective)(stacked_params, X, y)
        new_params = jax.tree.map(
            lambda p, g: p - lr * g, stacked_params, grads
        )
        return loss, new_params

    return step
