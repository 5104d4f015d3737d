"""ZeRO-style fully-sharded data parallelism (FSDP) over a mesh axis.

The reference has no analog (its parallelism stops at per-worker sockets
— SURVEY.md §2.5; like tensor/pipeline/sequence/expert parallelism this
is a bonus axis the TPU-native design gets from the mesh): parameters,
gradients AND optimizer state live as flat shards over an ``"fsdp"``
mesh axis — each device holds 1/N of every tensor — and the full
parameters exist only transiently inside the compiled step:

- **all_gather** (tiled, over ICI) materializes the full parameters from
  the shards right before the forward pass;
- the backward produces full-size gradients which are immediately
  **psum_scatter**-ed back to shards — the reduce-scatter both sums the
  data-parallel gradient contributions across devices and leaves each
  device exactly its own shard (ZeRO's reduce-scatter trick: the same
  collective does the DP mean and the partitioning);
- the optimizer update (SGD / momentum / Adam) runs on the local shard
  against local optimizer moments that are never gathered at all —
  ZeRO-1 (optimizer state), ZeRO-2 (gradients) and ZeRO-3 (parameters)
  in one shard_map.

XLA overlaps the gathers with computation where profitable; the layout
is the scaling-book FSDP recipe (shard everything, gather just-in-time,
reduce-scatter gradients) rather than a translation of any torch FSDP
wrapper. Batches are sharded on their leading axis over the same mesh
axis, so the data-parallel and parameter-shard axes coincide (the usual
single-axis FSDP; compose with "model"/"seq" axes via a 2-D mesh and an
outer shard_map if needed).

Every leaf is flattened and zero-padded to a multiple of the axis size —
uneven layers (biases, layernorm scales) shard evenly with no
per-shape special cases, at the cost of at most ``n_shards - 1`` padding
elements per leaf (the padding is mathematically inert: its gradients
are zero and it is sliced away on unshard).
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _flat_padded(leaf: jax.Array, n: int) -> jax.Array:
    flat = leaf.reshape(-1)
    pad = (-flat.size) % n
    return jnp.pad(flat, (0, pad)) if pad else flat


def shard_params(
    params: Sequence[jax.Array], mesh: Mesh, axis: str = "fsdp"
) -> list[jax.Array]:
    """Lay the parameter list out as flat shards: each leaf becomes a
    ``[n_shards, ceil(size/n)]`` array sharded on its leading dim, so one
    row — 1/N of the (padded) tensor — lives on each device."""
    n = mesh.shape[axis]
    sharding = NamedSharding(mesh, P(axis))
    return [
        jax.device_put(_flat_padded(p, n).reshape(n, -1), sharding)
        for p in params
    ]


def unshard_params(
    shards: Sequence[jax.Array], params_like: Sequence[jax.Array]
) -> list[jax.Array]:
    """Reassemble full parameters (for eval/checkpoint/serde) from the
    sharded layout. ``params_like`` supplies shapes — any pytree-level
    template, e.g. the original init."""
    return [
        s.reshape(-1)[: p.size].reshape(p.shape).astype(p.dtype)
        for s, p in zip(shards, params_like)
    ]


def _sgd(shard, grad, lr, state, _count, _hp):
    return shard - lr * grad, state


def _momentum(shard, grad, lr, state, _count, hp):
    (m,) = state
    m = hp["beta1"] * m + grad
    return shard - lr * m, (m,)


def _adam(shard, grad, lr, state, count, hp):
    m, v = state
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["eps"]
    m = b1 * m + (1.0 - b1) * grad
    v = b2 * v + (1.0 - b2) * grad * grad
    t = count.astype(jnp.float32)
    mhat = m / (1.0 - b1**t)
    vhat = v / (1.0 - b2**t)
    return shard - lr * mhat / (jnp.sqrt(vhat) + eps), (m, v)


_OPTIMIZERS: dict[str, tuple[Callable, int]] = {
    "sgd": (_sgd, 0),        # (update_fn, number of moment buffers)
    "momentum": (_momentum, 1),
    "adam": (_adam, 2),
}


def make_fsdp_training_step(
    loss_fn: Callable,
    params_like: Sequence[jax.Array],
    mesh: Mesh,
    axis: str = "fsdp",
    optimizer: str = "sgd",
    beta1: float = 0.9,
    beta2: float = 0.999,
    eps: float = 1e-8,
) -> tuple[Callable, Callable]:
    """Build the sharded training step.

    ``loss_fn(params, X, y) -> (loss, aux)`` — differentiable in
    ``params`` (a list of arrays; both ``models.mlp.loss_and_acc`` and
    ``models.transformer.loss_and_acc`` fit via ``functools.partial``).
    ``params_like`` fixes the leaf shapes (e.g. the init output).

    Returns ``(init_state, step)``:

    - ``init_state(params) -> state`` — shards the parameters and zeroed
      optimizer moments over the mesh;
    - ``step(state, X, y, lr) -> (state, loss, aux)`` — one jitted
      gather → grad → reduce-scatter → sharded-update round. ``X``/``y``
      are GLOBAL batches sharded on their leading axis (use
      ``NamedSharding(mesh, P(axis))``); loss/aux come back as the
      global-batch mean.
    """
    if optimizer not in _OPTIMIZERS:
        raise ValueError(
            f"optimizer {optimizer!r} not in {sorted(_OPTIMIZERS)}"
        )
    update_fn, n_moments = _OPTIMIZERS[optimizer]
    hp = {"beta1": beta1, "beta2": beta2, "eps": eps}
    n = mesh.shape[axis]
    shapes = [(p.shape, p.size) for p in params_like]

    def init_state(params: Sequence[jax.Array]) -> dict:
        shards = shard_params(params, mesh, axis)
        return {
            "shards": shards,
            "moments": [
                [jnp.zeros_like(s) for s in shards]
                for _ in range(n_moments)
            ],
            "count": jnp.zeros((), jnp.int32),
        }

    def body(shards, moments, count, X, y, lr):
        # shards/moments arrive as [1, shard_len] blocks; lr/count are
        # replicated — pcast marks them device-varying so the local
        # update math stays local (see make_sharded_round's note)
        lr_v = lax.pcast(lr, axis, to="varying")
        count_v = lax.pcast(count + 1, axis, to="varying")

        full = [
            lax.all_gather(s[0], axis, tiled=True)[:size].reshape(shape)
            for s, (shape, size) in zip(shards, shapes)
        ]
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            full, X, y
        )
        # reduce-scatter: sums the per-device grads AND partitions them;
        # /n turns the sum of local-batch means into the global mean
        grad_shards = [
            lax.psum_scatter(_flat_padded(g, n), axis, tiled=True) / n
            for g in grads
        ]
        new_shards, new_moments = [], [[] for _ in range(n_moments)]
        for i, (s, g) in enumerate(zip(shards, grad_shards)):
            state_i = tuple(m[i][0] for m in moments)
            new_s, new_state_i = update_fn(
                s[0], g, lr_v, state_i, count_v, hp
            )
            new_shards.append(new_s[None])
            for k in range(n_moments):
                new_moments[k].append(new_state_i[k][None])
        return (
            new_shards,
            new_moments,
            count + 1,
            lax.pmean(loss, axis),
            lax.pmean(aux, axis),
        )

    spec_shard = P(axis)
    sharded_body = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            [spec_shard] * len(shapes),
            [[spec_shard] * len(shapes)] * n_moments,
            P(),
            spec_shard,
            spec_shard,
            P(),
        ),
        out_specs=(
            [spec_shard] * len(shapes),
            [[spec_shard] * len(shapes)] * n_moments,
            P(),
            P(),
            P(),
        ),
    )

    @jax.jit
    def step(state: dict, X, y, lr):
        new_shards, new_moments, count, loss, aux = sharded_body(
            state["shards"], state["moments"], state["count"], X, y, lr
        )
        return (
            {"shards": new_shards, "moments": new_moments, "count": count},
            loss,
            aux,
        )

    return init_state, step
