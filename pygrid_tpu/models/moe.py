"""Mixture-of-experts FFN with expert parallelism.

The reference has no MoE (SURVEY.md §2.5 lists expert parallelism as
absent); this provides the TPU-native expert-parallel layer the framework
needs for sparse scaling. GShard-style top-1 routing with capacity:

- every shard routes its local tokens (gate softmax → argmax expert,
  position-in-expert via cumsum, tokens beyond capacity dropped);
- dispatch is two ``lax.all_to_all``s over the ``"expert"`` mesh axis:
  token buckets travel to the devices owning their expert, the expert FFN
  runs batched per device, results travel back and are combined with the
  gate weights. The all_to_alls ride ICI — no host gather ever sees the
  token stream.

With enough capacity (no drops) the expert-parallel output equals the
dense compute-every-expert reference bit-for-bit up to float
reassociation — that is what the tests pin.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def init(
    key: jax.Array, d_model: int, d_ff: int, n_experts: int
) -> list[jax.Array]:
    """[gate Wg, expert W1, b1, W2, b2] with experts stacked on axis 0."""
    kg, k1, k2 = jax.random.split(key, 3)
    scale1 = 1.0 / math.sqrt(d_model)
    scale2 = 1.0 / math.sqrt(d_ff)
    return [
        jax.random.normal(kg, (d_model, n_experts)) * scale1,
        jax.random.normal(k1, (n_experts, d_model, d_ff)) * scale1,
        jnp.zeros((n_experts, d_ff)),
        jax.random.normal(k2, (n_experts, d_ff, d_model)) * scale2,
        jnp.zeros((n_experts, d_model)),
    ]


def _expert_ffn(w1, b1, w2, b2, h):
    return jax.nn.gelu(h @ w1 + b1) @ w2 + b2


def _route(x: jax.Array, wg: jax.Array, capacity: int):
    """Top-1 routing: dispatch one-hot [t, E, C] + combine weights."""
    n_experts = wg.shape[1]
    gates = jax.nn.softmax(x @ wg, axis=-1)  # [t, E]
    expert_idx = jnp.argmax(gates, axis=-1)  # [t]
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=x.dtype)  # [t, E]
    # arrival order within each expert's bucket
    pos = (
        jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0), expert_idx[:, None], axis=1
        )[:, 0]
        - 1
    ).astype(jnp.int32)
    keep = (pos < capacity).astype(x.dtype)
    dispatch = (
        onehot[:, :, None]
        * jax.nn.one_hot(pos, capacity, dtype=x.dtype)[:, None, :]
        * keep[:, None, None]
    )  # [t, E, C]
    gate_val = jnp.take_along_axis(gates, expert_idx[:, None], axis=1)[:, 0]
    combine = dispatch * gate_val[:, None, None]
    return dispatch, combine


def apply_dense(params: list, x: jax.Array) -> jax.Array:
    """Single-device reference: every expert computes every token, the
    top-1 gate selects (exact — no capacity drops)."""
    wg, w1, b1, w2, b2 = params
    gates = jax.nn.softmax(x @ wg, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)
    all_out = jax.vmap(
        lambda w1e, b1e, w2e, b2e: _expert_ffn(w1e, b1e, w2e, b2e, x)
    )(w1, b1, w2, b2)  # [E, t, d]
    sel = jnp.take_along_axis(
        all_out, expert_idx[None, :, None], axis=0
    )[0]  # [t, d]
    gate_val = jnp.take_along_axis(gates, expert_idx[:, None], axis=1)
    return sel * gate_val


def param_specs(n_leading: int = 5, axis: str = "expert"):
    """Shardings for ``init``'s param list: gate replicated, experts
    sharded on their stacking axis."""
    return [P()] + [P(axis), P(axis), P(axis), P(axis)][: n_leading - 1]


def apply_expert_parallel(
    params: list,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "expert",
    capacity_factor: float = 2.0,
) -> jax.Array:
    """Expert-parallel MoE: tokens sharded on [B] over ``axis``, experts
    sharded on their stacking axis; two all_to_alls move the buckets."""
    p_sz = mesh.shape[axis]
    wg = params[0]
    n_experts = wg.shape[1]
    if n_experts % p_sz:
        raise ValueError(
            f"experts ({n_experts}) must divide over mesh axis ({p_sz})"
        )
    if x.shape[0] % p_sz:
        raise ValueError(f"tokens ({x.shape[0]}) must shard over {p_sz}")
    t_local = x.shape[0] // p_sz
    capacity = max(1, int(math.ceil(t_local * capacity_factor / n_experts)))

    def inner(wg, w1, b1, w2, b2, x):
        dispatch, combine = _route(x, wg, capacity)  # [t, E, C]
        buckets = jnp.einsum("tec,td->ecd", dispatch, x)  # [E, C, d]
        # buckets for expert e hop to e's owner; capacity axis concatenates
        expert_in = lax.all_to_all(
            buckets, axis, split_axis=0, concat_axis=1, tiled=True
        )  # [E/P, P*C, d]
        expert_out = jax.vmap(_expert_ffn)(w1, b1, w2, b2, expert_in)
        back = lax.all_to_all(
            expert_out, axis, split_axis=1, concat_axis=0, tiled=True
        )  # [E, C, d]
        return jnp.einsum("tec,ecd->td", combine, back)

    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
    )(*params, x)
