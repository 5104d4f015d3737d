"""Mesh-sharded SMPC: the party axis as a ``jax.sharding.Mesh`` axis.

The TPU-native answer to the reference's share distribution across physical
nodes (``/root/reference/apps/network/src/app/routes/network.py:16,98-131``
hands each of 4 nodes one share): here parties are the leading array axis,
that axis is sharded over a mesh axis, each device holds its parties' shares
in its own HBM, and the only cross-party traffic in a Beaver round — opening
the masked values d = x−a and e = y−b — is a ``psum``-shaped collective over
the party axis riding ICI, not sockets (:func:`pygrid_tpu.smpc.ring.ring_psum`
does the exact mod-2^64 sum; carries can't ride a raw u32 psum).

Three tiers of the same kernels, one semantic:

- in-process protocol objects (``smpc.additive``) — parity surface;
- single-chip vmapped batches (``smpc.kernels``) — B×P virtual parties per
  launch;
- this module — parties (and/or instance batches) spread over a device mesh
  via ``shard_map``, scaling P beyond one chip's HBM.

Layout: stacked shares ``[P, B, ...]`` (party-major, then instance batch).
``in_specs=P(axis)`` shards the party axis; everything after it stays local.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pygrid_tpu.smpc import ring as R
from pygrid_tpu.smpc.kernels import share_kernel


def party_sharding(mesh: Mesh, axis: str = "parties") -> NamedSharding:
    """Sharding that puts the leading (party) axis on ``axis``."""
    return NamedSharding(mesh, P(axis))


def _batched(ring_op: Callable) -> Callable:
    """Lift a ring op over the instance-batch axis that follows the party
    axis (ring ops are written for single instances)."""
    return jax.vmap(ring_op)


def make_sharded_open(
    mesh: Mesh, axis: str = "parties"
) -> Callable[[R.Ring64], R.Ring64]:
    """Reconstruct ("open") shares ``[P, ...]`` sharded over ``axis``:
    one exact collective sum, result replicated on every device."""

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(),
        check_vma=False,
    )
    def open_(shares: R.Ring64) -> R.Ring64:
        return R.ring_psum(shares, axis, local_axis=0)

    return open_


def make_sharded_beaver(
    mesh: Mesh, op: str = "matmul", axis: str = "parties"
) -> Callable:
    """Beaver combine with the party axis sharded over ``axis``.

    Takes stacked shares ``x_sh, y_sh, a_sh, b_sh, c_sh`` of layout
    ``[P, B, ...]`` (triple shares from any dealer — ``share_kernel`` or the
    cross-node provider) and returns product shares, same layout. The two
    opens are party-axis collectives; everything else is local to each
    device's party block.
    """
    ring_op = R.ring_mul if op == "mul" else R.ring_matmul
    bop = _batched(ring_op)

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis),) * 5,
        out_specs=P(axis),
        check_vma=False,
    )
    def combine(x_sh, y_sh, a_sh, b_sh, c_sh):
        # local blocks: [P_local, B, ...]
        d = R.ring_psum(R.ring_sub(x_sh, a_sh), axis, local_axis=0)
        e = R.ring_psum(R.ring_sub(y_sh, b_sh), axis, local_axis=0)
        db = jax.vmap(lambda b: bop(d, b))(b_sh)
        ae = jax.vmap(lambda a: bop(a, e))(a_sh)
        z = R.ring_add(c_sh, R.ring_add(db, ae))
        # the public d∘e correction belongs to exactly one party: global
        # party 0 = local row 0 on the first shard of the axis
        de = bop(d, e)
        z0 = R.ring_add(R.Ring64(z.lo[0], z.hi[0]), de)
        is_first = (jax.lax.axis_index(axis) == 0).astype(jnp.uint32)
        head = R.Ring64(
            is_first * z0.lo + (1 - is_first) * z.lo[0],
            is_first * z0.hi + (1 - is_first) * z.hi[0],
        )
        return R.Ring64(
            z.lo.at[0].set(head.lo), z.hi.at[0].set(head.hi)
        )

    return combine


def deal_triples(
    key: jax.Array,
    x_shape: tuple,
    y_shape: tuple,
    n_parties: int,
    op: str = "matmul",
    batch: int | None = None,
) -> tuple[R.Ring64, R.Ring64, R.Ring64]:
    """Dealer-side triple generation for the sharded kernels: returns
    ``(a_sh, b_sh, c_sh)`` stacked ``[P, ...]`` (or ``[P, B, ...]``).
    Runs as ordinary jit — placed/partitioned by the caller's shardings;
    in production the cross-node provider (smpc/remote.py) plays dealer."""
    ring_op = R.ring_mul if op == "mul" else R.ring_matmul

    def one(k):
        k1, k2, k3 = jax.random.split(k, 3)
        a = R.ring_random(k1, x_shape)
        b = R.ring_random(k2, y_shape)
        c = ring_op(a, b)
        return (
            share_kernel(k3, a, n_parties),
            share_kernel(jax.random.fold_in(k3, 1), b, n_parties),
            share_kernel(jax.random.fold_in(k3, 2), c, n_parties),
        )

    if batch is None:
        return one(key)
    keys = jax.random.split(key, batch)
    a_sh, b_sh, c_sh = jax.vmap(one, out_axes=1)(keys)
    return a_sh, b_sh, c_sh


def sharded_beaver(
    mesh: Mesh,
    key: jax.Array,
    x_sh: R.Ring64,
    y_sh: R.Ring64,
    op: str = "matmul",
    axis: str = "parties",
) -> R.Ring64:
    """One full sharded Beaver round: deal triples, place shares on the
    party mesh axis, combine with collective opens."""
    n_parties = x_sh.lo.shape[0]
    batch = x_sh.lo.shape[1]
    a_sh, b_sh, c_sh = deal_triples(
        key,
        x_sh.lo.shape[2:],
        y_sh.lo.shape[2:],
        n_parties,
        op=op,
        batch=batch,
    )
    sharding = party_sharding(mesh, axis)
    place = lambda r: jax.tree.map(lambda a: jax.device_put(a, sharding), r)
    combine = make_sharded_beaver(mesh, op=op, axis=axis)
    return combine(
        place(x_sh), place(y_sh), place(a_sh), place(b_sh), place(c_sh)
    )
