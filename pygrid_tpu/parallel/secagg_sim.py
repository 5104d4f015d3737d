"""On-mesh SecAgg simulation — the kernel-plane twin of the protocol in
`federated/secagg.py` / `client/secagg.py`.

Thousands of *simulated* clients don't ride sockets (SURVEY §2.6): their
masked reports are HBM-resident arrays and the "transmission to the
server" is a collective. This module runs the pairwise-mask half of
Bonawitz on a client axis that is either vmapped (single chip) or a mesh
axis (`shard_map` + `psum`), with masks expanded on device by Threefry
(`jax.random.bits`) — deterministic, so client *i* and client *j* derive
the identical pairwise stream from the shared pair key, and the uint32
sums cancel *identically* (wraparound is the group op, no float error).

Self-masks (`b_i`) are omitted: they exist to survive dropouts, and
on-mesh simulated clients cannot drop between launch and psum — the
collective is atomic. The protocol plane keeps the full double-masking.

Scope note: this simulates honest-but-curious aggregation semantics for
benchmarking/testing the masked-sum path at mesh scale; a real
adversarial server is only meaningful on the socket protocol, where
clients are separate trust domains.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _pair_key(key: jax.Array, i: jax.Array, j: jax.Array) -> jax.Array:
    """Symmetric pair key: fold in (min, max) so both ends agree."""
    lo, hi = jnp.minimum(i, j), jnp.maximum(i, j)
    return jax.random.fold_in(jax.random.fold_in(key, lo), hi)


def client_mask(
    key: jax.Array, i: jax.Array, n_clients: int, shape: tuple[int, ...]
) -> jax.Array:
    """Client i's total pairwise mask: Σ_{j>i} PRG(k_ij) − Σ_{j<i} PRG(k_ij)
    (uint32). O(n_clients) Threefry expansions, fused on device."""

    def body(j, acc):
        bits = jax.random.bits(_pair_key(key, i, j), shape, dtype=jnp.uint32)
        sign_pos = (j > i).astype(jnp.uint32)
        sign_neg = (j < i).astype(jnp.uint32)
        # +bits, -bits, or 0 — selected branchlessly so the loop is a scan
        return acc + sign_pos * bits - sign_neg * bits

    # the carry must inherit i's varying type under shard_map (vma typing:
    # an unvarying init cannot carry a varying body output), so build the
    # zeros from a draw that depends on i
    init = jax.random.bits(
        _pair_key(key, i, i), shape, dtype=jnp.uint32
    ) * jnp.uint32(0)
    return jax.lax.fori_loop(0, n_clients, body, init)


def mask_clients(key: jax.Array, quantized: jax.Array) -> jax.Array:
    """Mask a stacked [K, ...] uint32 client batch (vmapped single-chip
    path). The masked batch sums (mod 2^32) to exactly the unmasked sum."""
    K = quantized.shape[0]
    shape = quantized.shape[1:]
    masks = jax.vmap(
        lambda i: client_mask(key, i, K, shape)
    )(jnp.arange(K, dtype=jnp.uint32))
    return quantized + masks


def masked_sum(key: jax.Array, quantized: jax.Array) -> jax.Array:
    """Single-chip reference: mask every client, sum mod 2^32."""
    return jnp.sum(
        mask_clients(key, quantized), axis=0, dtype=jnp.uint32
    )


def make_sharded_masked_sum(mesh: Mesh, axis: str = "clients"):
    """The mesh path: clients sharded over ``axis``; each shard masks its
    own clients locally (Threefry keys are position-derived, so no
    cross-shard communication to build masks) and the server's "receive"
    is one ``psum`` — the masks cancel inside the collective.

    Returns ``fn(key, quantized[K, ...]) -> sum[...]`` (jitted)."""

    def shard_fn(key, q):
        axis_idx = jax.lax.axis_index(axis)
        per_shard = q.shape[0]
        K = per_shard * jax.lax.psum(1, axis)
        base = axis_idx * per_shard
        shape = q.shape[1:]
        masks = jax.vmap(
            lambda i: client_mask(key, base + i, K, shape)
        )(jnp.arange(per_shard, dtype=jnp.uint32))
        local = jnp.sum(q + masks, axis=0, dtype=jnp.uint32)
        # uint32 psum: lower on the mesh as an exact integer collective
        return jax.lax.psum(local, axis)

    sharded = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=P(),
    )
    fn = jax.jit(sharded)

    def run(key: jax.Array, quantized: jax.Array) -> jax.Array:
        spec = NamedSharding(mesh, P(axis))
        return fn(key, jax.device_put(quantized, spec))

    return run


def simulate_secagg_round(
    key: jax.Array,
    diffs: np.ndarray,
    clip_range: float,
    mesh: Mesh | None = None,
) -> np.ndarray:
    """End-to-end simulated round for a [K, ...] float diff batch:
    quantize (host, shared scale) → mask+sum on device (mesh or vmap) →
    dequantize the survivor mean. Bit-identical to summing the plaintext
    quantized diffs — the masks never meet the result."""
    from pygrid_tpu.federated import secagg

    K = diffs.shape[0]
    quantized = np.stack(
        [
            q[0]
            for q in (
                secagg.quantize([d], clip_range, K) for d in np.asarray(diffs)
            )
        ]
    )
    q_dev = jnp.asarray(quantized)
    if mesh is None:
        total = masked_sum(key, q_dev)
    else:
        total = make_sharded_masked_sum(mesh)(key, q_dev)
    return secagg.dequantize_sum(
        [np.asarray(total)], clip_range, K, K
    )[0]
