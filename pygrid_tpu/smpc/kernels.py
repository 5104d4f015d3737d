"""Batched, jitted SMPC kernels — the performance path.

The class API in :mod:`pygrid_tpu.smpc.additive` is the protocol-faithful,
numpy-facing surface. These functions are its pure-XLA core: everything is a
function of stacked ring arrays, jit-compiled once and ``vmap``-ed over a
batch axis so one chip runs B independent SMPC instances (B×P virtual
parties) per launch — the TPU-native answer to the reference's
one-process-per-party grid (SURVEY.md §2.5, BASELINE.json north star).

Layouts: shares are ``Ring64`` with leading axes ``[B?, P, ...]`` where P is
the party axis. "Opening" a masked value is a sum over P — the mesh-sharded
variant of these kernels (:mod:`pygrid_tpu.smpc.sharded`) puts P on a
``Mesh`` axis via ``shard_map`` and opens with an exact collective
(:func:`pygrid_tpu.smpc.ring.ring_psum`) instead of socket traffic.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from pygrid_tpu.smpc import ring as R


def share_kernel(key: jax.Array, value: R.Ring64, n_parties: int) -> R.Ring64:
    """Split a ring tensor into P additive shares, stacked on axis 0."""
    keys = jax.random.split(key, n_parties - 1)
    rand_lo, rand_hi, total = [], [], None
    for k in keys:
        r = R.ring_random(k, value.shape)
        rand_lo.append(r.lo)
        rand_hi.append(r.hi)
        total = r if total is None else R.ring_add(total, r)
    last = R.ring_sub(value, total)
    return R.Ring64(
        jnp.stack(rand_lo + [last.lo]), jnp.stack(rand_hi + [last.hi])
    )


def reconstruct_kernel(shares: R.Ring64) -> R.Ring64:
    """Sum over the party axis (axis 0). With shares sharded over a mesh
    party axis this is the collective 'open'."""
    total = R.Ring64(shares.lo[0], shares.hi[0])
    for i in range(1, shares.lo.shape[0]):
        total = R.ring_add(total, R.Ring64(shares.lo[i], shares.hi[i]))
    return total


def _party_map(fn, *stacked: R.Ring64) -> R.Ring64:
    """vmap a ring fn over the party axis of stacked shares."""
    return jax.vmap(fn)(*stacked)


def beaver_combine(
    x_sh: R.Ring64,
    y_sh: R.Ring64,
    a_sh: R.Ring64,
    b_sh: R.Ring64,
    c_sh: R.Ring64,
    op: str,
) -> R.Ring64:
    """One full Beaver round on stacked shares [P, ...] -> product shares.

    z_i = c_i + d∘b_i + a_i∘e + [i=0] d∘e,  d = open(x−a), e = open(y−b).
    """
    ring_op = R.ring_mul if op == "mul" else R.ring_matmul
    d = reconstruct_kernel(R.ring_sub(x_sh, a_sh))
    e = reconstruct_kernel(R.ring_sub(y_sh, b_sh))
    db = _party_map(lambda b: ring_op(d, b), b_sh)
    ae = _party_map(lambda a: ring_op(a, e), a_sh)
    z = R.ring_add(c_sh, R.ring_add(db, ae))
    de = ring_op(d, e)
    z0 = R.ring_add(R.Ring64(z.lo[0], z.hi[0]), de)
    return R.Ring64(z.lo.at[0].set(z0.lo), z.hi.at[0].set(z0.hi))


#: mask-and-open truncation offset magnitude: the secret product z must
#: satisfy |z| < scale * 2^OFFSET_BITS, i.e. |x·y| < 2^OFFSET_BITS / scale
OFFSET_BITS = 30


def masked_truncate(
    z_sh: R.Ring64, r_sh: R.Ring64, rp_sh: R.Ring64, scale: int
) -> R.Ring64:
    """Rescale product shares by ``scale`` without anyone seeing the secret.

    Mask-and-open truncation with a dealer-provided pair
    (``r`` uniform < 2^62, ``r' = floor(r/scale)``):

    1. open ``m = z + OFFSET + r``  (OFFSET = scale·2^30 keeps the sum
       positive; m < 2^63 so the ring sum is the exact integer sum);
    2. publicly compute ``q = floor(m / scale)``;
    3. output shares: party 0 holds ``q − 2^30 − r'_0``, party i>0 holds
       ``−r'_i``  →  the shares sum to ``floor(z/scale) + ε``, ε ∈ {0, 1}.

    Nobody learns z: parties only ever see their own shares, and the opened
    ``m`` is statistically masked by r (distance ≈ 2^(log2(scale)+31−62)).
    Compare the dealer-sees-all alternative
    :meth:`~pygrid_tpu.smpc.provider.CryptoProvider.reshare_truncated`,
    which reconstructs z at the dealer (reference-faithful exactness, kept
    behind ``trusted_dealer=True``).
    """
    import numpy as np

    offset = R.to_ring(np.uint64(scale) << np.uint64(OFFSET_BITS))
    m_sh = R.ring_add(z_sh, r_sh)
    m0 = R.ring_add(R.Ring64(m_sh.lo[0], m_sh.hi[0]), offset)
    m_sh = R.Ring64(m_sh.lo.at[0].set(m0.lo), m_sh.hi.at[0].set(m0.hi))
    m = reconstruct_kernel(m_sh)  # public masked value, < 2^63
    q = R.ring_div_const(m, scale)
    out = _party_map(R.ring_neg, rp_sh)  # party i: −r'_i
    head = R.ring_add(
        R.Ring64(out.lo[0], out.hi[0]),
        R.ring_sub(q, R.to_ring(np.uint64(1) << np.uint64(OFFSET_BITS))),
    )
    return R.Ring64(
        out.lo.at[0].set(head.lo), out.hi.at[0].set(head.hi)
    )


@partial(jax.jit, static_argnames=("op", "n_parties"))
def batched_beaver(
    key: jax.Array,
    x_sh: R.Ring64,
    y_sh: R.Ring64,
    op: str = "matmul",
    n_parties: int = 3,
) -> R.Ring64:
    """B independent Beaver rounds, triples generated on-chip.

    ``x_sh``/``y_sh``: shares with leading axes [B, P, ...]. The triple
    dealer runs inside the same XLA program (trusted-dealer simulation), so
    the whole round — deal, mask, open, combine — is one launch.
    """
    ring_op = R.ring_mul if op == "mul" else R.ring_matmul
    B = x_sh.lo.shape[0]

    def one(bkey, x1, y1):
        k1, k2, k3 = jax.random.split(bkey, 3)
        a = R.ring_random(k1, x1.lo.shape[1:])
        b = R.ring_random(k2, y1.lo.shape[1:])
        c = ring_op(a, b)
        a_sh = share_kernel(k3, a, n_parties)
        b_sh = share_kernel(jax.random.fold_in(k3, 1), b, n_parties)
        c_sh = share_kernel(jax.random.fold_in(k3, 2), c, n_parties)
        return beaver_combine(x1, y1, a_sh, b_sh, c_sh, op)

    keys = jax.random.split(key, B)
    return jax.vmap(one)(keys, x_sh, y_sh)
