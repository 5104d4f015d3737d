"""Ring-2^64 arithmetic on TPU as paired uint32 limbs.

The syft-0.2.9 ``AdditiveSharingTensor`` the reference depends on (consumed at
reference ``routes/data_centric/routes.py:215-236`` and exercised by
``tests/data_centric/test_basic_syft_operations.py:383-491``) does its ring
arithmetic in torch int64 with native wraparound. TPUs have no 64-bit integer
units, so here a ring element is a :class:`Ring64` pytree of two uint32 arrays
``(lo, hi)`` and every op is built from 32-bit limb arithmetic:

- add/sub/neg: limb add with carry (uint32 wraparound is well-defined in XLA);
- mul: 32x32→64 via 16-bit half-limbs;
- matmul: 8-bit limb decomposition into int32 ``dot_general``s (exact for
  contraction K ≤ 2^15 per chunk; longer K is scanned in chunks) recombined
  with shifted carries — see :func:`ring_matmul`;
- division by a small public constant (fixed-point truncation): 16-bit-limb
  long division.

Everything is jit/vmap-safe and shape-polymorphic over leading axes, so a
batch of SMPC parties is just a leading array axis (SURVEY.md §2.5).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

U32 = jnp.uint32
_MASK16 = np.uint32(0xFFFF)


class Ring64(NamedTuple):
    """One ring element per array position: value = hi * 2^32 + lo (mod 2^64)."""

    lo: jax.Array  # uint32
    hi: jax.Array  # uint32

    @property
    def shape(self):
        return self.lo.shape

    def __add__(self, other):
        return ring_add(self, other)

    def __sub__(self, other):
        return ring_sub(self, other)

    def __neg__(self):
        return ring_neg(self)

    def __mul__(self, other):
        return ring_mul(self, other)

    def __matmul__(self, other):
        return ring_matmul(self, other)


# --- host <-> ring conversion (numpy, exact via int64/uint64) ---------------


def to_ring(x: np.ndarray) -> Ring64:
    """Host integers (any int dtype, values taken mod 2^64) -> Ring64."""
    v = np.asarray(x).astype(np.uint64)
    return Ring64(
        lo=jnp.asarray((v & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        hi=jnp.asarray((v >> np.uint64(32)).astype(np.uint32)),
    )


def from_ring(r: Ring64) -> np.ndarray:
    """Ring64 -> host uint64 (exact)."""
    lo = np.asarray(r.lo).astype(np.uint64)
    hi = np.asarray(r.hi).astype(np.uint64)
    return (hi << np.uint64(32)) | lo


def from_ring_signed(r: Ring64) -> np.ndarray:
    """Ring64 -> host int64, two's-complement interpretation (exact)."""
    return from_ring(r).astype(np.int64)


def ring_zeros(shape) -> Ring64:
    return Ring64(jnp.zeros(shape, U32), jnp.zeros(shape, U32))


def ring_from_u32(lo: jax.Array) -> Ring64:
    return Ring64(lo.astype(U32), jnp.zeros_like(lo, U32))


# --- elementwise ring ops ---------------------------------------------------


def ring_add(a: Ring64, b: Ring64) -> Ring64:
    lo = a.lo + b.lo
    carry = (lo < a.lo).astype(U32)
    return Ring64(lo, a.hi + b.hi + carry)


def ring_neg(a: Ring64) -> Ring64:
    # two's complement: ~a + 1. The +1 carries into hi exactly when lo == 0
    # (~lo + 1 wraps to 0 only then).
    lo = ~a.lo + U32(1)
    carry = (a.lo == 0).astype(U32)
    return Ring64(lo, ~a.hi + carry)


def ring_sub(a: Ring64, b: Ring64) -> Ring64:
    return ring_add(a, ring_neg(b))


def _mul_u32(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """32x32 -> (lo32, hi32) exact product via 16-bit half-limbs."""
    a_lo, a_hi = a & _MASK16, a >> 16
    b_lo, b_hi = b & _MASK16, b >> 16
    ll = a_lo * b_lo  # < 2^32, exact in u32
    lh = a_lo * b_hi  # < 2^32
    hl = a_hi * b_lo
    hh = a_hi * b_hi
    # lo = ll + ((lh + hl) << 16)  with carries into hi
    mid = lh + hl
    mid_carry = (mid < lh).astype(U32)  # overflow of the u32 add
    lo = ll + (mid << 16)
    lo_carry = (lo < ll).astype(U32)
    hi = hh + (mid >> 16) + (mid_carry << 16) + lo_carry
    return lo, hi


def ring_mul(a: Ring64, b: Ring64) -> Ring64:
    """Elementwise 64x64 -> low 64 bits."""
    lo, hi = _mul_u32(a.lo, b.lo)
    hi = hi + a.lo * b.hi + a.hi * b.lo  # wrap mod 2^32 is correct here
    return Ring64(lo, hi)


def ring_mul_const(a: Ring64, c: int) -> Ring64:
    return ring_mul(a, to_ring(np.uint64(c % (1 << 64))))


# --- exact ring matmul via 8-bit limb dot_generals --------------------------

_CHUNK_K = 1 << 14  # int32 accumulator holds K * 255^2 exactly for K ≤ 2^15


def _to_limbs8(x_lo: jax.Array, x_hi: jax.Array) -> list[jax.Array]:
    """Split (lo, hi) uint32 pair into eight 8-bit limbs as int32 arrays."""
    limbs = []
    for word in (x_lo, x_hi):
        for s in (0, 8, 16, 24):
            limbs.append(((word >> s) & U32(0xFF)).astype(jnp.int32))
    return limbs


def _matmul_i32(a: jax.Array, b: jax.Array) -> jax.Array:
    return lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _ring_matmul_chunk(a: Ring64, b: Ring64) -> Ring64:
    """Exact [M,K]@[K,N] over the ring for K ≤ 2^15."""
    a_limbs = _to_limbs8(a.lo, a.hi)  # 8 limbs, int32 in [0, 255]
    b_limbs = _to_limbs8(b.lo, b.hi)
    out_shape = a.lo.shape[:-1] + b.lo.shape[1:]
    # Partial product P_ij (exact: ≤ K*255^2 < 2^31) contributes at bit
    # offset 8*(i+j); offsets ≥ 64 vanish mod 2^64. Summing partials of equal
    # offset *before* the ring add could overflow int32, so each P folds into
    # the u64 accumulator individually.
    acc = ring_zeros(out_shape)
    for i in range(8):
        for j in range(8 - i):
            p = _matmul_i32(a_limbs[i], b_limbs[j]).astype(U32)
            acc = ring_add(acc, _shift_left_u64(p, 8 * (i + j)))
    return acc


def _shift_left_u64(p_u32: jax.Array, off: int) -> Ring64:
    """(u32 value) << off as a Ring64, off in [0, 64)."""
    if off == 0:
        return Ring64(p_u32, jnp.zeros_like(p_u32))
    if off < 32:
        lo = p_u32 << off
        hi = p_u32 >> (32 - off)
        return Ring64(lo, hi)
    return Ring64(jnp.zeros_like(p_u32), p_u32 << (off - 32))


#: tri-state Pallas dispatch override: None = env/platform default
_PALLAS_ENABLED: bool | None = None


def set_pallas_enabled(enabled: bool | None) -> None:
    """Runtime kill-switch for the Pallas matmul dispatch.

    The dispatch decision is read at **trace time**, so flipping it must
    also drop cached executables — this clears the jit caches so every
    already-traced shape retraces with the new setting."""
    global _PALLAS_ENABLED
    _PALLAS_ENABLED = enabled
    jax.clear_caches()


def _pallas_eligible(a: Ring64, b: Ring64) -> bool:
    import os

    if _PALLAS_ENABLED is not None:
        if not _PALLAS_ENABLED:
            return False
    elif os.environ.get("PYGRID_TPU_NO_PALLAS"):
        # env read at trace time: set it before first use, or use
        # set_pallas_enabled() to flip a live process
        return False
    if a.lo.ndim != 2 or b.lo.ndim != 2:
        return False
    return jax.default_backend() == "tpu"


def ring_matmul(a: Ring64, b: Ring64) -> Ring64:
    """Exact matmul over Z_2^64: a [..M, K] @ b [K, N..].

    On TPU, 2-D contractions go through the fused Pallas kernel
    (:mod:`pygrid_tpu.smpc.pallas_kernels`, ~7× the XLA limb path;
    opt out with ``PYGRID_TPU_NO_PALLAS=1``). Elsewhere the contraction is
    chunked so each int32 ``dot_general`` stays exact; chunks are folded
    with ring adds. XLA maps the int32 dots onto the MXU/VPU and fuses the
    limb recombination.
    """
    if _pallas_eligible(a, b):
        from pygrid_tpu.smpc.pallas_kernels import pallas_ring_matmul

        return pallas_ring_matmul(a, b)
    k = a.lo.shape[-1]
    if k <= _CHUNK_K:
        return _ring_matmul_chunk(a, b)
    n_chunks = -(-k // _CHUNK_K)
    pad = n_chunks * _CHUNK_K - k
    a_lo = jnp.pad(a.lo, [(0, 0)] * (a.lo.ndim - 1) + [(0, pad)])
    a_hi = jnp.pad(a.hi, [(0, 0)] * (a.hi.ndim - 1) + [(0, pad)])
    b_lo = jnp.pad(b.lo, [(0, pad)] + [(0, 0)] * (b.lo.ndim - 1))
    b_hi = jnp.pad(b.hi, [(0, pad)] + [(0, 0)] * (b.hi.ndim - 1))
    out = None
    for c in range(n_chunks):
        sl = slice(c * _CHUNK_K, (c + 1) * _CHUNK_K)
        part = _ring_matmul_chunk(
            Ring64(a_lo[..., sl], a_hi[..., sl]),
            Ring64(b_lo[sl], b_hi[sl]),
        )
        out = part if out is None else ring_add(out, part)
    return out


# --- division by a small public constant (for fixed-point truncation) -------


def ring_div_const(a: Ring64, d: int) -> Ring64:
    """Exact unsigned division of each ring element by constant d < 2^16.

    16-bit-limb long division: remainders stay < d < 2^16 so every
    intermediate fits in uint32.
    """
    if not 0 < d < (1 << 16):
        raise ValueError("ring_div_const requires 0 < d < 2^16")
    dd = U32(d)
    limbs = [
        (a.hi >> 16) & _MASK16,
        a.hi & _MASK16,
        (a.lo >> 16) & _MASK16,
        a.lo & _MASK16,
    ]
    rem = jnp.zeros_like(a.lo)
    qs = []
    for limb in limbs:
        cur = (rem << 16) | limb  # rem < d ≤ 2^16-1 → cur < 2^32
        qs.append(cur // dd)
        rem = cur % dd
    q_hi = (qs[0] << 16) | qs[1]
    q_lo = (qs[2] << 16) | qs[3]
    return Ring64(q_lo, q_hi)


def ring_div_const_signed(a: Ring64, d: int) -> Ring64:
    """Signed (two's-complement) division by small constant, rounding toward
    zero — matches torch integer division used by the reference stack."""
    neg = a.hi >> 31  # sign bit
    abs_a = Ring64(
        jnp.where(neg.astype(bool), ring_neg(a).lo, a.lo),
        jnp.where(neg.astype(bool), ring_neg(a).hi, a.hi),
    )
    q = ring_div_const(abs_a, d)
    nq = ring_neg(q)
    return Ring64(
        jnp.where(neg.astype(bool), nq.lo, q.lo),
        jnp.where(neg.astype(bool), nq.hi, q.hi),
    )


# --- random ring elements ---------------------------------------------------


def ring_random(key: jax.Array, shape) -> Ring64:
    k1, k2 = jax.random.split(key)
    # randint over the full uint32 range
    lo = jax.random.bits(k1, shape, dtype=jnp.uint32)
    hi = jax.random.bits(k2, shape, dtype=jnp.uint32)
    return Ring64(lo, hi)


# --- collective ring sum (the mesh-sharded "open") ---------------------------


def ring_psum(
    r: Ring64, axis_name: str, local_axis: int | None = 0
) -> Ring64:
    """Exact sum mod 2^64 over ``local_axis`` *and* the mesh axis
    ``axis_name`` — the collective "open" for shares sharded over a party
    mesh axis (call inside ``shard_map``).

    A plain ``psum`` of the (lo, hi) u32 limbs would drop inter-limb
    carries (carry propagation is not linear, so it cannot ride the
    collective). Instead each 64-bit share splits into four 16-bit
    half-limbs held in u32; those sums are carry-free for up to 2^16
    parties (limb sum ≤ P·(2^16−1) < 2^32), so the psum is exact, and the
    carries are propagated once, locally, after the collective.
    """
    limbs = [
        r.lo & _MASK16,
        r.lo >> 16,
        r.hi & _MASK16,
        r.hi >> 16,
    ]
    if local_axis is not None:
        limbs = [l.sum(axis=local_axis, dtype=U32) for l in limbs]
    limbs = [lax.psum(l, axis_name) for l in limbs]
    out, carry = [], None
    for l in limbs:
        c = l if carry is None else l + carry
        out.append(c & _MASK16)
        carry = c >> 16
    return Ring64(out[0] | (out[1] << 16), out[2] | (out[3] << 16))
