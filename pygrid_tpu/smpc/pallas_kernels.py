"""Pallas TPU kernel for the SMPC hot op: exact uint64 ring matmul.

The Beaver-triple matmul (``smpc/kernels.py``) is the FLOP core of the
SMPC plane (SURVEY.md §7 "hard parts": no native uint64 matmul on TPU).
The XLA path in :func:`pygrid_tpu.smpc.ring.ring_matmul` materializes 16
limb arrays in HBM and runs 36 separate ``dot_general``s; this kernel fuses
the whole thing per output tile:

- 8-bit limb extraction happens in VMEM right after the block DMA,
- the 36 partial ``jnp.dot``s (limb pairs with i+j < 8) run back-to-back
  on the MXU in float32 — Mosaic has no int32 matmul on v5e; f32 products
  of 8-bit limbs summed over a ≤256 chunk stay < 2^24 so every dot is
  exact, and each is cast back to int32 before cross-pair accumulation
  (f32 would round above 2^24),
- the shifted carry recombination into (lo, hi) uint32 runs on the VPU
  while the next K-chunk streams in,

so HBM traffic is one read of A and B and one write of C instead of ~16
limb-array round-trips. Grid: (M/TM, N/TN, K/KC) with the K axis innermost
— the output tile stays resident in VMEM across K steps, accumulating with
explicit carries.

Correctness contract: identical bit-for-bit to ``ring_matmul`` (tests run
this kernel in interpret mode on CPU against the XLA path and against
numpy uint64).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pygrid_tpu.smpc.ring import Ring64

TILE_M = 128
TILE_N = 128
#: K-chunk per grid step; 255² × 256 = 16 646 400 < 2^24, so every f32
#: limb dot is exact — the binding constraint for the MXU path
CHUNK_K = 256


def _limbs8(lo: jax.Array, hi: jax.Array) -> list[jax.Array]:
    """Eight 8-bit limbs of a (lo, hi) uint32 pair, little-endian, as f32
    (the MXU-accepted dtype; values 0..255 are exact). Mosaic has no
    uint32→f32 cast, so the route is bitcast→int32→f32 (limbs < 2^31)."""
    from jax import lax

    mask = jnp.uint32(0xFF)

    def limb(word: jax.Array, i: int) -> jax.Array:
        raw = (word >> jnp.uint32(8 * i)) & mask
        return lax.bitcast_convert_type(raw, jnp.int32).astype(jnp.float32)

    return [limb(lo, i) for i in range(4)] + [limb(hi, i) for i in range(4)]


def _matmul_kernel(a_lo, a_hi, b_lo, b_hi, out_lo, out_hi):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        out_lo[:] = jnp.zeros_like(out_lo)
        out_hi[:] = jnp.zeros_like(out_hi)

    a_limbs = _limbs8(a_lo[:], a_hi[:])
    b_limbs = _limbs8(b_lo[:], b_hi[:])

    # partial products by output shift s = i + j (s ≥ 8 vanishes mod 2^64)
    parts = [None] * 8
    for i in range(8):
        for j in range(8 - i):
            d = jnp.dot(
                a_limbs[i], b_limbs[j], preferred_element_type=jnp.float32
            ).astype(jnp.int32)
            s = i + j
            parts[s] = d if parts[s] is None else parts[s] + d

    from jax import lax

    lo, hi = out_lo[:], out_hi[:]
    for s in range(8):
        p = lax.bitcast_convert_type(parts[s], jnp.uint32)
        shift = 8 * s
        if shift < 32:
            add_lo = p << jnp.uint32(shift) if shift else p
            add_hi = p >> jnp.uint32(32 - shift) if shift else jnp.uint32(0)
        else:
            add_lo = jnp.zeros_like(p)
            add_hi = p << jnp.uint32(shift - 32)
        new_lo = lo + add_lo
        carry = (new_lo < lo).astype(jnp.uint32)
        hi = hi + add_hi + carry
        lo = new_lo
    out_lo[:] = lo
    out_hi[:] = hi


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@partial(jax.jit, static_argnames=("interpret",))
def pallas_ring_matmul(a: Ring64, b: Ring64, interpret: bool = False) -> Ring64:
    """Exact ``a [M,K] @ b [K,N]`` over Z_2^64, one fused Pallas launch;
    batched ``[B,M,K] @ [B,K,N]`` operands vmap over the same kernel
    (pallas_call's batching rule turns the batch into a leading grid
    axis — the path ``smpc.kernels.batched_beaver`` drives).

    Tiles adapt downward for small operands: a 64×64 Beaver matmul under
    the fixed 128×128×256 tiling would spend ~8× its FLOPs multiplying
    zero padding (M, N and K each round up); only the lane dimension (N)
    is pinned to 128 by the hardware. Zero-padding stays exact (zero
    limbs contribute nothing). ``interpret=True`` runs the same kernel on
    CPU for tests."""
    if a.lo.ndim == 3 and b.lo.ndim == 3:
        if a.lo.shape[0] != b.lo.shape[0]:
            raise ValueError(
                f"batch mismatch: {a.lo.shape} @ {b.lo.shape}"
            )
        return jax.vmap(lambda x, y: pallas_ring_matmul(x, y, interpret))(
            a, b
        )
    if a.lo.ndim != 2 or b.lo.ndim != 2:
        raise ValueError("pallas_ring_matmul takes 2-D or 3-D operands")
    M, K = a.lo.shape
    K2, N = b.lo.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: {a.lo.shape} @ {b.lo.shape}")
    tile_m = min(TILE_M, _round_up(M, 8))     # sublane multiple
    chunk_k = min(CHUNK_K, _round_up(K, 128))  # MXU contraction lanes
    Mp = pl.cdiv(M, tile_m) * tile_m
    Np = pl.cdiv(N, TILE_N) * TILE_N
    Kp = pl.cdiv(K, chunk_k) * chunk_k
    a_lo, a_hi = _pad2(a.lo, Mp, Kp), _pad2(a.hi, Mp, Kp)
    b_lo, b_hi = _pad2(b.lo, Kp, Np), _pad2(b.hi, Kp, Np)

    a_spec = pl.BlockSpec(
        (tile_m, chunk_k), lambda mi, ni, ki: (mi, ki),
        memory_space=pltpu.VMEM,
    )
    b_spec = pl.BlockSpec(
        (chunk_k, TILE_N), lambda mi, ni, ki: (ki, ni),
        memory_space=pltpu.VMEM,
    )
    o_spec = pl.BlockSpec(
        (tile_m, TILE_N), lambda mi, ni, ki: (mi, ni),
        memory_space=pltpu.VMEM,
    )
    out_shape = jax.ShapeDtypeStruct((Mp, Np), jnp.uint32)
    lo, hi = pl.pallas_call(
        _matmul_kernel,
        grid=(Mp // tile_m, Np // TILE_N, Kp // chunk_k),
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[out_shape, out_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ring_matmul",
    )(a_lo, a_hi, b_lo, b_hi)
    return Ring64(lo[:M, :N], hi[:M, :N])
