"""Pallas ring-matmul vs the XLA limb path vs numpy uint64 truth.

Runs the kernel in interpret mode (tests are on the virtual CPU mesh); the
same program compiles for TPU unchanged."""

from __future__ import annotations

import numpy as np
import pytest

from pygrid_tpu.smpc import ring as R
from pygrid_tpu.smpc.pallas_kernels import pallas_ring_matmul


_to_ring = R.to_ring
_to_np = R.from_ring


@pytest.mark.parametrize(
    "m,k,n",
    [(4, 8, 4), (16, 32, 8), (128, 128, 128), (130, 600, 70), (1, 1, 1)],
)
def test_matches_numpy_uint64(m, k, n):
    rng = np.random.default_rng(m * 1000 + k + n)
    a = rng.integers(0, 2**64, size=(m, k), dtype=np.uint64)
    b = rng.integers(0, 2**64, size=(k, n), dtype=np.uint64)
    with np.errstate(over="ignore"):
        truth = (a[:, :, None] * b[None, :, :]).sum(axis=1)

    out = pallas_ring_matmul(_to_ring(a), _to_ring(b), interpret=True)
    np.testing.assert_array_equal(_to_np(out), truth)


def test_matches_xla_limb_path():
    """Kernel vs the XLA limb path — with the Pallas dispatch force-disabled
    so ring_matmul really takes the XLA route even on a TPU backend."""
    rng = np.random.default_rng(0)
    a = rng.integers(0, 2**64, size=(64, 256), dtype=np.uint64)
    b = rng.integers(0, 2**64, size=(256, 32), dtype=np.uint64)
    ra, rb = _to_ring(a), _to_ring(b)
    R.set_pallas_enabled(False)
    try:
        xla = R.ring_matmul(ra, rb)
    finally:
        R.set_pallas_enabled(None)
    pallas = pallas_ring_matmul(ra, rb, interpret=True)
    np.testing.assert_array_equal(np.asarray(xla.lo), np.asarray(pallas.lo))
    np.testing.assert_array_equal(np.asarray(xla.hi), np.asarray(pallas.hi))


def test_k_chunking_carries():
    """K > CHUNK_K exercises the cross-step carry accumulation: all-ones
    operands maximize carries."""
    m, k, n = 8, 1600, 8
    a = np.full((m, k), 0xFFFFFFFFFFFFFFFF, dtype=np.uint64)
    b = np.full((k, n), 0xFFFFFFFFFFFFFFFF, dtype=np.uint64)
    with np.errstate(over="ignore"):
        truth = (a[:, :, None] * b[None, :, :]).sum(axis=1)
    out = pallas_ring_matmul(_to_ring(a), _to_ring(b), interpret=True)
    np.testing.assert_array_equal(_to_np(out), truth)


def test_rejects_bad_shapes():
    a = _to_ring(np.zeros((2, 3), dtype=np.uint64))
    b = _to_ring(np.zeros((4, 2), dtype=np.uint64))
    with pytest.raises(ValueError):
        pallas_ring_matmul(a, b, interpret=True)


@pytest.mark.parametrize("b,m,k,n", [(3, 8, 8, 8), (2, 64, 64, 64), (4, 9, 130, 5)])
def test_batched_matches_numpy_uint64(b, m, k, n):
    """ndim-3 door: [B,M,K] @ [B,K,N] vmaps over the same kernel, exact
    per example (the shape `smpc.kernels.batched_beaver` drives)."""
    rng = np.random.default_rng(b * 100 + m + k + n)
    a = rng.integers(0, 2**64, size=(b, m, k), dtype=np.uint64)
    bb = rng.integers(0, 2**64, size=(b, k, n), dtype=np.uint64)
    with np.errstate(over="ignore"):
        truth = np.einsum("bmk,bkn->bmn", a, bb)
    out = pallas_ring_matmul(_to_ring(a), _to_ring(bb), interpret=True)
    np.testing.assert_array_equal(_to_np(out), truth)


def test_batched_matches_xla_limb_path():
    rng = np.random.default_rng(77)
    a = rng.integers(0, 2**64, size=(3, 12, 40), dtype=np.uint64)
    b = rng.integers(0, 2**64, size=(3, 40, 6), dtype=np.uint64)
    import jax

    limb = jax.vmap(R._ring_matmul_chunk)(_to_ring(a), _to_ring(b))
    pallas = pallas_ring_matmul(_to_ring(a), _to_ring(b), interpret=True)
    np.testing.assert_array_equal(_to_np(pallas), _to_np(limb))


def test_batched_rejects_batch_mismatch():
    a = _to_ring(np.zeros((2, 4, 4), np.uint64))
    b = _to_ring(np.zeros((3, 4, 4), np.uint64))
    with pytest.raises(ValueError, match="batch mismatch"):
        pallas_ring_matmul(a, b, interpret=True)
