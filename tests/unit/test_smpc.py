"""SMPC protocol tests — mirrors reference
tests/data_centric/test_basic_syft_operations.py:383-491 (fixed-precision
share/add/sub, Beaver mul/matmul with a crypto provider), plus the
crypto-store refill protocol."""

import numpy as np
import pytest

from pygrid_tpu import serde
from pygrid_tpu.smpc import (
    AdditiveSharingTensor,
    CryptoProvider,
    FixedPointEncoder,
    fix_prec,
)
from pygrid_tpu.utils.exceptions import EmptyCryptoPrimitiveStoreError

PARTIES = ("alice", "bob", "charlie")


@pytest.fixture()
def provider():
    return CryptoProvider(seed=42)


def test_fixed_point_encoder_roundtrip():
    enc = FixedPointEncoder()
    x = np.array([[1.5, -2.25], [0.001, -0.999]])
    np.testing.assert_allclose(enc.decode(enc.encode(x)), x, atol=1e-3)


def test_share_reconstruct(provider):
    x = np.array([[0.1, -4.5], [100.25, 0.0]])
    ast = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    assert ast.n_parties == 3 and ast.shape == (2, 2)
    np.testing.assert_allclose(ast.get(), x, atol=1e-3)
    # individual shares look nothing like the secret
    from pygrid_tpu.smpc import ring as R

    one_share = R.from_ring_signed(R.Ring64(ast.shares.lo[0], ast.shares.hi[0]))
    assert not np.allclose(one_share / 1000.0, x, atol=1.0)


def test_int_share_without_encoder(provider):
    x = np.array([1, -2, 3000], dtype=np.int64)
    ast = AdditiveSharingTensor.share(x, PARTIES, provider)
    np.testing.assert_array_equal(ast.get(), x)


def test_add_sub(provider):
    x = np.array([1.5, -2.0, 0.25])
    y = np.array([-0.5, 1.0, 10.0])
    sx = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    sy = fix_prec(y).share(*PARTIES, crypto_provider=provider)
    np.testing.assert_allclose((sx + sy).get(), x + y, atol=2e-3)
    np.testing.assert_allclose((sx - sy).get(), x - y, atol=2e-3)


def test_public_add_and_int_mul(provider):
    x = np.array([1.5, -2.0])
    sx = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    np.testing.assert_allclose((sx + np.array([1.0, 2.0])).get(), x + [1, 2], atol=2e-3)
    np.testing.assert_allclose((sx * 3).get(), x * 3, atol=3e-3)


def test_public_array_mul_and_float_rejection(provider):
    x = np.array([1.5, -2.0])
    sx = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    np.testing.assert_allclose(
        (sx * np.array([2, 3])).get(), x * [2, 3], atol=5e-3
    )
    import pytest as _pytest

    with _pytest.raises(TypeError):
        _ = sx * 0.5  # non-integer public multiplier


def test_beaver_mul(provider):
    x = np.array([[1.5, -2.0], [0.25, 3.0]])
    y = np.array([[2.0, 0.5], [-1.0, 1.5]])
    sx = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    sy = fix_prec(y).share(*PARTIES, crypto_provider=provider)
    np.testing.assert_allclose((sx * sy).get(), x * y, atol=5e-3)


def test_beaver_matmul(provider):
    """The reference's headline SMPC op (test_mul_shared_tensors :455-491)."""
    rng = np.random.default_rng(7)
    x = rng.uniform(-2, 2, (4, 6))
    y = rng.uniform(-2, 2, (6, 3))
    sx = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    sy = fix_prec(y).share(*PARTIES, crypto_provider=provider)
    got = (sx @ sy).get()
    # fixed-point error ~ k * 1e-3
    np.testing.assert_allclose(got, x @ y, atol=2e-2)


def test_two_party(provider):
    x = np.array([42.0])
    s = fix_prec(x).share("alice", "bob", crypto_provider=provider)
    np.testing.assert_allclose(s.get(), x, atol=1e-3)


def test_crypto_store_refill_protocol():
    provider = CryptoProvider(strict_store=True)
    x = np.array([[1.0, 2.0]])
    y = np.array([[3.0], [4.0]])
    sx = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    sy = fix_prec(y).share(*PARTIES, crypto_provider=provider)
    with pytest.raises(EmptyCryptoPrimitiveStoreError) as exc:
        _ = sx @ sy
    kwargs = exc.value.kwargs_
    assert kwargs["op"] == "matmul" and kwargs["n_parties"] == 3
    # refill round-trip, as the reference error path drives it
    provider.provide(
        kwargs["op"], tuple(kwargs["shapes"][0]), tuple(kwargs["shapes"][1]), 3
    )
    # fixed-point rescale draws a second primitive (the truncation pair) —
    # it reports dry through the same refill protocol
    with pytest.raises(EmptyCryptoPrimitiveStoreError) as exc2:
        _ = sx @ sy
    kwargs2 = exc2.value.kwargs_
    assert kwargs2["op"] == "trunc"
    provider.provide(
        kwargs["op"], tuple(kwargs["shapes"][0]), tuple(kwargs["shapes"][1]), 3
    )
    provider.provide(
        kwargs2["op"], tuple(kwargs2["shapes"][0]), tuple(kwargs2["shapes"][1]), 3
    )
    np.testing.assert_allclose((sx @ sy).get(), x @ y, atol=2e-2)


def test_mismatched_parties_rejected(provider):
    x = fix_prec(np.ones(2)).share("alice", "bob", crypto_provider=provider)
    y = fix_prec(np.ones(2)).share(*PARTIES, crypto_provider=provider)
    with pytest.raises(ValueError):
        _ = x + y


def test_default_truncation_never_opens_secret(provider, monkeypatch):
    """The default rescale path is mask-and-open: no code path may hand the
    dealer a reconstructed product (dealer-sees-all truncation was the
    weakest crypto link; reference-exact behavior stays opt-in behind
    trusted_dealer=True)."""

    def boom(self, *a, **k):
        raise AssertionError("dealer reconstructed the secret")

    monkeypatch.setattr(CryptoProvider, "reshare_truncated", boom)
    x = np.array([[1.5, -2.0], [0.25, 3.0]])
    y = np.array([[2.0, 0.5], [-1.0, 1.5]])
    sx = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    sy = fix_prec(y).share(*PARTIES, crypto_provider=provider)
    np.testing.assert_allclose((sx * sy).get(), x * y, atol=5e-3)
    sx = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    sy = fix_prec(y).share(*PARTIES, crypto_provider=provider)
    np.testing.assert_allclose((sx @ sy).get(), x @ y, atol=2e-2)


def test_trusted_dealer_truncation_opt_in():
    provider = CryptoProvider(seed=11, trusted_dealer=True)
    x = np.array([2.5, -1.5])
    y = np.array([4.0, 3.0])
    sx = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    sy = fix_prec(y).share(*PARTIES, crypto_provider=provider)
    np.testing.assert_allclose((sx * sy).get(), x * y, atol=5e-3)


def test_serde_roundtrip(provider):
    x = np.array([[7.125, -3.5]])
    ast = fix_prec(x).share(*PARTIES, crypto_provider=provider)
    out = serde.deserialize(serde.serialize(ast))
    assert out.owners == PARTIES
    np.testing.assert_allclose(out.get(), x, atol=1e-3)
