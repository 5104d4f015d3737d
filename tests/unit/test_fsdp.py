"""FSDP/ZeRO sharded training: equivalence to the unsharded update.

Runs on the 8-device CPU mesh (conftest). The contract under test: with
parameters, gradients and optimizer moments living as 1/8 shards and the
batch split across devices, every optimizer family must reproduce the
single-device full-batch update bit-for-near (the collectives — tiled
all_gather in, psum_scatter out — are exact re-associations of the same
math; tolerances cover float reduction-order drift only).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pygrid_tpu.models import mlp
from pygrid_tpu.parallel import make_mesh
from pygrid_tpu.parallel.fsdp import (
    make_fsdp_training_step,
    shard_params,
    unshard_params,
)

SIZES = (12, 16, 10)  # biases (16, 10) don't divide 8 — padding path
B = 32


def _data(seed: int = 0):
    k = jax.random.PRNGKey(seed)
    X = jax.random.normal(jax.random.fold_in(k, 1), (B, SIZES[0]))
    y = jax.nn.one_hot(
        jax.random.randint(jax.random.fold_in(k, 2), (B,), 0, SIZES[-1]),
        SIZES[-1],
    )
    return X, y


def _put_batch(mesh, X, y):
    s = NamedSharding(mesh, P("fsdp"))
    return jax.device_put(X, s), jax.device_put(y, s)


def _reference_updates(params, X, y, lr, optimizer, n_steps):
    """Unsharded full-batch reference for each optimizer family."""
    m = [jnp.zeros_like(p) for p in params]
    v = [jnp.zeros_like(p) for p in params]
    losses = []
    for t in range(1, n_steps + 1):
        (loss, _), grads = jax.value_and_grad(
            mlp.loss_and_acc, has_aux=True
        )(params, X, y)
        losses.append(float(loss))
        if optimizer == "sgd":
            params = [p - lr * g for p, g in zip(params, grads)]
        elif optimizer == "momentum":
            m = [0.9 * mi + g for mi, g in zip(m, grads)]
            params = [p - lr * mi for p, mi in zip(params, m)]
        else:  # adam
            m = [0.9 * mi + 0.1 * g for mi, g in zip(m, grads)]
            v = [0.999 * vi + 0.001 * g * g for vi, g in zip(v, grads)]
            params = [
                p
                - lr
                * (mi / (1 - 0.9**t))
                / (jnp.sqrt(vi / (1 - 0.999**t)) + 1e-8)
                for p, mi, vi in zip(params, m, v)
            ]
    return params, losses


@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adam"])
def test_fsdp_matches_unsharded(optimizer):
    mesh = make_mesh(8, axes=("fsdp",))
    params = mlp.init(jax.random.PRNGKey(0), SIZES)
    X, y = _data()
    lr = jnp.float32(0.1)
    n_steps = 3

    init_state, step = make_fsdp_training_step(
        mlp.loss_and_acc, params, mesh, optimizer=optimizer
    )
    state = init_state(params)
    Xs, ys = _put_batch(mesh, X, y)
    fsdp_losses = []
    for _ in range(n_steps):
        state, loss, acc = step(state, Xs, ys, lr)
        fsdp_losses.append(float(loss))

    ref_params, ref_losses = _reference_updates(
        params, X, y, lr, optimizer, n_steps
    )
    np.testing.assert_allclose(fsdp_losses, ref_losses, rtol=2e-5)
    got = unshard_params(state["shards"], params)
    for g, r in zip(got, ref_params):
        # the sharded program reduces in a different order than the
        # reference; adam's rsqrt amplifies the reassociation noise to
        # ~5e-5 relative
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), atol=1e-5, rtol=1e-4
        )


def test_state_is_actually_sharded():
    """Every shard and moment buffer must be laid out P('fsdp') with each
    device holding exactly one row — the ZeRO memory claim is the layout."""
    mesh = make_mesh(8, axes=("fsdp",))
    params = mlp.init(jax.random.PRNGKey(0), SIZES)
    init_state, step = make_fsdp_training_step(
        mlp.loss_and_acc, params, mesh, optimizer="adam"
    )
    state = init_state(params)
    X, y = _put_batch(mesh, *_data())
    state, _, _ = step(state, X, y, jnp.float32(0.1))

    expected = NamedSharding(mesh, P("fsdp"))
    buffers = list(state["shards"]) + [
        s for group in state["moments"] for s in group
    ]
    assert len(buffers) == 3 * len(params)  # shards + m + v
    for buf in buffers:
        assert buf.sharding.is_equivalent_to(expected, buf.ndim)
        assert buf.shape[0] == 8
        (local,) = {
            db.data.shape for db in buf.addressable_shards
        }  # one row each
        assert local == (1, buf.shape[1])


def test_padding_is_inert():
    """Leaves whose size doesn't divide the axis (here every bias) must
    train exactly as if unpadded — padding grads are zero by construction
    and sliced off on unshard."""
    mesh = make_mesh(8, axes=("fsdp",))
    params = mlp.init(jax.random.PRNGKey(3), SIZES)
    shards = shard_params(params, mesh, "fsdp")
    got = unshard_params(shards, params)
    for g, p in zip(got, params):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(p))
    # padded tail stays zero after a training step
    init_state, step = make_fsdp_training_step(
        mlp.loss_and_acc, params, mesh, optimizer="sgd"
    )
    state = init_state(params)
    X, y = _put_batch(mesh, *_data())
    state, _, _ = step(state, X, y, jnp.float32(0.1))
    b2 = state["shards"][-1]  # final bias: 10 real + 6 pad elements
    tail = np.asarray(b2).reshape(-1)[params[-1].size :]
    np.testing.assert_array_equal(tail, np.zeros_like(tail))


def test_fsdp_learns():
    mesh = make_mesh(8, axes=("fsdp",))
    params = mlp.init(jax.random.PRNGKey(1), SIZES)
    init_state, step = make_fsdp_training_step(
        mlp.loss_and_acc, params, mesh, optimizer="adam"
    )
    state = init_state(params)
    X, y = _put_batch(mesh, *_data(7))
    lr = jnp.float32(0.01)
    state, first, _ = step(state, X, y, lr)
    for _ in range(30):
        state, loss, acc = step(state, X, y, lr)
    assert float(loss) < float(first) * 0.5
    assert float(acc) > 0.5


def test_transformer_fsdp_compiles_and_matches():
    """The flagship family through the same FSDP step (tiny config):
    one step must match the unsharded transformer SGD update."""
    from pygrid_tpu.models import transformer

    mesh = make_mesh(8, axes=("fsdp",))
    cfg = transformer.TransformerConfig(
        vocab=29, d_model=16, n_heads=2, n_layers=1, d_ff=32, max_len=8
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    loss_fn = partial(transformer.loss_and_acc, cfg=cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0, cfg.vocab)
    tgt = jnp.roll(tok, -1, axis=1)

    init_state, step = make_fsdp_training_step(loss_fn, params, mesh)
    state = init_state(params)
    s = NamedSharding(mesh, P("fsdp"))
    state, loss, _ = step(
        state, jax.device_put(tok, s), jax.device_put(tgt, s),
        jnp.float32(0.1),
    )

    (ref_loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, tok, tgt
    )
    ref = [p - 0.1 * g for p, g in zip(params, grads)]
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    got = unshard_params(state["shards"], params)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), atol=5e-6, rtol=3e-5
        )


def test_bad_optimizer_rejected():
    mesh = make_mesh(8, axes=("fsdp",))
    params = mlp.init(jax.random.PRNGKey(0), SIZES)
    with pytest.raises(ValueError, match="optimizer"):
        make_fsdp_training_step(
            mlp.loss_and_acc, params, mesh, optimizer="lion"
        )
