"""FedAvg simulation engine — vmapped clients, psum aggregation.

This is the TPU-native replacement for the reference's per-client socket
round-trip (SURVEY.md §3.3: each worker downloads the model, runs the
training plan locally, reports a diff; the node averages with a Python
reduce loop — cycle_manager.py:275-290):

- K simulated clients are a **leading array axis** — their local training is
  one ``vmap``-ed program, their "reports" never leave HBM.
- On a device mesh the client axis is **sharded**; the average is a
  ``psum``/``pmean`` over the ``"clients"`` mesh axis riding ICI
  (:func:`make_sharded_round` via ``shard_map``).
- One FedAvg round — local steps, aggregation, model update — is a single
  compiled XLA program either way. Aggregation is reassociated from the
  protocol form (``params - mean_k(diff_k)``) to ``mean_k(new_p_k)``:
  same update, but no K-sized diff tensors ever exist on device.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _client_update(
    training_step: Callable, params: Sequence, X, y, lr, local_steps: int
):
    """One client's local training: ``local_steps`` SGD steps via scan."""

    def body(p, _):
        out = training_step(X, y, lr, *p)
        return list(out[2:]), (out[0], out[1])

    new_params, (losses, accs) = lax.scan(
        body, list(params), None, length=local_steps
    )
    return new_params, losses[-1], accs[-1]


def make_round(
    training_step: Callable,
    local_steps: int = 1,
    matmul_precision: str | None = None,
) -> Callable:
    """Build a jitted FedAvg round over a vmapped client axis.

    Returns ``round_fn(params, client_X [K,...], client_y [K,...], lr) ->
    (new_params, mean_loss, mean_acc)``. The new global params equal
    ``params - mean_k(diff_k)`` (reference cycle_manager.py:295-298).

    ``matmul_precision``: an XLA dot precision name (e.g.
    ``"BF16_BF16_F32"`` — single bf16 MXU pass with f32 accumulation,
    ~5% faster than the default on v5e at MNIST-MLP sizes); None keeps
    the platform default.
    """

    @jax.jit
    def round_fn(params, client_X, client_y, lr):
        def one_client(X, y):
            new_p, loss, acc = _client_update(
                training_step, params, X, y, lr, local_steps
            )
            return new_p, loss, acc

        def body():
            # params - mean_k(p - new_p_k) reassociated to mean_k(new_p_k):
            # same FedAvg update, but the K per-client diff tensors — pure
            # HBM traffic at scale — are never materialized
            new_ps, losses, accs = jax.vmap(one_client)(client_X, client_y)
            new_params = [jnp.mean(n, axis=0) for n in new_ps]
            return new_params, jnp.mean(losses), jnp.mean(accs)

        if matmul_precision is None:
            return body()
        with jax.default_matmul_precision(matmul_precision):
            return body()

    return round_fn


def make_sharded_round(
    training_step: Callable,
    mesh: Mesh,
    local_steps: int = 1,
    axis: str = "clients",
) -> Callable:
    """FedAvg round with the client axis sharded over the mesh.

    Each device trains its shard of clients (vmap inside the shard), then
    the new global params are a ``pmean`` of the shard-local client-mean
    params over the mesh axis — the collective rides ICI instead of the
    reference's socket fan-in. Params/results are replicated; client data
    is sharded on its leading axis.
    """

    def shard_fn(params, client_X, client_y, lr):
        # Mark params/lr device-varying: under shard_map's replication-aware
        # autodiff, grads w.r.t. REPLICATED values get an implicit psum
        # across the mesh (replicated cotangent rule) — which would silently
        # aggregate every client's gradient into each local step. pcast
        # keeps local training local; only the explicit pmean below crosses
        # devices.
        params_v = [lax.pcast(p, axis, to="varying") for p in params]
        lr_v = lax.pcast(lr, axis, to="varying")

        def one_client(X, y):
            new_p, loss, acc = _client_update(
                training_step, params_v, X, y, lr_v, local_steps
            )
            return new_p, loss, acc

        new_ps, losses, accs = jax.vmap(one_client)(client_X, client_y)
        # local mean then pmean over the mesh axis == global mean (equal
        # shard sizes — enforced by the sharding); the mean is over
        # new params directly — see make_round's reassociation note
        local_avg = [jnp.mean(n, axis=0) for n in new_ps]
        new_params = [lax.pmean(n, axis) for n in local_avg]
        return new_params, lax.pmean(jnp.mean(losses), axis), lax.pmean(
            jnp.mean(accs), axis
        )

    n_params_spec = P()
    sharded = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(n_params_spec, P(axis), P(axis), n_params_spec),
        out_specs=(n_params_spec, n_params_spec, n_params_spec),
    )
    return jax.jit(sharded)


def make_scanned_rounds(
    training_step: Callable,
    n_rounds: int,
    local_steps: int = 1,
    matmul_precision: str | None = None,
    fold_clients: bool = False,
) -> Callable:
    """All ``n_rounds`` FedAvg rounds fused into ONE XLA program.

    ``lax.scan`` over rounds keeps the whole multi-round simulation on
    device — no host round-trip per round (the loop being replaced lived in
    :func:`run_rounds`; the reference's analog re-enters Python every cycle,
    reference cycle_manager.py:309-323). Returns
    ``rounds_fn(params, client_X, client_y, lr) -> (final_params,
    losses[n_rounds], accs[n_rounds])``.

    ``fold_clients=True`` (requires ``local_steps == 1``) exploits the
    FedAvg identity: with one local step of a mean-loss gradient update,
    ``mean_k(new_p_k) = step(params, concat_k(data))`` — the K·B samples
    fold into one batch before the first matmul. Results are identical
    (same algorithm, reassociated); the win is a roofline shift: the
    per-client path materializes K per-client NEW-param tensors (the
    [K, 784, 392] carry dominates HBM traffic, ~1.3 GB/round at K=1024 —
    bandwidth-bound), while the folded path writes one. Only valid for
    update rules linear in the gradient of a mean-reduced loss (plain
    SGD — what the reference's workload runs); momentum/adam per-client
    states break the identity, hence opt-in.
    """
    if fold_clients and local_steps != 1:
        raise ValueError(
            "fold_clients requires local_steps=1 (the FedAvg identity "
            "breaks once per-client params diverge between local steps)"
        )

    @jax.jit
    def rounds_fn(params, client_X, client_y, lr):
        def one_client(p, X, y):
            new_p, loss, acc = _client_update(
                training_step, p, X, y, lr, local_steps
            )
            return new_p, loss, acc

        def one_round(p, _):
            # mean over per-client NEW params (see make_round) — the K
            # per-client diff tensors stay unmaterialized
            new_ps, losses, accs = jax.vmap(
                lambda X, y: one_client(p, X, y)
            )(client_X, client_y)
            new_params = [jnp.mean(n, axis=0) for n in new_ps]
            return new_params, (jnp.mean(losses), jnp.mean(accs))

        def one_round_folded(p, _):
            out = training_step(folded_X, folded_y, lr, *p)
            return list(out[2:]), (out[0], out[1])

        if fold_clients:
            K = client_X.shape[0]
            folded_X = client_X.reshape((K * client_X.shape[1],) + client_X.shape[2:])
            folded_y = client_y.reshape((K * client_y.shape[1],) + client_y.shape[2:])
            step = one_round_folded
        else:
            step = one_round

        def body():
            return lax.scan(step, list(params), None, length=n_rounds)

        if matmul_precision is None:
            final, (losses, accs) = body()
        else:
            with jax.default_matmul_precision(matmul_precision):
                final, (losses, accs) = body()
        return final, losses, accs

    return rounds_fn


def run_rounds(
    round_fn: Callable,
    params: Sequence,
    client_X,
    client_y,
    lr,
    n_rounds: int,
):
    """Drive n FedAvg rounds host-side (each round one XLA launch).

    For a fully on-device multi-round simulation use
    :func:`make_scanned_rounds` — one launch for all rounds."""
    metrics = []
    for _ in range(n_rounds):
        params, loss, acc = round_fn(params, client_X, client_y, lr)
        metrics.append((loss, acc))
    return params, metrics
