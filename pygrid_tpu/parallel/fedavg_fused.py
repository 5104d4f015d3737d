"""Loss-driven FedAvg rounds with a fused final-step aggregation.

:func:`pygrid_tpu.parallel.make_scanned_rounds`'s per-client path treats
the client update as an opaque ``training_step`` — under ``vmap`` every
weight-gradient dot becomes a K-batched matmul with only ``batch_size``
rows per client, and the K per-client results must materialize in HBM
before the mean. On a v5e that program ran at 34.3% MFU while the same
FLOPs through this builder ran at 89.9% (BENCH_r05.json): the MXU sees
64-row matmuls and the bandwidth sees K·|params| of traffic that the
*algorithm* does not require.

This module rebuilds the round from the model's **loss function** instead
of its opaque update step, which exposes the one reassociation the opaque
path cannot express::

    mean_k(p_k - lr * grad L(p_k, X_k))
      = mean_k(p_k) - lr * grad_q [ (1/K) * sum_k L(p_k + q, X_k) ] at q=0

The right-hand grad is taken w.r.t. a *shared* zero offset ``q`` added to
every client's params. Because ``q`` is unbatched under the client
``vmap``, JAX's transpose rule emits each layer's weight gradient as ONE
dot_general whose contraction axis is the merged ``K*batch`` dimension —
the MXU-shaped program — instead of K separate 64-row matmuls followed by
a K-sized reduce. No per-client gradient or updated-parameter tensor ever
exists for the final local step.

Semantics are exactly FedAvg-with-local-SGD (grad of mean == mean of
grads, by linearity): for ``local_steps = 1`` the whole round fuses and
runs at folded-path MFU while keeping per-client metrics; for
``local_steps = N`` the first ``N-1`` steps still carry true per-client
parameters (that part of the traffic *is* the algorithm) and only the
final step + aggregation fold. Equivalence against the opaque builder is
tested to f32-reassociation tolerance in
``tests/unit/test_fedavg_fused.py``.

Scope: the identity needs an update rule linear in the gradient of a
mean-reduced loss — plain SGD, which is what the reference's training
plans run (reference ``examples/model-centric/01-Create-plan.ipynb``
cell 16: softmax-CE + SGD). Stateful per-client optimizers must use the
opaque ``training_step`` path.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _sgd_steps(
    loss_fn: Callable, params, X, y, lr, n_steps: int,
    carry_dtype=None,
):
    """``n_steps`` per-client SGD steps (vmapped caller); returns the
    per-client updated params. Mirrors ``fedavg._client_update`` but built
    from the loss so the final step can be split off by the caller.

    With ``carry_dtype`` the scan carries the per-client params as a
    narrow-dtype DELTA against the shared round-start params — under the
    client vmap the carry is the [K, |params|] tensor whose read+write
    per local step is the middle steps' bandwidth bill, so bf16 halves
    it. The shared base ``params`` stays unbatched (one small broadcast
    read), and each step recomputes ``p = base + delta`` in f32 before
    the gradient, so only the accumulated delta — an ``-lr * sum(grads)``
    term, small against the parameter scale — ever sees the cast."""

    if carry_dtype is None:

        def body(p, _):
            grads = jax.grad(lambda q: loss_fn(q, X, y)[0])(p)
            return [pi - lr * g for pi, g in zip(p, grads)], None

        new_p, _ = lax.scan(body, list(params), None, length=n_steps)
        return new_p

    def body_delta(deltas, _):
        p = [
            base + d.astype(base.dtype)
            for base, d in zip(params, deltas)
        ]
        grads = jax.grad(lambda q: loss_fn(q, X, y)[0])(p)
        new_d = [
            (pi - lr * g - base).astype(carry_dtype)
            for pi, g, base in zip(p, grads, params)
        ]
        return new_d, None

    zeros = [jnp.zeros_like(p, dtype=carry_dtype) for p in params]
    deltas, _ = lax.scan(body_delta, zeros, None, length=n_steps)
    return [
        base + d.astype(base.dtype) for base, d in zip(params, deltas)
    ]


def _fused_grad_and_metrics(loss_fn, p_k, batched, client_X, client_y):
    """The gradient-semantics core both builders share: the mean loss
    over vmapped clients as a function of a shared zero offset ``q``
    added to every client's params. Because ``q`` is unbatched under the
    vmap, grad w.r.t. it emits each layer's weight gradient as ONE
    folded dot over the merged client×batch rows.

    ``p_k``: per-client params (leading K) when ``batched``, else the
    shared params. Returns ``(loss, acc, grads)`` — loss/acc are the
    client means at the pre-update point (matching the opaque path's
    metrics), grads are the client-mean gradients."""

    def mean_loss(q):
        def per_client(p, X, y):
            return loss_fn([pi + qi for pi, qi in zip(p, q)], X, y)

        losses, accs = jax.vmap(
            per_client, in_axes=(0 if batched else None, 0, 0)
        )(p_k, client_X, client_y)
        return jnp.mean(losses), jnp.mean(accs)

    # zeros derived FROM p_k leaves (slice, not fresh jnp.zeros): under
    # shard_map a fresh array is device-INVARIANT, and grads w.r.t. an
    # invariant value get an implicit psum across the mesh — which would
    # silently double-aggregate with the caller's explicit pmean
    zeros = [
        jnp.zeros_like(p[0]) if batched else jnp.zeros_like(p)
        for p in p_k
    ]
    with jax.named_scope("client_grad"):
        (loss, acc), g = jax.value_and_grad(mean_loss, has_aux=True)(zeros)
    return loss, acc, g


def make_fused_rounds(
    loss_fn: Callable,
    n_rounds: int,
    local_steps: int = 1,
    matmul_precision: str | None = None,
    carry_dtype: jnp.dtype | None = None,
) -> Callable:
    """Scanned FedAvg rounds from a loss function, final step fused.

    ``loss_fn(params, X, y) -> (loss, acc)`` — the shape all bundled
    models expose (``models.{mlp,cnn,transformer}.loss_and_acc``).

    Returns ``rounds_fn(params, client_X [K,...], client_y [K,...], lr)
    -> (final_params, losses[n_rounds], accs[n_rounds])`` with the same
    contract as :func:`fedavg.make_scanned_rounds` (losses/accs are the
    per-round mean over clients of the final local step's pre-update
    loss/acc).

    ``carry_dtype`` (e.g. ``jnp.bfloat16``) stores the *per-client delta*
    ``p_k - p_round`` between local steps in a narrower dtype: the deltas
    are ``-lr * grad`` sums — small against the parameter scale, so the
    cast loses little — and the [K, |params|] carry is the middle steps'
    bandwidth bill, so halving it halves their roofline. Only touches
    ``local_steps > 1``; None keeps full f32 deltas.
    """
    if local_steps < 1:
        raise ValueError("local_steps must be >= 1")

    @jax.jit
    def rounds_fn(params, client_X, client_y, lr):
        def one_round(p, _):
            if local_steps == 1:
                p_k, batched = p, False
            else:
                # steps 1..N-1 carry true per-client params (this
                # traffic IS the algorithm once clients diverge);
                # optionally as a narrow-dtype delta against the shared
                # round-start params
                def warm(X, y):
                    return _sgd_steps(
                        loss_fn, p, X, y, lr, local_steps - 1,
                        carry_dtype=carry_dtype,
                    )

                p_k, batched = jax.vmap(warm)(client_X, client_y), True

            loss, acc, g = _fused_grad_and_metrics(
                loss_fn, p_k, batched, client_X, client_y
            )
            with jax.named_scope("client_reduce"):
                mean_p = (
                    [jnp.mean(pk, axis=0) for pk in p_k] if batched else p_k
                )
            with jax.named_scope("server_update"):
                new_p = [mp - lr * gi for mp, gi in zip(mean_p, g)]
            return new_p, (loss, acc)

        def body():
            return lax.scan(
                one_round, list(params), None, length=n_rounds
            )

        if matmul_precision is None:
            final, (losses, accs) = body()
        else:
            with jax.default_matmul_precision(matmul_precision):
                final, (losses, accs) = body()
        return final, losses, accs

    return rounds_fn


def make_sharded_fused_round(
    loss_fn: Callable,
    mesh: Mesh,
    local_steps: int = 1,
    axis: str = "clients",
    carry_dtype: jnp.dtype | None = None,
) -> Callable:
    """Fused-aggregation FedAvg round with the client axis SHARDED.

    The multi-chip shape of :func:`make_fused_rounds`: each device runs
    its client shard's local steps and the fused final-step gradient
    (one folded matmul per layer over the shard's ``K_local·B`` rows);
    the cross-device aggregation is a single ``pmean`` of those
    already-reduced gradients (plus one of the shard-mean params when
    ``local_steps > 1``) riding ICI — O(|params|) bytes on the wire per
    round, never O(K·|params|). Mirrors
    :func:`fedavg.make_sharded_round`'s contract (params/lr replicated
    in, client data sharded on its leading axis, outputs replicated);
    equivalence against the single-device fused builder is tested on the
    8-device CPU mesh in ``tests/unit/test_fedavg_fused.py``.
    """
    if local_steps < 1:
        raise ValueError("local_steps must be >= 1")

    def shard_fn(params, client_X, client_y, lr):
        # pcast keeps local training local under shard_map's
        # replication-aware autodiff (see make_sharded_round's note)
        params_v = [lax.pcast(p, axis, to="varying") for p in params]
        lr_v = lax.pcast(lr, axis, to="varying")

        if local_steps > 1:

            def warm(X, y):
                return _sgd_steps(
                    loss_fn, params_v, X, y, lr_v, local_steps - 1,
                    carry_dtype=carry_dtype,
                )

            p_k = jax.vmap(warm)(client_X, client_y)
            batched = True
        else:
            p_k = params_v
            batched = False

        loss, acc, g = _fused_grad_and_metrics(
            loss_fn, p_k, batched, client_X, client_y
        )
        # shard-local mean then pmean == global mean (equal shard sizes,
        # enforced by the sharding); the final combine uses the
        # REPLICATED params/lr — pmean outputs are device-invariant and
        # mixing the pcast-varying lr back in would make the outputs
        # varying, which out_specs=P() rejects
        with jax.named_scope("client_reduce"):
            g = [lax.pmean(gi, axis) for gi in g]
            if batched:
                mean_p = [
                    lax.pmean(jnp.mean(p, axis=0), axis) for p in p_k
                ]
            else:
                mean_p = params
        with jax.named_scope("server_update"):
            new_params = [mp - lr * gi for mp, gi in zip(mean_p, g)]
        return (
            new_params,
            lax.pmean(loss, axis),
            lax.pmean(acc, axis),
        )

    repl = P()
    sharded = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(repl, P(axis), P(axis), repl),
        out_specs=(repl, repl, repl),
    )
    return jax.jit(sharded)


def make_fused_round(
    loss_fn: Callable,
    local_steps: int = 1,
    matmul_precision: str | None = None,
    carry_dtype: jnp.dtype | None = None,
) -> Callable:
    """Single fused round — :func:`fedavg.make_round`'s contract
    (``round_fn(params, client_X, client_y, lr) -> (new_params,
    mean_loss, mean_acc)``) built from a loss function with the fused
    final-step aggregation of :func:`make_fused_rounds`."""
    rounds = make_fused_rounds(
        loss_fn, n_rounds=1, local_steps=local_steps,
        matmul_precision=matmul_precision, carry_dtype=carry_dtype,
    )

    def round_fn(params, client_X, client_y, lr):
        final, losses, accs = rounds(params, client_X, client_y, lr)
        return final, losses[0], accs[0]

    return round_fn
