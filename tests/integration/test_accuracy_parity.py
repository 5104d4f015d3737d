"""Accuracy parity: the fused on-device kernel and the real socket protocol
train to the same final accuracy — the "iso final accuracy" leg of the
north-star claim (BASELINE.json; reference workload
``/root/reference/examples/model-centric/01-Create-plan.ipynb`` cell 10).

Same data partition, same rounds, same lr through (a) ``make_scanned_rounds``
(everything fused on device) and (b) the full WS/HTTP cycle protocol with 4
workers — both must clear the accuracy bar on a held-out split and agree
with each other. With one local step per cycle the two are the same
algorithm, so this is an equivalence check, not a lucky pair of runs."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pygrid_tpu.client import FLClient, ModelCentricFLClient
from pygrid_tpu.models import mlp
from pygrid_tpu.parallel import make_scanned_rounds
from pygrid_tpu.plans.plan import Plan
from pygrid_tpu.plans.state import serialize_model_params

K, D, H, C = 4, 64, 32, 10
ROUNDS = 40
LR = 0.5
TARGET_ACC = 0.85
NAME, VERSION = "digits-parity", "1.0"


@pytest.fixture(scope="module")
def digits():
    """Real data, no download: sklearn's 8x8 handwritten digits."""
    from sklearn.datasets import load_digits

    ds = load_digits()
    X = (ds.data / 16.0).astype(np.float32)
    y = ds.target
    rng = np.random.default_rng(0)
    order = rng.permutation(len(X))
    X, y = X[order], y[order]
    n_train = 1536  # K clients x 384
    per = n_train // K
    train_X = X[:n_train].reshape(K, per, D)
    train_y = np.eye(C, dtype=np.float32)[y[:n_train]].reshape(K, per, C)
    return {
        "train_X": train_X,
        "train_y": train_y,
        "test_X": X[n_train:],
        "test_y": y[n_train:],
    }


def _accuracy(params, X, y) -> float:
    h = np.maximum(X @ np.asarray(params[0]) + np.asarray(params[1]), 0.0)
    logits = h @ np.asarray(params[2]) + np.asarray(params[3])
    return float(np.mean(np.argmax(logits, axis=1) == y))


def _init_params():
    return [np.asarray(p) for p in mlp.init(jax.random.PRNGKey(42), (D, H, C))]


@pytest.fixture(scope="module")
def scanned_result(digits):
    """The fused-kernel run both tests compare against (fixture, not test
    ordering, carries the result)."""
    params = _init_params()
    rounds = make_scanned_rounds(mlp.training_step, n_rounds=ROUNDS)
    final, losses, accs = rounds(
        params,
        jnp.asarray(digits["train_X"]),
        jnp.asarray(digits["train_y"]),
        jnp.float32(LR),
    )
    return {
        "acc": _accuracy(final, digits["test_X"], digits["test_y"]),
        "params": [np.asarray(p) for p in final],
    }


def test_scanned_kernel_reaches_target_accuracy(scanned_result):
    assert scanned_result["acc"] >= TARGET_ACC, (
        f"scanned kernel held-out acc {scanned_result['acc']:.3f}"
    )


def test_protocol_reaches_same_accuracy(grid, digits, scanned_result):
    """The same FL run through the real protocol: host on bob, 4 binary-wire
    workers each holding one data shard, ROUNDS cycles of FedAvg."""
    params = _init_params()
    plan = Plan(name="training_plan", fn=mlp.training_step)
    per = digits["train_X"].shape[1]
    plan.build(
        np.zeros((per, D), np.float32),
        np.zeros((per, C), np.float32),
        np.float32(LR),
        *params,
    )
    mc = ModelCentricFLClient(grid.node_url("bob"))
    resp = mc.host_federated_training(
        model=params,
        client_plans={"training_plan": plan},
        client_config={
            "name": NAME, "version": VERSION,
            "batch_size": per, "lr": LR, "max_updates": 1,
        },
        server_config={
            "min_workers": K, "max_workers": K,
            "min_diffs": K, "max_diffs": K,
            "num_cycles": ROUNDS,
            "pool_selection": "random",
            "do_not_reuse_workers_until_cycle": 0,
        },
    )
    assert resp.get("status") == "success", resp

    clients = []
    for k in range(K):
        client = FLClient(grid.node_url("bob"), wire="binary")
        auth = client.authenticate(NAME, VERSION)
        clients.append((client, auth["worker_id"], k))

    plans = {}
    for _ in range(ROUNDS):
        accepted = []
        for client, wid, k in clients:
            cyc = client.cycle_request(wid, NAME, VERSION, 1.0, 100.0, 100.0)
            assert cyc["status"] == "accepted", cyc
            accepted.append((client, wid, k, cyc))
        for client, wid, k, cyc in accepted:
            model_params = client.get_model(
                wid, cyc["request_key"], cyc["model_id"]
            )
            if k not in plans:
                plans[k] = client.get_plan(
                    wid, cyc["request_key"], cyc["plans"]["training_plan"]
                )
            out = plans[k](
                digits["train_X"][k], digits["train_y"][k],
                np.float32(LR), *model_params,
            )
            new_params = [np.asarray(t) for t in out[2:]]
            diff = [p - n for p, n in zip(model_params, new_params)]
            rep = client.report(
                wid, cyc["request_key"], serialize_model_params(diff)
            )
            assert rep.get("status") == "success", rep
    for client, _, _ in clients:
        client.close()

    final = mc.retrieve_model(NAME, VERSION)
    mc.close()
    acc = _accuracy(final, digits["test_X"], digits["test_y"])
    assert acc >= TARGET_ACC, f"protocol held-out acc {acc:.3f}"
    # iso accuracy: same algorithm through either plane -> same result
    assert abs(acc - scanned_result["acc"]) <= 0.02, (
        f"protocol acc {acc:.3f} vs scanned acc {scanned_result['acc']:.3f}"
    )
    for a, b in zip(final, scanned_result["params"]):
        np.testing.assert_allclose(a, b, atol=5e-3)
