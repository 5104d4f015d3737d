"""Serving scale-out: node REPLICA processes sharing one coordination DB.

Two separate `python -m pygrid_tpu.node` processes point at the same
postgres database (the in-process protocol-v3 fake from
tests/unit/_pg_fake.py — the same engine path a live RDS/Cloud SQL server
exercises) and serve ONE model-centric FL process: hosted through
replica A, authenticated and cycle-requested through replica B, model
downloaded from A, the diff reported to B, and the aggregated checkpoint
then retrieved from A. Every hop crosses processes through SQL only.

Reference posture: gunicorn workers sharing a SQLAlchemy DATABASE_URL
(``apps/node/entrypoint.sh:2``) plus ``--num_replicas``; the sqlite-only
warehouse could never do this across hosts, which is what pinned the AWS
serverless stack to one concurrent Lambda before the postgres engine.
"""

from __future__ import annotations

import os
import pathlib
import socket
import subprocess
import sys
import time

import numpy as np
import pytest
import requests

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "tests" / "unit"))

NAME, VERSION = "scaleout-mnist", "1.0"
D, H, C, B = 16, 8, 4, 4


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_up(
    url: str, proc: subprocess.Popen, log: pathlib.Path,
    timeout: float = 90.0,
) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise AssertionError(f"replica died:\n{log.read_text()[-3000:]}")
        try:
            requests.get(url + "/", timeout=2)
            return
        except requests.RequestException:
            time.sleep(0.5)
    raise AssertionError(f"replica at {url} never came up")


@pytest.fixture()
def replicas(tmp_path):
    from _pg_fake import FakePg

    fake = FakePg()
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}:{env.get('PYTHONPATH', '')}"
    env["DATABASE_URL"] = fake.url
    # the replicas are children of a process that has used JAX: they
    # stay off whatever device the parent holds
    env["JAX_PLATFORMS"] = "cpu"
    procs, urls, logs = [], [], []
    for i in range(2):
        port = _free_port()
        # log to a FILE, never an undrained PIPE: a replica can emit an
        # access-log line per poll request, and a full 64 KB pipe buffer
        # would block its event loop mid-test
        log = tmp_path / f"replica{i}.log"
        logs.append(log)
        p = subprocess.Popen(
            [sys.executable, "-m", "pygrid_tpu.node", "--id", "shared",
             "--port", str(port)],
            env=env, cwd=str(tmp_path), stdout=log.open("w"),
            stderr=subprocess.STDOUT, text=True,
        )
        procs.append(p)
        urls.append(f"http://127.0.0.1:{port}")
    try:
        for url, p, log in zip(urls, procs, logs):
            _wait_up(url, p, log)
        yield urls
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        fake.close()


def test_fl_cycle_spans_replicas(replicas):
    """host→A, auth→B, cycle→B, model→A, report→B, checkpoint→A."""
    import jax

    from pygrid_tpu.client import FLClient, ModelCentricFLClient
    from pygrid_tpu.models import mlp
    from pygrid_tpu.plans.plan import Plan

    url_a, url_b = replicas
    params = [np.asarray(p) for p in mlp.init(jax.random.PRNGKey(0), (D, H, C))]
    plan = Plan(name="training_plan", fn=mlp.training_step)
    plan.build(
        np.zeros((B, D), np.float32), np.zeros((B, C), np.float32),
        np.float32(0.1), *params,
    )
    mc = ModelCentricFLClient(url_a)
    resp = mc.host_federated_training(
        model=params,
        client_plans={"training_plan": plan},
        client_config={
            "name": NAME, "version": VERSION, "batch_size": B, "lr": 0.1,
            "max_updates": 1,
        },
        server_config={
            "min_workers": 1, "max_workers": 2, "min_diffs": 1,
            "max_diffs": 1, "num_cycles": 2,
        },
    )
    assert resp.get("status") == "success"

    # the OTHER replica sees the hosted process through the shared DB
    cl = FLClient(url_b)
    auth = cl.authenticate(NAME, VERSION)
    wid = auth["worker_id"]
    cyc = cl.cycle_request(wid, NAME, VERSION, 1.0, 100.0, 100.0)
    assert cyc["status"] == "accepted", cyc

    # model download from replica A with B's request key: eligibility is
    # DB state, not process state
    cl_a = FLClient(url_a)
    got = cl_a.get_model(wid, cyc["request_key"], cyc["model_id"])
    for a, b in zip(got, params):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    # train one step locally, report the diff to replica B
    rng = np.random.default_rng(1)
    X = rng.normal(size=(B, D)).astype(np.float32)
    y = np.eye(C, dtype=np.float32)[rng.integers(0, C, B)]
    out = mlp.training_step(X, y, np.float32(0.1), *[np.asarray(p) for p in got])
    new_params = [np.asarray(p) for p in out[2:]]  # (loss, acc, *params)
    from pygrid_tpu.plans.state import serialize_model_params

    diff = [p - n for p, n in zip(params, new_params)]
    rep = cl.report(wid, cyc["request_key"], serialize_model_params(diff))
    assert "error" not in rep, rep

    # aggregation (min_diffs=1) produced checkpoint 2 — visible from A
    from pygrid_tpu.plans.state import unserialize_model_params

    try:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            r = requests.get(
                url_a + "/model-centric/retrieve-model",
                params={
                    "name": NAME, "version": VERSION, "checkpoint": "latest",
                },
                timeout=10,
            )
            if r.status_code == 200:
                ckpt = unserialize_model_params(r.content)
                if not all(
                    np.allclose(a, b) for a, b in zip(ckpt, params)
                ):
                    for a, b in zip(ckpt, new_params):
                        np.testing.assert_allclose(
                            np.asarray(a), np.asarray(b),
                            rtol=1e-4, atol=1e-5,
                        )
                    return
            time.sleep(0.5)
        raise AssertionError(
            "aggregated checkpoint never appeared on replica A"
        )
    finally:
        mc.close()
        cl.close()
        cl_a.close()


def test_aggregation_spans_replicas(replicas):
    """min_diffs=2 with the two diffs reported to DIFFERENT replicas:
    the replica receiving the completing report must fold in the diff
    row the other process ingested — the in-memory accumulator cannot
    cover it, so completion has to rebuild from the shared rows."""
    import jax

    from pygrid_tpu.client import FLClient, ModelCentricFLClient
    from pygrid_tpu.models import mlp
    from pygrid_tpu.plans.plan import Plan
    from pygrid_tpu.plans.state import (
        serialize_model_params,
        unserialize_model_params,
    )

    url_a, url_b = replicas
    name = "scaleout-agg"
    params = [np.asarray(p) for p in mlp.init(jax.random.PRNGKey(1), (D, H, C))]
    plan = Plan(name="training_plan", fn=mlp.training_step)
    plan.build(
        np.zeros((B, D), np.float32), np.zeros((B, C), np.float32),
        np.float32(0.1), *params,
    )
    mc = ModelCentricFLClient(url_a)
    mc.host_federated_training(
        model=params,
        client_plans={"training_plan": plan},
        client_config={
            "name": name, "version": VERSION, "batch_size": B, "lr": 0.1,
            "max_updates": 1,
        },
        server_config={
            "min_workers": 2, "max_workers": 2, "min_diffs": 2,
            "max_diffs": 2, "num_cycles": 2,
        },
    )
    mc.close()

    diffs = []
    clients = []
    for i, url in enumerate((url_a, url_b)):
        cl = FLClient(url)
        clients.append(cl)
        auth = cl.authenticate(name, VERSION)
        cyc = cl.cycle_request(
            auth["worker_id"], name, VERSION, 1.0, 100.0, 100.0
        )
        assert cyc["status"] == "accepted", cyc
        diff = [np.full_like(p, 0.1 * (i + 1)) for p in params]
        diffs.append(diff)
        rep = cl.report(
            auth["worker_id"], cyc["request_key"], serialize_model_params(diff)
        )
        assert "error" not in rep, rep
    for cl in clients:
        cl.close()

    expected = [
        p - (d0 + d1) / 2.0
        for p, d0, d1 in zip(params, diffs[0], diffs[1])
    ]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        r = requests.get(
            url_b + "/model-centric/retrieve-model",
            params={"name": name, "version": VERSION, "checkpoint": "latest"},
            timeout=10,
        )
        if r.status_code == 200:
            ckpt = unserialize_model_params(r.content)
            if not np.allclose(np.asarray(ckpt[0]), params[0]):
                for a, b in zip(ckpt, expected):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
                    )
                return
        time.sleep(0.5)
    raise AssertionError("cross-replica aggregation never completed")
