"""Model-centric FL coordination plane — mirrors the protocol semantics of
reference tests/model_centric/test_fl_process.py (host → authenticate →
cycle-request → report → aggregate) without the WS transport (integration
tests add it)."""

import datetime as dt

import jax
import jax.numpy as jnp
import numpy as np
import os

import pytest

from pygrid_tpu.federated import FLController, auth as fed_auth, tasks
from pygrid_tpu.federated import schemas as S
from pygrid_tpu.plans import Plan
from pygrid_tpu.plans.state import serialize_model_params, unserialize_model_params
from pygrid_tpu.storage import Database
from pygrid_tpu.utils import exceptions as E
from pygrid_tpu.utils.codes import CYCLE
from pygrid_tpu.utils.exceptions import (
    AuthorizationError,
    FLProcessConflict,
    InvalidRequestKeyError,
)

tasks.set_sync(True)  # deterministic cycle completion in tests


def _model_params():
    rng = np.random.RandomState(0)
    return [
        rng.randn(10, 4).astype(np.float32) * 0.1,
        np.zeros(4, np.float32),
    ]


def _training_plan():
    def step(X, y, lr, w, b):
        def loss_fn(p):
            w_, b_ = p
            pred = X @ w_ + b_
            return jnp.mean((pred - y) ** 2)

        loss, g = jax.value_and_grad(loss_fn)((w, b))
        return loss, w - lr * g[0], b - lr * g[1]

    plan = Plan(name="training_plan", fn=step)
    plan.build(
        np.zeros((8, 10), np.float32),
        np.zeros((8, 4), np.float32),
        np.float32(0.1),
        *_model_params(),
    )
    return plan



#: engines the suite runs against: sqlite always; postgres against a
#: live server when PYGRID_TEST_DATABASE_URL names a throwaway database,
#: else against the in-process protocol-v3 fake (tests/unit/_pg_fake.py)
#: so the pg engine path executes in CI regardless. Every fresh_db()
#: call drops the grid tables first so each test starts clean,
#: mirroring :memory: semantics.
_GRID_TABLES = (
    "flprocess", "model", "modelcheckpoint", "plan", "protocol", "config",
    "cycle", "workercycle", "worker", "serveroptstate",
    "fedbuffcontribution",
)


@pytest.fixture(params=["sqlite", "postgres"])
def fresh_db(request):
    """Factory for a clean Database on the parametrized engine."""
    if request.param == "postgres":
        url = os.environ.get("PYGRID_TEST_DATABASE_URL")
        fake = None
        if not url:
            from _pg_fake import FakePg

            fake = FakePg()
            url = fake.url

        def make():
            db = Database(url)
            for t in _GRID_TABLES:
                db.execute(f'DROP TABLE IF EXISTS "{t}"')
            return db

        yield make
        if fake is not None:
            fake.close()
        return
    yield lambda: Database(":memory:")


SERVER_CONFIG = {
    "min_workers": 2,
    "max_workers": 5,
    "num_cycles": 2,
    "cycle_length": None,
    "max_diffs": 2,
    "min_diffs": 2,
    "minimum_upload_speed": 0,
    "minimum_download_speed": 0,
}
CLIENT_CONFIG = {
    "name": "mnist", "version": "1.0", "batch_size": 8, "lr": 0.1,
    "max_updates": 2,
}


@pytest.fixture()
def controller(fresh_db):
    db = fresh_db()
    ctl = FLController(db)
    ctl.create_process(
        model_blob=serialize_model_params(_model_params()),
        client_plans={"training_plan": _training_plan()},
        name="mnist",
        version="1.0",
        client_config=dict(CLIENT_CONFIG),
        server_config=dict(SERVER_CONFIG),
    )
    return ctl


def _register_worker(ctl, wid, upload=100.0, download=100.0):
    w = ctl.worker_manager.create(wid)
    w.avg_upload, w.avg_download, w.ping = upload, download, 1.0
    ctl.worker_manager.update(w)
    return ctl.worker_manager.get(id=wid)


def test_host_conflict(controller):
    with pytest.raises(FLProcessConflict):
        controller.create_process(
            model_blob=b"x",
            client_plans={"p": _training_plan()},
            name="mnist",
            version="1.0",
            client_config={},
            server_config={},
        )


def test_assign_accept_shape(controller):
    w = _register_worker(controller, "w1")
    resp = controller.assign("mnist", "1.0", w)
    assert resp[CYCLE.STATUS] == CYCLE.ACCEPTED
    assert len(resp[CYCLE.KEY]) == 64  # sha256 hex
    assert "training_plan" in resp[CYCLE.PLANS]
    assert resp[CYCLE.CLIENT_CONFIG]["batch_size"] == 8


def test_assign_dedup_rejected(controller):
    w = _register_worker(controller, "w1")
    assert controller.assign("mnist", "1.0", w)[CYCLE.STATUS] == CYCLE.ACCEPTED
    assert controller.assign("mnist", "1.0", w)[CYCLE.STATUS] == CYCLE.REJECTED


def test_assign_bandwidth_rejected(controller):
    slow = _register_worker(controller, "slow", upload=0.1, download=0.1)
    cfg = controller.process_manager.get_configs(
        fl_process_id=1, is_server_config=True
    )
    cfg["minimum_upload_speed"] = 2.0
    cfg["minimum_download_speed"] = 4.0
    controller.process_manager._configs.modify(
        {"fl_process_id": 1, "is_server_config": True}, {"config": cfg}
    )
    assert controller.assign("mnist", "1.0", slow)[CYCLE.STATUS] == CYCLE.REJECTED


def test_invalid_request_key(controller):
    _register_worker(controller, "w1")
    with pytest.raises(InvalidRequestKeyError):
        controller.submit_diff("w1", "bogus", b"diff")


def _one_round(ctl, worker_ids, lr=0.1):
    """Run one full cycle: each worker trains locally and reports a diff."""
    accepted = {}
    for wid in worker_ids:
        w = _register_worker(ctl, wid)
        resp = ctl.assign("mnist", "1.0", w)
        if resp[CYCLE.STATUS] == CYCLE.ACCEPTED:
            accepted[wid] = resp

    rng = np.random.RandomState(42)
    X = rng.randn(8, 10).astype(np.float32)
    true_w = rng.randn(10, 4).astype(np.float32)
    y = X @ true_w

    for wid, resp in accepted.items():
        ckpt = ctl.model_manager.load(model_id=resp["model_id"], alias="latest")
        params = unserialize_model_params(ckpt.value)
        plan_blob = ctl.plan_manager.get_variant(
            resp[CYCLE.PLANS]["training_plan"], "torchscript"
        )
        plan = ctl.plan_manager.deserialize_plan(plan_blob)
        loss, new_w, new_b = plan(X, y, np.float32(lr), *params)
        diff = [
            np.asarray(p) - np.asarray(n) for p, n in zip(params, (new_w, new_b))
        ]
        ctl.submit_diff(wid, resp[CYCLE.KEY], serialize_model_params(diff))
    return accepted


def test_full_fedavg_round_updates_checkpoint(controller):
    before = controller.model_manager.load(model_id=1, alias="latest")
    _one_round(controller, ["w1", "w2"])
    after = controller.model_manager.load(model_id=1, alias="latest")
    assert after.number == before.number + 1 and after.alias == "latest"
    p_before = unserialize_model_params(before.value)
    p_after = unserialize_model_params(after.value)
    assert not np.allclose(p_before[0], p_after[0])  # params moved
    # next cycle spawned
    cycle = controller.cycle_manager.last(1)
    assert cycle.sequence == 2


def test_fedavg_learns(controller):
    """Two FedAvg rounds reduce the loss on the shared objective."""
    rng = np.random.RandomState(42)
    X = rng.randn(8, 10).astype(np.float32)
    true_w = rng.randn(10, 4).astype(np.float32)
    y = X @ true_w

    def loss_of(params):
        return float(np.mean((X @ params[0] + params[1] - y) ** 2))

    l0 = loss_of(
        unserialize_model_params(
            controller.model_manager.load(model_id=1, alias="latest").value
        )
    )
    _one_round(controller, ["w1", "w2"])
    _one_round(controller, ["w3", "w4"])
    l2 = loss_of(
        unserialize_model_params(
            controller.model_manager.load(model_id=1, alias="latest").value
        )
    )
    assert l2 < l0


def test_num_cycles_exhaustion(controller):
    _one_round(controller, ["w1", "w2"])
    _one_round(controller, ["w3", "w4"])
    # num_cycles=2 reached: no open cycle remains
    from pygrid_tpu.utils.exceptions import CycleNotFoundError

    with pytest.raises(CycleNotFoundError):
        controller.cycle_manager.last(1)


def test_checkpoint_history_retrievable(controller):
    _one_round(controller, ["w1", "w2"])
    first = controller.model_manager.load(model_id=1, number=1)
    latest = controller.model_manager.load(model_id=1, alias="latest")
    assert first.number == 1 and latest.number == 2


def test_iterative_averaging_plan(fresh_db):
    """Hosted running-mean averaging plan: avg = plan(*avg, *diff, i) with the
    index LAST (reference cycle_manager.py:269)."""
    db = fresh_db()
    ctl = FLController(db)

    def running_mean(avg_w, avg_b, diff_w, diff_b, i):
        new_w = (avg_w * (i - 1) + diff_w) / i
        new_b = (avg_b * (i - 1) + diff_b) / i
        return new_w, new_b

    avg_plan = Plan(name="avg", fn=running_mean)
    avg_plan.build(
        np.zeros((10, 4), np.float32), np.zeros(4, np.float32),
        np.zeros((10, 4), np.float32), np.zeros(4, np.float32),
        np.float32(1.0),
    )
    ctl.create_process(
        model_blob=serialize_model_params(_model_params()),
        client_plans={"training_plan": _training_plan()},
        server_averaging_plan=avg_plan,
        name="mnist", version="1.0",
        client_config={},
        server_config={**SERVER_CONFIG, "iterative_plan": True, "num_cycles": 1},
    )
    p0 = unserialize_model_params(
        ctl.model_manager.load(model_id=1, alias="latest").value
    )
    diffs = []
    for wid in ("w1", "w2"):
        w = ctl.worker_manager.create(wid)
        w.avg_upload = w.avg_download = 100.0
        ctl.worker_manager.update(w)
        resp = ctl.assign("mnist", "1.0", ctl.worker_manager.get(id=wid))
        d = [np.full((10, 4), 0.5 if wid == "w1" else 1.5, np.float32),
             np.full(4, 0.1 if wid == "w1" else 0.3, np.float32)]
        diffs.append(d)
        ctl.submit_diff(wid, resp[CYCLE.KEY], serialize_model_params(d))
    p1 = unserialize_model_params(
        ctl.model_manager.load(model_id=1, alias="latest").value
    )
    # avg of the two diffs: w -> 1.0, b -> 0.2
    np.testing.assert_allclose(p0[0] - p1[0], np.full((10, 4), 1.0), atol=1e-5)
    np.testing.assert_allclose(p0[1] - p1[1], np.full(4, 0.2), atol=1e-5)


def test_run_task_once_rerun_coalescing():
    """A trigger arriving mid-run must re-run the task once, not be dropped."""
    import threading as th
    import time

    tasks.set_sync(False)
    try:
        runs, gate = [], th.Event()

        def task():
            runs.append(1)
            if len(runs) == 1:
                gate.wait(5)

        tasks.run_task_once("k", task)      # starts, blocks on gate
        time.sleep(0.05)
        tasks.run_task_once("k", task)      # arrives mid-run -> queued
        tasks.run_task_once("k", task)      # coalesced with the queued one
        gate.set()
        for _ in range(100):
            with tasks._lock:
                if "k" not in tasks._state:
                    break
            time.sleep(0.02)
        assert len(runs) == 2  # initial + exactly one rerun
    finally:
        tasks.set_sync(True)


# --- federated JWT auth -----------------------------------------------------


def test_auth_unauthenticated_allowed():
    assert fed_auth.verify_token(None, {})["status"] == "success"


def test_auth_hs256_roundtrip():
    cfg = {"authentication": {"secret": "topsecret"}}
    token = fed_auth.jwt_encode({"sub": "w1"}, secret="topsecret")
    assert fed_auth.verify_token(token, cfg)["payload"]["sub"] == "w1"
    with pytest.raises(AuthorizationError):
        fed_auth.verify_token(token[:-3] + "xyz", cfg)
    with pytest.raises(AuthorizationError):
        fed_auth.verify_token(None, cfg)


def test_auth_rs256_roundtrip():
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric import rsa

    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    priv = key.private_bytes(
        serialization.Encoding.PEM,
        serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption(),
    )
    pub = key.public_key().public_bytes(
        serialization.Encoding.PEM, serialization.PublicFormat.SubjectPublicKeyInfo
    )
    cfg = {"authentication": {"pub_key": pub.decode()}}
    token = fed_auth.jwt_encode({"sub": "w2"}, private_key_pem=priv)
    assert fed_auth.verify_token(token, cfg)["payload"]["sub"] == "w2"
    other = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    bad = fed_auth.jwt_encode(
        {"sub": "w2"},
        private_key_pem=other.private_bytes(
            serialization.Encoding.PEM,
            serialization.PrivateFormat.PKCS8,
            serialization.NoEncryption(),
        ),
    )
    with pytest.raises(AuthorizationError):
        fed_auth.verify_token(bad, cfg)


def test_auth_expired_token():
    import time

    cfg = {"authentication": {"secret": "s"}}
    token = fed_auth.jwt_encode({"sub": "w", "exp": time.time() - 10}, secret="s")
    with pytest.raises(AuthorizationError):
        fed_auth.verify_token(token, cfg)


def test_aggregation_scales_to_256_diffs(fresh_db):
    """One cycle ingesting 256 worker diffs: the submit-time accumulator
    folds each into the running f64 sum, so completion is a divide and the
    result is the exact average (the scaling case the reference's per-diff
    f32 reduce loop, cycle_manager.py:275-290, degrades on)."""
    K = 256
    db = fresh_db()
    ctl = FLController(db)
    params = _model_params()
    ctl.create_process(
        model_blob=serialize_model_params(params),
        client_plans={"training_plan": _training_plan()},
        name="mnist-wide",
        version="1.0",
        client_config=dict(CLIENT_CONFIG, name="mnist-wide"),
        server_config=dict(
            SERVER_CONFIG,
            min_diffs=K,
            max_diffs=K,
            min_workers=K,
            max_workers=K,
            num_cycles=1,
        ),
    )
    model_id = None
    for k in range(K):
        w = _register_worker(ctl, f"wide-{k}")
        resp = ctl.assign("mnist-wide", "1.0", w)
        assert resp[CYCLE.STATUS] == CYCLE.ACCEPTED
        model_id = resp["model_id"]
        diff = [
            np.full((10, 4), 0.01 * k, np.float32),
            np.full((4,), 0.01 * k, np.float32),
        ]
        ctl.submit_diff(f"wide-{k}", resp[CYCLE.KEY], serialize_model_params(diff))
    latest = ctl.model_manager.load(model_id=model_id, alias="latest")
    new = unserialize_model_params(latest.value)
    mean_diff = np.float32(np.mean([0.01 * k for k in range(K)], dtype=np.float64))
    np.testing.assert_allclose(
        np.asarray(new[0]), params[0] - mean_diff, rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(new[1]), params[1] - mean_diff, rtol=1e-4
    )


def test_deadline_completes_cycle_without_further_reports(fresh_db):
    """min_diffs reached, remaining workers vanish: the deadline timer armed
    at cycle creation closes the cycle within ~1s of ``cycle.end`` with no
    further protocol event. The reference only re-checks readiness inside
    submit_worker_diff (cycle_manager.py:180-217), so its cycle would hang."""
    import time

    db = fresh_db()
    ctl = FLController(db)
    params = _model_params()
    ctl.create_process(
        model_blob=serialize_model_params(params),
        client_plans={"training_plan": _training_plan()},
        name="mnist-deadline",
        version="1.0",
        client_config=dict(CLIENT_CONFIG, name="mnist-deadline"),
        server_config=dict(
            SERVER_CONFIG,
            min_diffs=1,
            max_diffs=5,
            min_workers=1,
            max_workers=5,
            # 3s, not 1s: the postgres engines add per-statement socket
            # round-trips to setup, and the deadline must not fire
            # before the first is-open assertion
            cycle_length=3,
            num_cycles=1,
        ),
    )
    w = _register_worker(ctl, "early-bird")
    resp = ctl.assign("mnist-deadline", "1.0", w)
    assert resp[CYCLE.STATUS] == CYCLE.ACCEPTED
    diff = [np.full((10, 4), 0.5, np.float32), np.full((4,), 0.5, np.float32)]
    ctl.submit_diff("early-bird", resp[CYCLE.KEY], serialize_model_params(diff))
    cycle = ctl.cycle_manager._cycles.first(is_completed=False)
    assert cycle is not None, "cycle must stay open until the deadline"
    deadline = time.monotonic() + 8.0
    while time.monotonic() < deadline:
        cycle = ctl.cycle_manager._cycles.first(id=cycle.id)
        if cycle.is_completed:
            break
        time.sleep(0.05)
    assert cycle.is_completed, "deadline timer did not close the cycle"
    # the single received diff became the aggregate
    latest = ctl.model_manager.load(model_id=resp["model_id"], alias="latest")
    new = unserialize_model_params(latest.value)
    np.testing.assert_allclose(np.asarray(new[0]), params[0] - 0.5, rtol=1e-5)


def test_recover_deadlines_rearms_after_restart(fresh_db):
    """A node restarted mid-cycle re-arms deadline timers from SQL
    (recover_deadlines is called by NodeContext init)."""
    import time

    db = fresh_db()
    ctl = FLController(db)
    params = _model_params()
    ctl.create_process(
        model_blob=serialize_model_params(params),
        client_plans={"training_plan": _training_plan()},
        name="mnist-recover",
        version="1.0",
        client_config=dict(CLIENT_CONFIG, name="mnist-recover"),
        server_config=dict(
            SERVER_CONFIG, min_diffs=1, max_diffs=5, min_workers=1,
            cycle_length=3, num_cycles=1,
        ),
    )
    w = _register_worker(ctl, "w-restart")
    resp = ctl.assign("mnist-recover", "1.0", w)
    diff = [np.zeros((10, 4), np.float32), np.zeros(4, np.float32)]
    ctl.submit_diff("w-restart", resp[CYCLE.KEY], serialize_model_params(diff))
    # simulate restart: drop the live timer, then recover from SQL
    cycle = ctl.cycle_manager._cycles.first(is_completed=False)
    timer = ctl.cycle_manager._deadline_timers.pop(cycle.id)
    timer.cancel()
    ctl.cycle_manager.recover_deadlines()
    deadline = time.monotonic() + 8.0
    while time.monotonic() < deadline:
        if ctl.cycle_manager._cycles.first(id=cycle.id).is_completed:
            break
        time.sleep(0.05)
    assert ctl.cycle_manager._cycles.first(id=cycle.id).is_completed


def test_accumulator_matches_blob_rebuild(fresh_db):
    """The streaming accumulator and the restart path (rebuild from stored
    blobs) must agree exactly: drop the accumulator mid-cycle and the
    aggregate is unchanged."""
    db = fresh_db()
    ctl = FLController(db)
    params = _model_params()
    ctl.create_process(
        model_blob=serialize_model_params(params),
        client_plans={"training_plan": _training_plan()},
        name="mnist-acc",
        version="1.0",
        client_config=dict(CLIENT_CONFIG, name="mnist-acc"),
        server_config=dict(SERVER_CONFIG, num_cycles=1),
    )
    rng = np.random.RandomState(3)
    diffs = []
    for k in range(2):
        w = _register_worker(ctl, f"acc-{k}")
        resp = ctl.assign("mnist-acc", "1.0", w)
        d = [rng.randn(10, 4).astype(np.float32), rng.randn(4).astype(np.float32)]
        diffs.append(d)
        if k == 0:
            ctl.submit_diff(f"acc-{k}", resp[CYCLE.KEY], serialize_model_params(d))
            # "restart": the in-memory accumulator is lost
            ctl.cycle_manager._accum.clear()
        else:
            ctl.submit_diff(f"acc-{k}", resp[CYCLE.KEY], serialize_model_params(d))
    latest = ctl.model_manager.load(model_id=resp["model_id"], alias="latest")
    new = unserialize_model_params(latest.value)
    expected = params[0] - np.mean([d[0] for d in diffs], axis=0)
    np.testing.assert_allclose(np.asarray(new[0]), expected, rtol=1e-5)


def test_a_second_managers_readiness_check_is_its_own_task():
    """Every database numbers its cycles from 1 and ``run_task_once``
    keys are global to the process: while ANOTHER manager's check of
    its cycle 1 is still in flight (a node beside this one, or the
    previous test's deadline timer), this manager's report must still
    complete its own cycle 1 before ``submit_diff`` returns — not be
    queued as a rerun behind the other's and read stale."""
    import threading as th

    ctls = []
    for name in ("mgr-a", "mgr-b"):
        ctl = FLController(Database(":memory:"))
        ctl.create_process(
            model_blob=serialize_model_params(_model_params()),
            client_plans={"training_plan": _training_plan()},
            name=name,
            version="1.0",
            client_config=dict(CLIENT_CONFIG, name=name),
            server_config=dict(
                SERVER_CONFIG, min_workers=1, min_diffs=1, max_diffs=1,
                num_cycles=1,
            ),
        )
        ctls.append(ctl)
    a, b = ctls
    cycle_a = a.cycle_manager._cycles.first(is_completed=False)
    cycle_b = b.cycle_manager._cycles.first(is_completed=False)
    assert cycle_a.id == cycle_b.id  # the collision this test is about
    started, gate = th.Event(), th.Event()

    def held():
        started.set()
        gate.wait(10)

    other = th.Thread(
        target=tasks.run_task_once,
        args=(a.cycle_manager._completion_key(cycle_a.id), held),
    )
    other.start()
    try:
        assert started.wait(5)
        w = _register_worker(b, "solo")
        resp = b.assign("mgr-b", "1.0", w)
        diff = [np.full((10, 4), 0.5, np.float32), np.full(4, 0.5, np.float32)]
        b.submit_diff("solo", resp[CYCLE.KEY], serialize_model_params(diff))
        assert b.cycle_manager._cycles.first(id=cycle_b.id).is_completed
        latest = b.model_manager.load(model_id=resp["model_id"], alias="latest")
        np.testing.assert_allclose(
            np.asarray(unserialize_model_params(latest.value)[0]),
            _model_params()[0] - 0.5, rtol=1e-5,
        )
    finally:
        gate.set()
        other.join(10)


def test_deadline_with_zero_diffs_closes_cycle_without_checkpoint(fresh_db):
    """No min_diffs + nobody reports: the deadline closes the cycle with
    the model unchanged (no checkpoint written) and spawns the next cycle —
    averaging nothing must not crash the timer thread."""
    import time

    db = fresh_db()
    ctl = FLController(db)
    params = _model_params()
    ctl.create_process(
        model_blob=serialize_model_params(params),
        client_plans={"training_plan": _training_plan()},
        name="mnist-empty",
        version="1.0",
        client_config=dict(CLIENT_CONFIG, name="mnist-empty"),
        server_config={
            "min_workers": 1, "max_workers": 5, "cycle_length": 1,
            "num_cycles": 2,
        },
    )
    first = ctl.cycle_manager._cycles.first(is_completed=False)
    deadline = time.monotonic() + 8.0
    while time.monotonic() < deadline:
        if ctl.cycle_manager._cycles.first(id=first.id).is_completed:
            break
        time.sleep(0.05)
    assert ctl.cycle_manager._cycles.first(id=first.id).is_completed
    # model untouched, next cycle spawned (the timer thread marks the cycle
    # complete a few queries before it creates the next one)
    model = ctl.model_manager.get(fl_process_id=1)
    assert ctl.model_manager.load(model_id=model.id, alias="latest").number == 1
    while time.monotonic() < deadline:
        if ctl.cycle_manager._cycles.first(fl_process_id=1, is_completed=False):
            break
        time.sleep(0.05)
    assert ctl.cycle_manager.last(1).sequence == 2


def test_add_raw_matches_add_exactly():
    """The wire-buffer fold (add_raw, native kernels) and the decoded
    fold (add) must produce bit-identical sums — they are the same f64
    accumulation in different plumbing."""
    from pygrid_tpu.federated.cycle_manager import _DiffAccumulator
    from pygrid_tpu.serde import state_raw_tensors

    rng = np.random.RandomState(11)
    diffs = [
        [rng.randn(37, 5).astype(np.float32), rng.randn(5).astype(np.float32)]
        for _ in range(4)
    ]
    a_dec, a_raw = _DiffAccumulator(), _DiffAccumulator()
    for d in diffs:
        a_dec.add(d)
        raws = state_raw_tensors(serialize_model_params(d))
        assert raws is not None
        a_raw.add_raw(raws)
    for s_dec, s_raw in zip(a_dec.sums, a_raw.sums):
        np.testing.assert_array_equal(s_dec, s_raw)
    # bf16 wire: add_raw folds the bf16 bits; equal to decoding then adding
    from pygrid_tpu.native import bf16_to_f32, f32_to_bf16

    a_bf_dec, a_bf_raw = _DiffAccumulator(), _DiffAccumulator()
    for d in diffs:
        decoded = [bf16_to_f32(f32_to_bf16(t)).reshape(t.shape) for t in d]
        a_bf_dec.add(decoded, weight=0.5)
        raws = state_raw_tensors(serialize_model_params(d, bf16=True))
        a_bf_raw.add_raw(raws, weight=0.5)
    for s_dec, s_raw in zip(a_bf_dec.sums, a_bf_raw.sums):
        np.testing.assert_array_equal(s_dec, s_raw)


def test_wrong_shape_fast_path_report_bounces(fresh_db):
    """A dense State with mismatched shapes must bounce through the fast
    ingest exactly like the decode door (same typed error, no state
    change)."""
    db = fresh_db()
    ctl = FLController(db)
    params = _model_params()
    ctl.create_process(
        model_blob=serialize_model_params(params),
        client_plans={"training_plan": _training_plan()},
        name="mnist-badshape",
        version="1.0",
        client_config=dict(CLIENT_CONFIG, name="mnist-badshape"),
        server_config=dict(SERVER_CONFIG, num_cycles=1),
    )
    w = _register_worker(ctl, "bad-shape-w")
    resp = ctl.assign("mnist-badshape", "1.0", w)
    bad = [np.zeros((3, 3), np.float32)]
    with pytest.raises(E.PyGridError, match="shapes"):
        ctl.submit_diff(
            "bad-shape-w", resp[CYCLE.KEY], serialize_model_params(bad)
        )
    # the assignment is still open and a correct report succeeds
    good = [np.zeros_like(p) for p in params]
    ctl.submit_diff("bad-shape-w", resp[CYCLE.KEY], serialize_model_params(good))


def test_fedbuff_migration_marks_preexisting_rows_flushed(fresh_db):
    """A pre-durability DB (no `flushed` column) migrates with every
    completed row marked flushed — whatever those rows contributed was
    handled by the old in-memory flush, and they must never re-enter a
    buffer and double-apply onto the current checkpoint."""
    db = fresh_db()
    # hand-written pre-upgrade DDL must speak the engine's own dialect
    # (a live postgres rejects AUTOINCREMENT and x'..' literals)
    if db.dialect == "postgres":
        pk, blob = "id BIGSERIAL PRIMARY KEY", "BYTEA"
    else:
        pk, blob = "id INTEGER PRIMARY KEY AUTOINCREMENT", "BLOB"
    db.execute(
        f'CREATE TABLE "workercycle" ({pk}, cycle_id INTEGER, '
        "worker_id TEXT, request_key TEXT, started_at TEXT, "
        f"is_completed INTEGER, completed_at TEXT, diff {blob}, "
        f"assigned_checkpoint INTEGER, metrics {blob})"
    )
    db.execute(
        'INSERT INTO "workercycle" (cycle_id, worker_id, request_key, '
        "is_completed, diff) VALUES (1, 'old-w', 'old-k', 1, ?)",
        (b"\x00",),
    )
    db.execute(
        'INSERT INTO "workercycle" (cycle_id, worker_id, request_key, '
        "is_completed) VALUES (1, 'open-w', 'open-k', 0)"
    )
    ctl = FLController(db)
    done = ctl.cycle_manager._worker_cycles.first(worker_id="old-w")
    assert done.flushed is True
    still_open = ctl.cycle_manager._worker_cycles.first(worker_id="open-w")
    assert not still_open.flushed
    assert ctl.cycle_manager._async_buffered_count(0) == 0


def test_empty_diff_accumulator_mean_is_typed():
    """A cycle can flush with zero accepted reports (deadline fires,
    every diff bounced validation): ``_DiffAccumulator.mean()`` on the
    empty accumulator used to raise a raw TypeError (iterating
    ``sums=None``) — it must surface the real condition as a typed
    PyGridError the protocol boundary can frame."""
    from pygrid_tpu.federated.cycle_manager import _DiffAccumulator

    acc = _DiffAccumulator()
    with pytest.raises(E.PyGridError, match="zero accepted reports"):
        acc.mean()
    # zero total weight (all contributions weighted to nothing) is the
    # same condition via the ZeroDivisionError door
    acc.add([np.zeros(3, np.float32)], weight=0.0)
    with pytest.raises(E.PyGridError, match="zero accepted reports"):
        acc.mean()
    # a real report still averages
    acc.add([np.ones(3, np.float32)], weight=2.0)
    (mean,) = acc.mean()
    np.testing.assert_allclose(mean, np.ones(3, np.float32))
