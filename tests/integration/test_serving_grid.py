"""Continuous-batching serving over a real grid node.

N concurrent websocket clients issue mixed-length greedy generation
requests against one hosted bundle and must get EXACTLY the tokens the
sequential single-request path produces — the end-to-end proof that the
shared block pool leaks nothing across concurrently-decoding requests.
Plus: the async HTTP door, typed backpressure over the wire, and the
new serving metrics families under the strict Prometheus parser.
"""

from __future__ import annotations

import base64
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest
import requests

import jax

from pygrid_tpu.client import DataCentricFLClient
from pygrid_tpu.models import decode
from pygrid_tpu.models import transformer as T
from pygrid_tpu.serde import serialize
from pygrid_tpu.telemetry import promtext

CFG = T.TransformerConfig(
    vocab=37, d_model=16, n_heads=2, n_layers=1, d_ff=32, max_len=48
)
MODEL_ID = "serving-grid"


@pytest.fixture(scope="module")
def hosted(grid):
    params = T.init(jax.random.PRNGKey(11), CFG)
    client = DataCentricFLClient(grid.node_url("dan"))
    out = client.serve_model(
        decode.bundle(CFG, params), MODEL_ID, allow_remote_inference=True
    )
    assert out.get("success"), out
    yield params, client
    client.close()


def _cases(n, seed=0):
    """Mixed prompt lengths and n_new — every (len, n_new) distinct
    enough that a program per request shape would compile each time."""
    rng = np.random.RandomState(seed)
    return [
        (
            rng.randint(0, CFG.vocab, size=(1, int(rng.randint(1, 9)))),
            int(rng.randint(1, 10)),
        )
        for _ in range(n)
    ]


def test_concurrent_ws_clients_match_sequential_path(grid, hosted):
    """8 clients, 8 sockets, mixed shapes, all in flight at once: the
    batched engine's greedy tokens are bit-identical to the sequential
    single-request ``decode.generate`` for every request."""
    params, _ = hosted
    cases = _cases(8, seed=3)
    results: list = [None] * len(cases)
    errors: list = []

    def go(i):
        client = None
        try:
            client = DataCentricFLClient(grid.node_url("dan"))
            prompt, n_new = cases[i]
            results[i] = client.run_remote_generation(
                MODEL_ID, prompt, n_new=n_new
            )
        except Exception as err:  # noqa: BLE001 — collected for assert
            errors.append((i, err))
        finally:
            if client is not None:
                client.close()

    threads = [
        threading.Thread(target=go, args=(i,)) for i in range(len(cases))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    for (prompt, n_new), got in zip(cases, results):
        expect = np.asarray(
            decode.generate(params, prompt.astype(np.int32), n_new, CFG)
        )
        np.testing.assert_array_equal(got, expect)
    # the public leak ledger (ServingManager.ledger): once responses
    # land the engine may still be retiring its last slot, so allow a
    # short drain — then all block accounting must balance, with
    # nothing stuck in queues or slots
    serving = grid.nodes["dan"].app["node"].serving
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        ledger = serving.ledger()
        if ledger["balanced"] and all(
            led["queue_depth"] == 0 and led["live_slots"] == 0
            for led in ledger["engines"]
        ):
            break
        time.sleep(0.05)
    assert ledger["balanced"], ledger
    for led in ledger["engines"]:
        assert led["queue_depth"] == 0 and led["live_slots"] == 0, led


def test_http_route_serves_and_is_typed(grid, hosted):
    params, client = hosted
    base = grid.node_url("dan")
    prompt = np.array([[1, 2, 3]], np.int32)
    body = {
        "model_id": MODEL_ID,
        "data": base64.b64encode(serialize(prompt)).decode(),
        "n_new": 4,
    }
    headers = {"token": client._auth_token}
    resp = requests.post(
        base + "/data-centric/run-generation",
        json=body, headers=headers, timeout=60,
    )
    assert resp.status_code == 200, resp.text
    expect = np.asarray(decode.generate(params, prompt, 4, CFG))
    np.testing.assert_array_equal(np.asarray(resp.json()["tokens"]), expect)
    # validation defects are 400 with the same typed message as the WS
    # door (shared _prepare_generation)
    bad = dict(body, temperature=True)
    resp = requests.post(
        base + "/data-centric/run-generation",
        json=bad, headers=headers, timeout=30,
    )
    assert resp.status_code == 400
    assert "temperature" in resp.json()["error"]
    # no session token → 401-family error, not a traceback
    resp = requests.post(
        base + "/data-centric/run-generation", json=body, timeout=30
    )
    assert resp.status_code in (400, 401, 403)


def test_sampled_generation_reproducible_over_wire(grid, hosted):
    _params, client = hosted
    a = client.run_remote_generation(
        MODEL_ID, np.array([[5, 6]]), n_new=6, temperature=0.8, seed=99
    )
    b = client.run_remote_generation(
        MODEL_ID, np.array([[5, 6]]), n_new=6, temperature=0.8, seed=99
    )
    np.testing.assert_array_equal(a, b)
    # the SDK float()-coerces, so drive the raw frame: a string
    # temperature must bounce typed over the wire (satellite contract)
    out = client.ws.send_json(
        "run-generation", model_id=MODEL_ID, n_new=2,
        data=base64.b64encode(
            serialize(np.array([[1]], np.int32))
        ).decode(),
        temperature="0.9",
    )
    assert out.get("success") is False and "temperature" in out["error"]


def test_serving_metrics_families_strictly_valid(grid, hosted):
    """After traffic, the node /metrics exposes the serving families
    (queue depth, occupancy, TTFT, per-token latency, compiles) and the
    whole exposition still parses under the strict checker."""
    base = grid.node_url("dan")
    families = promtext.parse(
        requests.get(base + "/metrics", timeout=10).text
    )
    for name, kind in (
        ("pygrid_serving_requests_total", "counter"),
        ("pygrid_serving_tokens_total", "counter"),
        ("pygrid_serving_compiles_total", "counter"),
        ("pygrid_serving_ttft_seconds", "histogram"),
        ("pygrid_serving_token_seconds", "histogram"),
        ("pygrid_serving_batch_occupancy", "histogram"),
        ("pygrid_serving_queue_wait_seconds", "histogram"),
        ("pygrid_serving_queue_depth", "gauge"),
        ("pygrid_serving_live_slots", "gauge"),
        ("pygrid_serving_max_slots", "gauge"),
    ):
        assert name in families, f"/metrics missing {name}"
        assert families[name].type == kind, name

    stats = requests.get(base + "/telemetry/serving", timeout=10).json()
    (engine,) = [
        e for e in stats["engines"] if e["model_id"] == MODEL_ID
    ]
    assert engine["tokens_total"] > 0
    assert engine["requests_total"] >= 10
    assert engine["compiles_total"] > 0


def test_block_pool_gauges_are_there_for_every_engine(grid, hosted):
    """``/metrics`` shows the block pool of every engine, whatever it
    serves: free + used + cached is the pool ``/telemetry/serving``
    reports, beside the page size and the live pages' tail waste."""
    base = grid.node_url("dan")
    _, client = hosted
    client.run_remote_generation(MODEL_ID, np.array([[5, 6, 7]]), n_new=3)
    families = promtext.parse(
        requests.get(base + "/metrics", timeout=10).text
    )
    rows = requests.get(base + "/telemetry/serving", timeout=10).json()
    assert rows["engines"]
    for row in rows["engines"]:

        def mine(name, model=row["model_id"]):
            return [
                (labels, value)
                for _, labels, value in families[name].samples
                if labels.get("model") == model
            ]

        by_state = {
            labels["state"]: value
            for labels, value in mine("pygrid_serving_kv_blocks")
        }
        assert set(by_state) == {"free", "used", "cached"}
        assert sum(by_state.values()) == row["kv_blocks_total"]
        ((_, tokens),) = mine("pygrid_serving_kv_block_tokens")
        assert tokens == row["block_size"]
        ((_, waste),) = mine("pygrid_serving_kv_fragmentation")
        assert 0.0 <= waste < 1.0
        assert families["pygrid_serving_kv_blocks"].type == "gauge"


# ── two model families on one node ───────────────────────────────────────


@pytest.mark.parametrize("first", ["transformer", "recurrent"])
def test_two_families_are_served_through_one_door_under_one_budget(
    first, monkeypatch
):
    """A transformer bundle and a bundle of the family with a recurrent
    state, hosted on ONE node under one ``PYGRID_KV_BUDGET``, in either
    order: ``run-generation`` answers both through their engines, each
    to its own reference (``generate()`` bit for bit; the benchmark's
    plain float32 adapter to the logit tolerance of
    ``tests/unit/test_jamba_serving.py``). The recurrent family's fixed
    per-slot state is charged to its share before any block, neither
    share runs past its half by more than a block, and both ledgers
    close. Nothing at the door tells the families apart."""
    import jax.numpy as jnp

    from pygrid_tpu.models import jamba
    from pygrid_tpu.node import create_app
    from pygrid_tpu.serving import pagedkv

    from .conftest import ServerThread, _free_port

    root = Path(__file__).resolve().parents[2]
    if str(root / "perfbench") not in sys.path:
        sys.path.append(str(root / "perfbench"))
    from lib import reference, spec

    adapter = spec.load_model("jamba")
    cfg = json.loads(
        (root / "perfbench/configs/ai21-jamba2-3b.json").read_text()
    )
    cfg.update(adapter.tiny(cfg))
    jcfg = adapter.jamba_config(cfg)
    jparams = adapter.make_program_params(3, cfg, "float32")
    jweights = adapter.make_weights(3, cfg, "float32")
    tparams = T.init(jax.random.PRNGKey(11), CFG)

    slots, block = 2, 16
    state = pagedkv.state_bytes(jcfg, slots, jnp.float32)
    per_block = {
        "recurrent": pagedkv.block_bytes(jcfg, block, jnp.float32),
        "transformer": pagedkv.block_bytes(CFG, block, jnp.float32),
    }
    assert state > 0
    budget = 2 * (state + 16 * per_block["recurrent"])
    monkeypatch.setenv("PYGRID_KV_BUDGET", str(budget))
    monkeypatch.setenv("PYGRID_KV_BLOCK", str(block))
    monkeypatch.setenv("PYGRID_SERVING_SLOTS", str(slots))

    bundles = {
        "transformer": decode.bundle(CFG, tparams),
        "recurrent": jamba.bundle(jcfg, jparams),
    }
    rng = np.random.RandomState(5)
    prompts = {
        "transformer": rng.randint(0, CFG.vocab, size=(1, 7)),
        "recurrent": rng.randint(0, jcfg.vocab, size=(1, 21)),
    }
    order = [first] + [f for f in bundles if f != first]

    server = ServerThread(create_app("two-families"), _free_port()).start()
    client = DataCentricFLClient(server.url)
    try:
        for family in order:
            out = client.serve_model(
                bundles[family], family, allow_remote_inference=True
            )
            assert out.get("success"), out
        served = {
            family: np.asarray(
                client.run_remote_generation(
                    family, prompts[family], n_new=6
                )
            )
            for family in order + order  # each engine again, once warm
        }
        np.testing.assert_array_equal(
            served["transformer"],
            np.asarray(decode.generate(
                tparams, prompts["transformer"].astype(np.int32), 6, CFG
            )),
        )
        gaps, _ = reference.served_gaps(
            adapter, jweights, cfg, prompts["recurrent"],
            served["recurrent"], 32,
        )
        assert gaps.max() <= 5e-5, gaps

        serving = server.app["node"].serving
        rows = {row["model_id"]: row for row in serving.stats()}
        assert set(rows) == set(bundles)
        held = serving.budget.snapshot()["allocated_bytes"]
        for family, row in rows.items():
            fixed = state if family == "recurrent" else 0
            assert row["state_bytes"] == fixed
            assert row["requests_total"] == 2 and row["compiles_total"] > 0
            # what the budget holds against the model: its fixed state,
            # then its blocks (the trash block with them)
            assert held[family] == fixed + (
                row["kv_blocks_total"] + 1
            ) * per_block[family], (family, held, row)
            assert held[family] <= budget // 2 + per_block[family]
        assert sum(held.values()) <= budget
        ledger = serving.ledger()
        assert ledger["balanced"], ledger
        for led in ledger["engines"]:
            assert led["drained"], led
    finally:
        client.close()
        server.stop()
