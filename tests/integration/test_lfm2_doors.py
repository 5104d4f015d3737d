"""A short-convolution / attention bundle (a dense layer ahead of expert
layers with a sigmoid router and its bias) behind both doors of one node:
``run-generation`` over WS through the client SDK and over HTTP answers the
tokens the plain greedy loop over the reference's ``logits`` makes, for
prompts shorter than the convolution's taps and longer than a page, and
requests that share the engine's slots."""

from __future__ import annotations

import base64
import json
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np
import pytest
import requests

import jax.numpy as jnp

from pygrid_tpu.client import DataCentricFLClient
from pygrid_tpu.models import lfm2_moe
from pygrid_tpu.serde import serialize

from .conftest import ServerThread, _free_port

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "perfbench") not in sys.path:
    sys.path.append(str(ROOT / "perfbench"))
from lib import reference, spec  # noqa: E402

PAD = 128


@pytest.fixture(scope="module")
def node():
    from pygrid_tpu.node import create_app

    mp = pytest.MonkeyPatch()
    mp.setenv("PYGRID_KV_BLOCK", "16")
    mp.setenv("PYGRID_SERVING_SLOTS", "4")
    adapter = spec.load_model("lfm2_moe")
    cfg = json.loads((ROOT / "perfbench/configs/lfm2-24b-a2b.json").read_text())
    cfg.update(adapter.tiny(cfg))
    lcfg = adapter.lfm2_config(cfg)
    params = adapter.make_program_params(3, cfg, "float32")
    server = ServerThread(create_app("lfm2-doors"), _free_port()).start()
    client = DataCentricFLClient(server.url)
    try:
        out = client.serve_model(
            lfm2_moe.bundle(lcfg, params), "lfm2", allow_remote_inference=True
        )
        assert out.get("success"), out
        yield server, client, adapter, cfg
    finally:
        client.close()
        server.stop()
        mp.undo()


def _plain_generate(adapter, weights, cfg, prompt, n_new):
    """Greedy decoding by the reference alone: the whole sequence through
    ``logits`` again for every token (no cache, no state carried)."""
    seq = np.zeros((1, PAD), np.int32)
    seq[0, : prompt.shape[1]] = prompt[0]
    cfg_json = json.dumps(cfg, sort_keys=True)
    for at in range(prompt.shape[1], prompt.shape[1] + n_new):
        rows = reference._logit_rows(weights, jnp.asarray(seq), adapter.logits, cfg_json)
        seq[0, at] = int(np.asarray(rows[0, at - 1]).argmax())
    return seq[:, prompt.shape[1] : prompt.shape[1] + n_new]


def _post(server, client, prompt, n_new):
    return requests.post(
        server.url + "/data-centric/run-generation",
        headers={"token": client._auth_token},
        json={
            "model_id": "lfm2", "n_new": n_new,
            "data": base64.b64encode(serialize(prompt)).decode(),
        },
        timeout=300,
    )


@pytest.mark.parametrize("p_len, n_new", [(1, 6), (2, 5), (70, 9), (33, 12)])
def test_both_doors_answer_the_plain_generate_s_tokens(node, p_len, n_new):
    server, client, adapter, cfg = node
    weights = adapter.make_weights(3, cfg, "float32")
    prompt = np.random.default_rng(p_len).integers(0, 128, (1, p_len)).astype(np.int32)
    want = _plain_generate(adapter, weights, cfg, prompt, n_new)
    over_ws = client.run_remote_generation("lfm2", prompt, n_new=n_new)
    assert isinstance(over_ws, np.ndarray)
    np.testing.assert_array_equal(over_ws, want)
    resp = _post(server, client, prompt, n_new)
    assert resp.status_code == 200, resp.text
    assert resp.json() == {"success": True, "tokens": want.tolist()}


def test_requests_that_share_the_slots_each_get_their_own_answer(node):
    """Six at once on four slots through both doors: a slot's taps are
    rewritten whole by the prefill of whoever takes it next."""
    server, client, adapter, cfg = node
    weights = adapter.make_weights(3, cfg, "float32")
    prompts = [
        np.random.default_rng(100 + i).integers(0, 128, (1, 5 + 9 * i)).astype(np.int32)
        for i in range(6)
    ]

    def ask(i):
        if i % 2:
            return np.asarray(_post(server, client, prompts[i], 4 + i).json()["tokens"])
        return DataCentricFLClient(server.url).run_remote_generation(
            "lfm2", prompts[i], n_new=4 + i
        )

    with ThreadPoolExecutor(6) as pool:
        answers = list(pool.map(ask, range(6)))
    for i, got in enumerate(answers):
        np.testing.assert_array_equal(
            got, _plain_generate(adapter, weights, cfg, prompts[i], 4 + i)
        )
    row = requests.get(server.url + "/telemetry/serving", timeout=30).json()["engines"][0]
    assert row["state_bytes"] > 0 and row["prefix_hits"] == 0
