"""A block-diffusion bundle behind both doors of one node: ``run-generation``
takes the request's ``denoising_steps`` and answers ``reveal_step`` (and the
last block's dropped tail) beside ``tokens``, over WS through the client
SDK and over HTTP; a defect in the field bounces typed from both, with one
message; a causal bundle on the same node answers as it always has."""

from __future__ import annotations

import base64
import json
import sys
from pathlib import Path

import numpy as np
import pytest
import requests

import jax

from pygrid_tpu.client import DataCentricFLClient
from pygrid_tpu.models import decode, sdar_moe
from pygrid_tpu.models import transformer as T
from pygrid_tpu.serde import serialize
from pygrid_tpu.utils.exceptions import PyGridError

from .conftest import ServerThread, _free_port

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "perfbench") not in sys.path:
    sys.path.append(str(ROOT / "perfbench"))
from lib import spec  # noqa: E402

CAUSAL = T.TransformerConfig(vocab=37, d_model=16, n_heads=2, n_layers=1, d_ff=32, max_len=48)
FIELDS = ("tokens", "reveal_step", "dropped_tokens", "dropped_reveal_step")


@pytest.fixture(scope="module")
def node():
    from pygrid_tpu.node import create_app

    mp = pytest.MonkeyPatch()
    mp.setenv("PYGRID_KV_BLOCK", "16")
    mp.setenv("PYGRID_SERVING_SLOTS", "4")
    adapter = spec.load_model("sdar_moe")
    cfg = json.loads((ROOT / "perfbench/configs/sdar-30b-a3b-chat.json").read_text())
    cfg.update(adapter.tiny(cfg))
    scfg = adapter.sdar_config(cfg)
    params = adapter.make_program_params(3, cfg, "float32")
    server = ServerThread(create_app("blockdiff-doors"), _free_port()).start()
    client = DataCentricFLClient(server.url)
    try:
        for model_id, bundle in (
            ("blocks", sdar_moe.bundle(scfg, params)),
            ("causal", decode.bundle(CAUSAL, T.init(jax.random.PRNGKey(1), CAUSAL))),
        ):
            out = client.serve_model(bundle, model_id, allow_remote_inference=True)
            assert out.get("success"), out
        yield server, client, adapter, cfg
    finally:
        client.close()
        server.stop()
        mp.undo()


def _post(server, client, model_id, prompt, **fields):
    return requests.post(
        server.url + "/data-centric/run-generation",
        headers={"token": client._auth_token},
        json={
            "model_id": model_id,
            "data": base64.b64encode(serialize(prompt)).decode(), **fields,
        },
        timeout=120,
    )


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_both_doors_answer_the_plain_generate_with_its_reveal_steps(node, steps):
    server, client, adapter, cfg = node
    prompt = np.random.default_rng(steps).integers(0, 128, (1, 11)).astype(np.int32)
    want = adapter.generate(
        adapter.make_weights(3, cfg, "float32"), cfg, prompt, 10, steps, pad_to=32
    )
    over_ws = client.run_remote_generation("blocks", prompt, n_new=10, denoising_steps=steps)
    assert set(over_ws) == set(FIELDS)
    assert {k: np.asarray(v).tolist() for k, v in over_ws.items()} == want
    resp = _post(server, client, "blocks", prompt, n_new=10, denoising_steps=steps)
    assert resp.status_code == 200, resp.text
    body = resp.json()
    assert body.pop("success") is True and body == want
    assert len(want["dropped_tokens"][0]) == 3  # 11 + 10 ends a position into its block


def test_without_the_field_a_block_takes_its_length_in_forwards(node):
    server, client, adapter, cfg = node
    prompt = np.full((1, 8), 5, np.int32)
    out = client.run_remote_generation("blocks", prompt, n_new=8)
    assert sorted(out["reveal_step"][0].tolist()) == [0, 0, 1, 1, 2, 2, 3, 3]
    assert out["dropped_tokens"].shape == (1, 0)


def _scraped(server, family):
    """``{kind: value}`` of one counter family on the node's ``/metrics``."""
    from pygrid_tpu.telemetry import promtext

    found = promtext.parse(requests.get(server.url + "/metrics", timeout=30).text)
    if family not in found:
        return {}
    return {labels["kind"]: value for _, labels, value in found[family].samples}


def test_the_node_counts_a_commit_s_positions_and_no_forward_for_it(node):
    """Two blocks at two forwards each: four row-forwards, every one a
    denoising forward; the first block's commit rode in the second block's
    first forward, four positions beside that forward's own four."""
    server, client, _, _ = node
    forwards = "pygrid_serving_block_forwards_total"
    positions = "pygrid_serving_block_positions_total"
    before = _scraped(server, forwards), _scraped(server, positions)
    client.run_remote_generation(
        "blocks", np.full((1, 8), 9, np.int32), n_new=8, denoising_steps=2
    )
    after = _scraped(server, forwards), _scraped(server, positions)
    gained = [
        {k: v - was.get(k, 0.0) for k, v in now.items()} for was, now in zip(before, after)
    ]
    assert gained == [{"denoise": 4.0}, {"denoise": 16.0, "commit": 4.0}]


@pytest.mark.parametrize("bad, says", [
    ({"denoising_steps": 3}, "must divide the block length (4)"),
    ({"denoising_steps": 0}, "must divide the block length (4)"),
    ({"denoising_steps": "2"}, "denoising_steps must be a JSON integer"),
    ({"denoising_steps": True}, "denoising_steps must be a JSON integer"),
    ({"denoising_steps": 1.5}, "denoising_steps must be a JSON integer"),
    ({"temperature": 0.7}, "temperature must be 0"),
])
def test_a_defect_in_the_field_bounces_typed_from_both_doors(node, bad, says):
    server, client, _, _ = node
    prompt = np.full((1, 6), 3, np.int32)
    resp = _post(server, client, "blocks", prompt, n_new=4, **bad)
    assert resp.status_code == 400 and says in resp.json()["error"], resp.text
    out = client.ws.send_json(
        "run-generation", model_id="blocks", n_new=4,
        data=base64.b64encode(serialize(prompt)).decode(), **bad,
    )
    assert out.get("success") is False and says in out["error"]
    if "temperature" not in bad:
        with pytest.raises(PyGridError, match="denoising_steps"):
            client.run_remote_generation("blocks", prompt, n_new=4, **bad)


def test_a_causal_family_answers_as_it_always_has(node):
    """It reads no such field: the tokens alone, as an array, whatever
    ``denoising_steps`` says."""
    server, client, _, _ = node
    prompt = np.array([[1, 2, 3]], np.int32)
    plain = client.run_remote_generation("causal", prompt, n_new=5)
    odd = client.run_remote_generation("causal", prompt, n_new=5, denoising_steps=3)
    assert isinstance(odd, np.ndarray) and odd.shape == (1, 5)
    np.testing.assert_array_equal(plain, odd)
    body = _post(server, client, "causal", prompt, n_new=5, denoising_steps="x").json()
    assert body == {"success": True, "tokens": plain.tolist()}
