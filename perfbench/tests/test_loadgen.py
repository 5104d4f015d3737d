"""The load generator's two ends, with the node faked: a request's named
fields go out through either door as they are named, whatever an answer
holds beside its tokens is kept, and a closed loop says whether a caller
found its list spent before the window closed."""

import json
import time

import numpy as np

from lib import loadgen, traffic


class FakeWS:
    def __init__(self, answer):
        self.answer, self.calls = answer, []

    def run_remote_generation(self, model_id, prompt, **kw):
        self.calls.append((model_id, kw))
        return self.answer


class FakeHTTP:
    def __init__(self, body):
        self.body, self.calls = body, []

    def post(self, url, headers, json, timeout):  # noqa: A002 — requests' own name
        self.calls.append((url, json))
        return type("R", (), {"status_code": 200, "json": lambda _s: dict(self.body)})()


def door(kind, client):
    d = object.__new__(loadgen.Door)
    d.kind, d.url, d.model_id, d.timeout, d.token, d.client = kind, "u", "m", 1.0, "t", client
    return d


def test_fields_go_out_and_answers_come_back_through_either_door():
    prompt = np.ones((1, 4), np.int32)
    ws = FakeWS(np.array([[5, 6]]))
    assert door("ws", ws).send(prompt, 2) == {"tokens": [[5, 6]]}
    assert ws.calls == [("m", {"n_new": 2})]  # a mix without fields sends what it sent
    ws = FakeWS({"tokens": np.array([[5, 6]]), "reveal_step": np.array([[1, 0]]), "forwards": 3})
    got = door("ws", ws).send(prompt, 2, steps=4)
    assert got == {"tokens": [[5, 6]], "reveal_step": [[1, 0]], "forwards": 3}
    assert ws.calls == [("m", {"n_new": 2, "steps": 4})]
    json.dumps(got)

    http = FakeHTTP({"success": True, "tokens": [[7, 8]]})
    assert door("http", http).send(prompt, 2) == {"tokens": [[7, 8]]}
    (_, body), = http.calls
    assert set(body) == {"model_id", "data", "n_new", "temperature"}
    http = FakeHTTP({"success": True, "tokens": [[7, 8]], "reveal_step": [[0, 1]]})
    assert door("http", http).send(prompt, 2, steps=4) == {
        "tokens": [[7, 8]], "reveal_step": [[0, 1]],
    }
    assert http.calls[0][1]["steps"] == 4 and http.calls[0][1]["n_new"] == 2

    # the row: tokens where they were, the rest under ``answer``, the fields kept
    req = {"i": 0, "prompt_len": 4, "n_new": 2, "due": None, "counted": None,
           "fields": {"steps": 4}}
    rows: list = []
    loadgen._serve(door("http", http), req, {0: prompt}, time.time(), rows)
    loadgen._serve(door("ws", FakeWS(np.array([[5, 6]]))), dict(req, fields={}),
                   {0: prompt}, time.time(), rows)
    assert rows[0]["tokens"] == [[7, 8]] and rows[0]["answer"] == {"reveal_step": [[0, 1]]}
    assert rows[0]["fields"] == {"steps": 4} and rows[0]["ok"] is True
    assert rows[1]["tokens"] == [[5, 6]] and "answer" not in rows[1] and rows[1]["fields"] == {}


class SlowWS(FakeWS):
    def run_remote_generation(self, model_id, prompt, **kw):
        time.sleep(0.02)
        return np.zeros((1, kw["n_new"]), np.int64)


def test_a_closed_loop_says_whether_its_list_was_spent():
    tr = {"loop": "closed", "grid": 4, "cycles": 2, "lead_in_s": 0.0, "drain_s": 5.0,
          "prompt_len": {"dist": "fixed", "value": 4}, "n_new": {"dist": "fixed", "value": 2},
          "doors": {"ws": 1}}
    built = traffic.build(tr, 1, 0.5)
    prompts = {r["i"]: np.ones((1, 4), np.int32) for r in built["requests"]}
    doors = {"ws": [door("ws", SlowWS(None)) for _ in range(2)]}
    plan = {"seconds": 0.5, "traffic": tr}
    rows, held = loadgen.run_closed(plan, doors, built, prompts, time.time())
    assert held == {"spent": True, "list_used": 1.0} and len(rows) == 8
    # a list longer than the window: the window closes first
    built = traffic.build(dict(tr, cycles=50), 1, 0.5)
    prompts = {r["i"]: np.ones((1, 4), np.int32) for r in built["requests"]}
    rows, held = loadgen.run_closed(plan, doors, built, prompts, time.time())
    assert held["spent"] is False and 0 < held["list_used"] < 0.5
    assert held["list_used"] == len(rows) / 200
    rows, held = loadgen.run_open(
        dict(plan, traffic=dict(tr, drain_s=1.0)), {"ws": []}, {"requests": []}, {}, time.time()
    )
    assert (rows, held) == ([], {})
