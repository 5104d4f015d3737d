"""The comparison that decides a serving cell's ``correct`` is the
architecture's: ``check_served`` samples, checks an answer's shape and
counts, and reaches the mathematics only through ``model.served_gaps``.
A toy that is not causal (``noncausal_toy.py``) is judged by the order its
answers name; the two adapters the benchmark has read what the shifted,
causal comparison reads, to the last bit."""

import inspect
import types

import numpy as np
import pytest

import noncausal_toy as toy
from lib import reference, serving, spec, traffic
from tiny import tiny_cell

TOY_CELL = {
    "config": {
        "vocab_size": 64, "width": 16, "deployment": {"weights_dtype": "float32"},
    },
    "traffic": {
        "kind": "serve", "loop": "closed", "callers": 2, "grid": 8, "cycles": 1,
        "prompt_len": {"dist": "uniform", "lo": 3, "hi": 9},
        "n_new": {"dist": "uniform", "lo": 5, "hi": 13},
        "doors": {"ws": 1}, "lead_in_s": 0,
        "fields": {"block": {"values": [2, 4], "weights": [1, 1]}},
        "check": {"sample": 6, "gap_max_limit": 1e-4, "gap_mean_limit": 1e-5},
    },
}
SEED = 2**31 + 5


def toy_results():
    """Every request of the toy mix, answered as a door would answer."""
    cfg, tr = TOY_CELL["config"], TOY_CELL["traffic"]
    w = toy.make_weights(SEED, cfg, "float32")
    rows = []
    for r in traffic.build(tr, SEED, 1.0)["requests"]:
        prompt = traffic.prompt_tokens(tr, SEED, r, cfg["vocab_size"])
        answer = toy.generate(w, cfg, prompt, r["n_new"], r["fields"]["block"])
        rows.append({
            "i": r["i"], "prompt_len": r["prompt_len"], "n_new": r["n_new"],
            "counted": None, "ok": True, "fields": r["fields"],
            "tokens": answer.pop("tokens"), "answer": answer,
        })
    return rows


def test_a_non_causal_adapter_is_judged_by_the_order_its_answers_name():
    rows = toy_results()
    assert {r["fields"]["block"] for r in rows} == {2, 4}
    assert any(max(r["answer"]["reveal_step"][0]) == 3 for r in rows)
    sound = serving.check_served(TOY_CELL, toy, SEED, rows, control=True)
    assert sound["correct"] is True, sound
    by_name = {c["name"]: c["value"] for c in sound["compared"]}
    assert by_name == {"served_gap_max": 0.0, "served_gap_mean": 0.0, "malformed_answers": 0.0}
    assert sound["sampled_requests"] == 6 and sound["sampled_tokens"] >= 6 * 5
    # the int8 control, read in the same states, puts other tokens first
    assert sound["control"]["served_gap_max"] > TOY_CELL["traffic"]["check"]["gap_max_limit"]

    # the same tokens under another order: the states they are held against
    # are not the ones they were chosen from
    shuffled = [
        dict(r, answer={"reveal_step": [[max(s) - v for v in s] for s in r["answer"]["reveal_step"]]})
        for r in rows
    ]
    wrong = serving.check_served(TOY_CELL, toy, SEED, shuffled, control=False)
    assert wrong["correct"] is False
    over = {c["name"] for c in wrong["compared"] if c["value"] > c["limit"]}
    assert over == {"served_gap_max", "served_gap_mean"}

    # answers without the field (an empty mapping, no mapping at all) and
    # one whose field has another length: malformed answers, counted, and
    # the run goes on to judge the rest
    bare = [
        dict(rows[0], answer={}),
        {k: v for k, v in rows[1].items() if k != "answer"},
        dict(rows[2], answer={"reveal_step": [[0]]}),
        *rows[3:],
    ]
    every = dict(TOY_CELL["traffic"]["check"], sample=len(bare))
    cell = dict(TOY_CELL, traffic=dict(TOY_CELL["traffic"], check=every))
    got = serving.check_served(cell, toy, SEED, bare, control=False)
    by_name = {c["name"]: c["value"] for c in got["compared"]}
    assert by_name["malformed_answers"] == 3.0 and got["correct"] is False
    assert by_name["served_gap_max"] == 0.0  # the rest was judged, and is sound
    assert got["sampled_tokens"] == sum(r["n_new"] for r in rows[3:])
    assert sorted(m.split(": ")[1] for m in got["malformed"]) == [
        "KeyError", "KeyError", "ValueError",
    ]


def test_check_served_reaches_the_mathematics_through_the_adapter_alone():
    text = inspect.getsource(serving.check_served)
    code = "\n".join(line.split("#")[0] for line in text.split('"""')[2].splitlines())
    for word in ("logits", "reference.", "p_len", "[:-1]", "argmax", "hasattr", "getattr"):
        assert word not in code, word
    assert code.count("model.served_gaps(") == 1
    assert "reference" not in vars(serving), "lib/serving.py imports no comparison of its own"


@pytest.mark.parametrize("name", ["chat-steady", "reason-saturate"])
def test_the_causal_adapters_read_what_the_direct_call_reads(name):
    cell = tiny_cell(name)
    cfg, tr = cell["config"], cell["traffic"]
    model = spec.load_model(cfg["model_type"])
    seed, rng = 2**31 + 9, np.random.default_rng(9)
    rows = []
    for r in traffic.build(tr, seed, 2.0)["requests"][:5]:
        served = rng.integers(0, cfg["vocab_size"], (1, r["n_new"]))
        rows.append({**r, "counted": True, "ok": True, "tokens": served.tolist()})
    direct, direct_control = [], []

    def spy(w, cfg, request, answer, pad_to, control=None):
        got = model.served_gaps(w, cfg, request, answer, pad_to, control)
        want = reference.served_gaps(
            model, w, cfg, request["prompt"], answer["tokens"], pad_to, control
        )
        assert set(answer) == {"tokens"} and request["fields"] == {}
        assert request["prompt"].dtype == np.int32 and answer["tokens"].dtype == np.int64
        for g, d in zip(got, want):
            assert np.array_equal(g, d)
        direct.append(want[0])
        direct_control.append(want[1])
        return got

    spied = types.SimpleNamespace(**{**vars(model), "served_gaps": spy})
    out = serving.check_served(cell, spied, seed, rows, control=True)
    assert out["sampled_requests"] == len(direct) == 5
    by_name = {c["name"]: c["value"] for c in out["compared"]}
    flat, cflat = np.concatenate(direct), np.concatenate(direct_control)
    assert by_name["served_gap_max"] == float(flat.max())
    assert by_name["served_gap_mean"] == float(flat.mean())
    assert by_name["malformed_answers"] == 0.0
    assert out["control"] == {
        "served_gap_max": float(cflat.max()), "served_gap_mean": float(cflat.mean()),
    }
    # random tokens are not what the reference would have served
    assert out["correct"] is False and flat.max() > 0
    # a token out of range, a row too short: counted, never handed over
    rows[0]["tokens"][0][0] = cfg["vocab_size"]
    rows[1]["tokens"][0].pop()
    seen = len(direct)
    out = serving.check_served(cell, spied, seed, rows, control=False)
    assert {c["name"]: c["value"] for c in out["compared"]}["malformed_answers"] == 2.0
    assert len(direct) - seen == 3
