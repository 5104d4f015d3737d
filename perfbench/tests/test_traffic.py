"""The stratified generator: the same multiset of lengths and the same
count per window for every seed; order, pairing, arrivals and contents
from the seed."""

import json

import numpy as np
import pytest

from lib import spec, traffic


def mix(name):
    return json.loads((spec.BENCH_DIR / "traffic" / f"{name}.json").read_text())


SEEDS = (0, 1, 7, 2**31 + 12345, 3_000_000_019)


def test_open_loop_same_work_every_seed():
    tr, seconds = mix("chat-steady"), float(spec.load_benchmark()["run_seconds"])
    runs = [traffic.build(tr, s, seconds) for s in SEEDS]
    counted = [[r for r in run["requests"] if r["counted"]] for run in runs]
    n = len(counted[0])
    assert n == int(seconds * tr["rate_per_s"])
    first = sorted((r["n_new"] for r in counted[0]))
    assert len(set(first)) >= 32, "fewer than 32 distinct n_new values"
    assert min(first) >= 16 and max(first) <= 128
    for run, rows in zip(runs, counted):
        assert len(rows) == n
        assert sorted(r["n_new"] for r in rows) == first
        assert sorted(r["prompt_len"] for r in rows) == sorted(
            r["prompt_len"] for r in counted[0]
        )
        lead = run["lead_in_s"]
        # request k is due inside slot k; the window opens on a slot boundary
        for k, r in enumerate(run["requests"]):
            assert k / tr["rate_per_s"] <= r["due"] < (k + 1) / tr["rate_per_s"]
        assert all(lead <= r["due"] < lead + seconds for r in rows)
        assert all(r["due"] < lead for r in run["requests"] if not r["counted"])
    orders = {tuple(r["n_new"] for r in rows) for rows in counted}
    assert len(orders) == len(SEEDS), "seeds did not reorder the lengths"
    dues = {tuple(round(r["due"], 6) for r in rows) for rows in counted}
    assert len(dues) == len(SEEDS)
    doors = [r["door"] for r in runs[0]["requests"]]
    assert doors.count("http") * 3 == pytest.approx(doors.count("ws"), abs=3)


def test_same_seed_same_requests_and_tokens():
    tr = mix("chat-steady")
    a, b = traffic.build(tr, 99, 30.0), traffic.build(tr, 99, 30.0)
    assert a == b
    r = a["requests"][5]
    ta = traffic.prompt_tokens(tr, 99, r, 50257)
    assert ta.shape == (1, r["prompt_len"]) and ta.dtype == np.int32
    assert np.array_equal(ta, traffic.prompt_tokens(tr, 99, r, 50257))
    assert not np.array_equal(ta, traffic.prompt_tokens(tr, 100, r, 50257))
    assert 0 <= ta.min() and ta.max() < 50257


def test_closed_loop_cycles_whole_grids():
    tr = mix("batch-saturate")
    a, b = traffic.build(tr, 1, 45.0), traffic.build(tr, 2, 45.0)
    grid = tr["grid"]
    assert len(a["requests"]) == grid * tr["cycles"]
    assert all(r["due"] is None for r in a["requests"])
    for run in (a, b):
        for c in range(tr["cycles"]):
            chunk = run["requests"][c * grid:(c + 1) * grid]
            assert sorted(r["n_new"] for r in chunk) == sorted(
                r["n_new"] for r in a["requests"][:grid]
            )
    assert [r["n_new"] for r in a["requests"]] != [r["n_new"] for r in b["requests"]]


def test_free_arrivals_and_shared_prefixes_are_data_only():
    tr = dict(mix("chat-steady"), arrivals="poisson",
              shared_prefix={"tokens": 64, "prompts": 4, "zipf": 1.0})
    run = traffic.build(tr, 5, 40.0)
    due = [r["due"] for r in run["requests"]]
    assert due == sorted(due) and due[-1] < run["lead_in_s"] + 40.0
    counted = sum(r["counted"] for r in run["requests"])
    assert 0.5 * 40 * tr["rate_per_s"] < counted < 1.5 * 40 * tr["rate_per_s"]
    same = [r for r in run["requests"] if r["prefix"] == 0][:2]
    t0, t1 = (traffic.prompt_tokens(tr, 5, r, 1000) for r in same)
    assert np.array_equal(t0[0, :64], t1[0, :64]) and t0.shape[1] == same[0]["prompt_len"] + 64
    gam = traffic.build(dict(mix("chat-steady"), arrivals="gamma", arrival_cv=3.0), 5, 40.0)
    gaps = np.diff([r["due"] for r in gam["requests"]])
    assert gaps.std() / gaps.mean() > 1.5
