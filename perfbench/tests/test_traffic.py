"""The stratified generator: the same multiset of lengths and the same
count per window for every seed; order, pairing, arrivals and contents
from the seed; named fields dealt in proportion; and every mix the
benchmark has making what it made before fields came (PR 32)."""

import collections
import hashlib
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
    assert len(a["requests"]) == grid * tr["cycles"] == 8192
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


# ── what every mix made at PR 31, byte for byte ──────────────────────────

RECORDED = json.loads((spec.BENCH_DIR / "tests/fixtures/traffic.sha256.json").read_text())


def requests_digest(built, first=None):
    """sha256 of a built list's requests as PR 31 knew them (every key but
    ``fields``), of its first ``first`` where the list has grown since."""
    rows = [{k: v for k, v in r.items() if k != "fields"} for r in built["requests"][:first]]
    blob = json.dumps(
        {"requests": rows, "lead_in_s": built["lead_in_s"], "loop": built["loop"]},
        sort_keys=True,
    )
    return hashlib.sha256(blob.encode()).hexdigest()


@pytest.mark.parametrize("seed", [0, 1, 2**31])
@pytest.mark.parametrize("name", sorted(RECORDED["digests"]))
def test_each_mix_makes_what_the_parent_made(name, seed):
    bench = spec.load_benchmark()
    (cell_name,) = [w["name"] for w in bench["workloads"] if w["traffic"] == name]
    cell = spec.cell(bench, cell_name)
    tr = cell["traffic"]
    assert "fields" not in tr, "no mix the benchmark had gains the key"
    if tr["kind"] == "serve":
        built = traffic.build(tr, seed, RECORDED["seconds"])
        assert all(r["fields"] == {} for r in built["requests"])
        # a list that has grown since opens with the parent's requests
        first = RECORDED.get(f"{name}_requests")
        assert first is None or len(built["requests"]) >= first
        got = requests_digest(built, first)
    else:
        from lib import training

        X, y = training.make_batch(cell, seed)
        got = hashlib.sha256(
            np.asarray(X, np.int32).tobytes() + np.asarray(y, np.int32).tobytes()
        ).hexdigest()
    assert got == RECORDED["digests"][name][str(seed)]


def test_a_closed_list_holds_four_times_what_the_ledger_has_seen():
    # the sizing rule of lib/traffic.py. The fastest engines seen: 1,390.4
    # tokens/s in batch-saturate (chip runs of PR 32: the 800 of the ledger's
    # PRs 25-31 was the old list of 1,024 requests running dry), 4,652.8 in
    # reason-saturate (ledger, PR 29, refused); lead-in 10 s + window 51 s
    for name, fastest in (("batch-saturate", 1390.4), ("reason-saturate", 4652.8)):
        tr = mix(name)
        tokens = sum(r["n_new"] for r in traffic.build(tr, 0, 51.0)["requests"])
        assert tokens / (fastest * (tr["lead_in_s"] + 51.0)) >= 3.99, (name, tokens)


# ── named fields ─────────────────────────────────────────────────────────


def test_weighted_fields_are_dealt_in_proportion_in_every_group():
    steps = {"values": [1, 2, 4], "weights": [1, 2, 1]}
    tr = dict(mix("batch-saturate"), cycles=3, fields={"steps": steps, "mode": "block"})
    plain = traffic.build(dict(mix("batch-saturate"), cycles=3), 9, 51.0)
    a, b = traffic.build(tr, 9, 51.0), traffic.build(tr, 10, 51.0)
    # the last draw: everything else is what the mix without fields makes
    assert requests_digest(a) == requests_digest(plain)
    grid = tr["grid"]
    for run in (a, b):
        assert all(r["fields"]["mode"] == "block" for r in run["requests"])
        for c in range(tr["cycles"]):
            dealt = collections.Counter(
                r["fields"]["steps"] for r in run["requests"][c * grid:(c + 1) * grid]
            )
            assert dealt == {1: 32, 2: 64, 4: 32}
    order = lambda run: [r["fields"]["steps"] for r in run["requests"]]  # noqa: E731
    assert order(a) != order(b) and order(a)[:grid] != order(a)[grid:2 * grid]
    assert a == traffic.build(tr, 9, 51.0)
    # an open loop deals within the lead-in and within the window
    tr = dict(mix("chat-steady"), fields={"steps": {"values": [1, 4], "weights": [3, 1]}})
    run = traffic.build(tr, 3, 51.0)
    assert requests_digest(run) == requests_digest(traffic.build(mix("chat-steady"), 3, 51.0))
    for flag in (False, True):
        got = collections.Counter(
            r["fields"]["steps"] for r in run["requests"] if r["counted"] is flag
        )
        n = sum(got.values())
        assert abs(got[1] - 0.75 * n) <= 1 and got[1] + got[4] == n
    # counts that do not divide: the remainder goes to the largest fractions
    rng = np.random.default_rng(0)
    assert collections.Counter(traffic.deal(["a", "b", "c"], [1, 1, 1], 8, rng)) == {
        "a": 3, "b": 3, "c": 2,
    }
    with pytest.raises(ValueError, match="do not pair"):
        traffic.deal([1, 2], [1], 4, rng)
