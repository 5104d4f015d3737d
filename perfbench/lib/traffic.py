"""The one general traffic generator: a mix is a JSON file of parameters.

Serving mixes (``"kind": "serve"``) give a loop kind, a rate or a number
of callers, length distributions, doors and sharing; this module turns
one into a list of requests from ``--seed``. What a seed changes is the
token contents, the pairing and order of the lengths, the arrival
offsets and (elsewhere) the weights. What it never changes is the
multiset of lengths or the number of requests due in a window: every run
of a cell is given the same work in another order.

A mix may give its requests named fields, ``"fields": {name: value}``,
which go out with each request as they are named (a keyword of the WS
client's call, a key of the HTTP body) and come back to the adapter's
comparison: a constant, or ``{"values": [...], "weights": [...]}`` dealt
in proportion (``deal``). They are drawn after every other draw and only
where the key is present, so a mix without it is the mix it was.

Arrivals are pre-drawn before anything is sent, as
``pygrid_tpu/storm/loadgen.py`` ``arrival_times`` does (exponential gaps
from a seeded generator, open loop: an arrival never waits for a
completion); that arithmetic is copied here as ``"poisson"``, with
``"gamma"`` gaps for bursts and ``"jittered_slots"`` for a schedule whose
count per window is fixed.
"""

from __future__ import annotations

import math

import numpy as np


def quantile_grid(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles ``(j + 0.5) / n`` of ``dist``:
    the same multiset for every seed. ``log_uniform`` and ``uniform`` over
    ``[lo, hi]``, or ``fixed``."""
    if n <= 0:
        return np.zeros(0, np.int64)
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        out = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        out = dist["lo"] + (dist["hi"] - dist["lo"]) * q
    elif kind == "log_uniform":
        out = dist["lo"] * (dist["hi"] / dist["lo"]) ** q
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.rint(out).astype(np.int64)


def arrival_offsets(traffic: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` arrival offsets in seconds from the start of sending, at the
    mix's mean rate."""
    rate = float(traffic["rate_per_s"])
    kind = traffic.get("arrivals", "jittered_slots")
    if kind == "jittered_slots":
        # request k is due somewhere inside slot k of a regular schedule
        return (np.arange(n) + rng.random(n)) / rate
    if kind == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate, n))
    if kind == "gamma":
        # inter-arrival gaps of mean 1/rate and coefficient of variation cv
        cv = float(traffic["arrival_cv"])
        shape = 1.0 / (cv * cv)
        return np.cumsum(rng.gamma(shape, 1.0 / (rate * shape), n))
    raise ValueError(f"unknown arrival process {kind!r}")


def deal(values: list, weights: list, n: int, rng: np.random.Generator) -> list:
    """``n`` of ``values`` in proportion to ``weights``, the same counts
    for every seed (the remainder goes to the largest fractions, the
    earlier value first), in an order the seed gives."""
    if len(values) != len(weights) or not values or min(weights) < 0 or sum(weights) <= 0:
        raise ValueError(f"field values {values!r} and weights {weights!r} do not pair")
    exact = n * np.asarray(weights, float) / float(sum(weights))
    counts = np.floor(exact).astype(int)
    for j in np.argsort(-(exact - counts), kind="stable")[: n - int(counts.sum())]:
        counts[j] += 1
    dealt = [v for v, c in zip(values, counts) for _ in range(c)]
    return [dealt[j] for j in rng.permutation(n)]


def _fields(traffic: dict, groups: list, rng: np.random.Generator) -> list[dict]:
    """Each request's named fields: a weighted value dealt anew within
    every group (a closed loop's grid, an open loop's lead-in and
    window), so that any stretch of the list holds the mix's shares."""
    total = sum(n for n, _ in groups)
    out: list[dict] = [{} for _ in range(total)]
    for name, value in sorted(traffic.get("fields", {}).items()):
        if isinstance(value, dict):
            column = [
                v for n, _ in groups
                for v in deal(value["values"], value["weights"], n, rng)
            ]
        else:
            column = [value] * total
        for fields, v in zip(out, column):
            fields[name] = v
    return out


def _doors(traffic: dict, n: int) -> list[str]:
    """Doors in a fixed rotation by their weights: ``{"ws": 3, "http": 1}``
    sends every fourth request through HTTP."""
    by_weight = sorted(traffic["doors"].items(), key=lambda kv: (-kv[1], kv[0]))
    cycle = [door for door, weight in by_weight for _ in range(int(weight))]
    return [cycle[i % len(cycle)] for i in range(n)]


def window_counts(traffic: dict, seconds: float) -> tuple[int, int, float]:
    """Open loop: (requests of the lead-in, requests due in the window,
    the lead-in's length in seconds). The lead-in is a whole number of
    slots, so the window opens on a slot boundary and holds
    ``floor(seconds * rate)`` whole slots for every seed."""
    rate = float(traffic["rate_per_s"])
    n_lead = max(1, round(float(traffic["lead_in_s"]) * rate))
    n_win = int(math.floor(seconds * rate + 1e-9))
    return n_lead, n_win, n_lead / rate


def build(traffic: dict, seed: int, seconds: float) -> dict:
    """The requests of one run: ``{"requests": [...], "lead_in_s",
    "loop"}``. Each request has ``i``, ``prompt_len``, ``n_new``, ``door``,
    ``due`` (seconds from the start of sending; None in a closed loop),
    ``counted`` (due inside the window), ``prefix`` (index of the shared
    prefix it opens with, or None) and ``fields`` (the mix's named fields
    of this request; empty where the mix names none)."""
    rng = np.random.default_rng([int(seed), 0x7A11])
    loop = traffic["loop"]
    if loop == "open":
        n_lead, n_win, lead_s = window_counts(traffic, seconds)
        if traffic.get("arrivals", "jittered_slots") == "jittered_slots":
            due = arrival_offsets(traffic, rng, n_lead + n_win)
        else:
            # free arrivals: draw past the window's end and keep what falls
            # before it; the count then varies from seed to seed
            draw = arrival_offsets(
                traffic, rng, int(2 * (n_lead + n_win)) + 16
            )
            due = draw[draw < lead_s + seconds]
            n_win = int((due >= lead_s).sum())
            n_lead = len(due) - n_win
        groups = [(n_lead, False), (n_win, True)]
    elif loop == "closed":
        # more than any run can finish: whole grids, one permutation each,
        # drawn grid after grid from one stream (a longer list opens with
        # the shorter one's requests). A caller that finds the list spent
        # before the window closes fails the run (``lib/serving.py``), so
        # size ``cycles`` to hold at least four times what the fastest
        # engine the ledger has seen would finish in lead-in plus window
        grid = int(traffic["grid"])
        cycles = int(traffic.get("cycles", 8))
        groups = [(grid, None)] * cycles
        lead_s = float(traffic["lead_in_s"])
        due = None
    else:
        raise ValueError(f"unknown loop kind {loop!r}")
    prompt_len, n_new, counted = [], [], []
    for n, flag in groups:
        prompt_len.append(rng.permutation(quantile_grid(traffic["prompt_len"], n)))
        n_new.append(rng.permutation(quantile_grid(traffic["n_new"], n)))
        counted.extend([flag] * n)
    prompt_len = np.concatenate(prompt_len)
    n_new = np.concatenate(n_new)
    total = len(prompt_len)
    doors = _doors(traffic, total)
    share = traffic.get("shared_prefix")
    prefixes = [None] * total
    if share:
        # Zipf-skewed choice among a few shared prefixes
        ranks = np.arange(1, int(share["prompts"]) + 1, dtype=float)
        p = ranks ** -float(share.get("zipf", 1.0))
        prefixes = rng.choice(len(ranks), size=total, p=p / p.sum()).tolist()
    fields = _fields(traffic, groups, rng)  # the last draw: see the docstring
    requests = [
        {
            "i": i,
            "prompt_len": int(prompt_len[i]),
            "n_new": int(n_new[i]),
            "door": doors[i],
            "due": None if due is None else float(due[i]),
            "counted": counted[i],
            "prefix": prefixes[i],
            "fields": fields[i],
        }
        for i in range(total)
    ]
    return {"requests": requests, "lead_in_s": lead_s, "loop": loop}


def prompt_tokens(traffic: dict, seed: int, request: dict, vocab: int) -> np.ndarray:
    """The request's prompt, ``int32 [1, prompt_len]``: distinct contents
    for every request and seed; a shared prefix, where the mix has one,
    opens it."""
    rng = np.random.default_rng([int(seed), 0x70C5, request["i"]])
    tokens = rng.integers(0, vocab, size=request["prompt_len"], dtype=np.int64)
    share = traffic.get("shared_prefix")
    if share and request["prefix"] is not None:
        pre = np.random.default_rng(
            [int(seed), 0x5BA8ED, request["prefix"]]
        ).integers(0, vocab, size=int(share["tokens"]), dtype=np.int64)
        tokens = np.concatenate([pre, tokens])
    return tokens[None, :].astype(np.int32)
