"""The host account's readers (``lib/hostclock.py`` and the four metrics over
it) on two hand-written scrapes whose answers can be worked out on paper, and
``BENCHMARK.json`` held against the form's limits, string by string."""

import json

import pytest

from lib import spec

P = "pygrid_"
LADDER = (0.0001, 0.0025, 0.005, 0.1, 0.25, 0.5, 1.0, float("inf"))


def _hist(out, name, labels, observed, total):
    """One series as ``/metrics`` renders it: ``observed`` maps a bucket's
    upper bound to the observations that fell into it."""
    running = 0
    for le in LADDER:
        running += observed.get(le, 0)
        bound = "+Inf" if le == float("inf") else f"{le:g}"
        out[f'{P}{name}_bucket{{{labels},le="{bound}"}}'] = float(running)
    out[f"{P}{name}_sum{{{labels}}}"] = float(total)
    out[f"{P}{name}_count{{{labels}}}"] = float(running)


def _snap(wall, cpu, instances, pauses):
    """A scrape of ``/metrics`` as ``lib/serving.scrape`` keeps it."""
    out = {"engine": {"kv_blocks_free": 1}}
    for phase, seconds in wall.items():
        out[f'{P}serving_loop_seconds_total{{phase="{phase}"}}'] = float(seconds)
    for phase, seconds in cpu.items():
        out[f'{P}serving_loop_cpu_seconds_total{{phase="{phase}"}}'] = float(seconds)
    for phase, observed in instances.items():
        _hist(out, "serving_loop_phase_seconds", f'phase="{phase}"', observed, wall[phase])
    for generation, (observed, total) in pauses.items():
        _hist(out, "process_gc_seconds", f'generation="{generation}"', observed, total)
    # neighbours that must not be picked up
    out[f'{P}serving_dispatch_seconds_bucket{{path="step",width="16",le="+Inf"}}'] = 9e6
    out[f'{P}serving_tokens_total{{model="bench"}}'] = 1e6
    return out


#: over a 50 s window. Wall: admit 2, build 6, emit 2, fetch 30, prefill 1 (the
#: CPU seconds beside them are a series no reader here takes up). Instances:
#: 1,000 builds under 2.5 ms and ONE in (100, 250] ms (the stall); a build in
#: (250, 500] lies BEFORE the window and a fetch in (500, 1000] inside it is a
#: wait, not work. The collector: 5,000 young collections, none over 0.1 ms;
#: 10 of generation 1; 2 full ones, the longer in (5, 100] ms, and one in
#: (100, 250] before the window. ``emit`` has no series before the window.
W0 = _snap(
    {"admit": 1.0, "build": 3.0, "fetch": 20.0, "prefill": 1.0, "idle": 9.0},
    {"admit": 0.5, "build": 2.5, "fetch": 0.5, "prefill": 0.9, "idle": 0.1},
    {"admit": {0.005: 20}, "build": {0.0025: 500, 0.5: 1}, "fetch": {0.1: 300}},
    {0: ({0.0001: 1000}, 0.05), 1: ({0.0025: 4}, 0.04), 2: ({0.25: 1}, 0.2)},
)
W1 = _snap(
    {"admit": 3.0, "build": 9.0, "emit": 2.0, "fetch": 50.0, "prefill": 2.0, "idle": 9.0},
    {"admit": 1.5, "build": 8.0, "emit": 1.5, "fetch": 0.9, "prefill": 1.8, "idle": 0.1},
    {
        "admit": {0.005: 60}, "build": {0.0025: 1500, 0.25: 1, 0.5: 1},
        "emit": {0.0025: 1000}, "fetch": {0.1: 1300, 1.0: 1},
    },
    {
        0: ({0.0001: 6000}, 0.30), 1: ({0.0025: 14}, 0.14),
        2: ({0.005: 1, 0.1: 1, 0.25: 1}, 0.35),
    },
)
SERVE = {"kind": "serve", "seconds": 50.0, "snap_w0": W0, "snap_w1": W1}

EXPECTED = {"engine_longest_phase_ms": 250.0, "gc_longest_ms": 100.0}


def _new_metrics():
    return [
        m for m in spec.load_benchmark()["per_layer"]
        if m["name"].rsplit(".", 1)[0] in EXPECTED
    ]


def test_the_four_entries_are_there_at_the_end_and_the_rules_hold():
    bench = spec.load_benchmark()
    assert spec.check_rules(bench) == []
    new = _new_metrics()
    assert len(new) == 4
    first = bench["per_layer"].index(new[0])
    assert bench["per_layer"][first : first + 4] == new  # added in one block
    serving = [
        w["name"] for w in bench["workloads"]
        if spec.cell(bench, w["name"])["traffic"]["kind"] == "serve"
    ]
    listed = set()
    for m in new:
        assert m["layer"] == "scheduler" and m["better"] == "lower"
        if m["name"].endswith(".sat"):
            assert m["moves"] == "gen_tokens_per_s"
        else:
            assert m["workloads"] == ["chat-steady"]
            assert m["moves"] == "norm_latency_p90"
        for w in m["workloads"]:
            mine = {e["name"] for e in spec.metrics_for(bench, "end_to_end", w)}
            assert m["moves"] in mine, (m["name"], w)
        listed.update(m["workloads"])
    # every serving cell reads the account, no training cell does
    assert listed == set(serving)


@pytest.mark.parametrize("name", sorted(m["name"] for m in _new_metrics()))
def test_reader_on_hand_written_scrapes(name):
    read = spec.load_reader(name)
    assert read(SERVE) == pytest.approx(EXPECTED[name.rsplit(".", 1)[0]], rel=1e-12)
    # a training cell has no engine, and neither has the window of a
    # program without the account (this PR's parent): nothing to read
    assert read({"kind": "train"}) is None
    bare = {
        k: v for k, v in W1.items()
        if "loop_phase" not in k and "process_gc" not in k
    }
    assert f'{P}serving_loop_seconds_total{{phase="build"}}' in bare
    assert read({"kind": "serve", "seconds": 50.0, "snap_w0": bare, "snap_w1": bare}) is None
    # a window in which nothing ran gained nothing
    still = {"kind": "serve", "seconds": 50.0, "snap_w0": W1, "snap_w1": W1}
    assert read(still) in (None, 0.0)


def test_a_pause_past_the_last_bound_reads_as_the_window():
    from lib import hostclock

    late = dict(W1)
    late[f'{P}process_gc_seconds_bucket{{generation="2",le="+Inf"}}'] += 1.0
    ctx = dict(SERVE, snap_w1=late)
    assert hostclock.gc_longest_ms(ctx) == 50_000.0


# ── BENCHMARK.json against the form, string by string ────────────────────

LINE = "a line of 1 to 200 characters with no tab"
ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"}, {"workloads"}),
    "per_layer": (
        {"name", "unit", "better", "source", "layer", "moves"}, {"workloads"},
    ),
}
#: the keys of an entry that hold free text
LINES = {
    "configs": ("source", "why"), "workloads": ("why",), "end_to_end": (),
    "per_layer": ("layer",),
}


def form_breaches(bench: dict, raw: bytes) -> list:
    """What the form refuses before a run, beyond ``spec.check_rules``: the
    lengths of every string, the keys of every entry, the counts."""
    bad = []

    def line(value, what):
        if not isinstance(value, str) or not 1 <= len(value) <= 200 or "\n" in value or "\t" in value:
            bad.append(f"{what}: not {LINE}")

    if len(raw) > 64 * 1024:
        bad.append(f"the file is {len(raw)} bytes")
    for word in bench["command"]:
        line(word, "command")
    for group, (needed, allowed) in ENTRY_KEYS.items():
        for entry in bench[group]:
            keys = set(entry)
            if not needed <= keys <= needed | allowed:
                bad.append(f"{group} {entry.get('name')}: keys {sorted(keys ^ needed)}")
            if not spec.NAME_RE.match(entry["name"]):
                bad.append(f"{group}: name {entry['name']!r}")
            for key in LINES[group]:
                line(entry[key], f"{group} {entry['name']} {key}")
    for c in bench["configs"]:
        if len(c["reduced"]) > 16:
            bad.append(f"config {c['name']}: {len(c['reduced'])} reduced keys")
        if not all(ch.isalnum() or ch in "_.-/" for ch in c["file"]):
            bad.append(f"config {c['name']}: file {c['file']!r}")
    for group, most in (("configs", 24), ("workloads", 24), ("per_layer", 128)):
        if not 1 <= len(bench[group]) <= most:
            bad.append(f"{len(bench[group])} {group}")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    if four > max(1, len(bench["workloads"]) // 4):
        bad.append(f"{four} cells of four chips")
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    if len(set(pairs)) != len(pairs):
        bad.append("a pair of configuration and traffic appears twice")
    runs = 2 + 14 * len(bench["workloads"])
    if runs * (bench["run_seconds"] + 60) + 180 * len(bench["workloads"]) + 1200 > 43200:
        bad.append("a full check does not fit into 43,200 s")
    return bad


def test_benchmark_json_is_inside_the_forms_limits():
    raw = (spec.ROOT / "BENCHMARK.json").read_bytes()
    bench = json.loads(raw)
    assert form_breaches(bench, raw) == []
    # and the script does refuse what the form refuses
    broken = json.loads(raw)
    broken["workloads"][0]["why"] = "x" * 201
    broken["per_layer"][-1]["why"] = "a metric takes no why"
    broken["per_layer"][-2]["layer"] = "two\nlines"
    found = form_breaches(broken, raw)
    assert len(found) == 3 and all(
        any(w in f for f in found) for w in ("why", "keys", "layer")
    )
