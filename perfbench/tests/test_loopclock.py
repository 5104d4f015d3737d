"""The loop clock's readers (``lib/loopclock.py`` and the twelve metrics over
it) on two hand-written scrapes whose answers can be worked out on paper,
and the gap attribution (``lib/hostgaps.py``) on a hand-written trace and
on ``fixtures/engine.xplane.pb``, recorded on a v5e (``gaps_by_phase.py``)."""

import json
from pathlib import Path

import pytest

from lib import hostgaps, spec, trace

FIXTURES = Path(__file__).with_name("fixtures")

P = "pygrid_serving_"


def _snap(loop, dispatch, live, computed, admitted):
    """A scrape of ``/metrics`` as ``lib/serving.scrape`` keeps it."""
    out = {"engine": {"kv_blocks_free": 1}}
    for phase, seconds in loop.items():
        out[f'{P}loop_seconds_total{{phase="{phase}"}}'] = float(seconds)
    for (path, width), (total, count) in dispatch.items():
        labels = f'path="{path}",width="{width}"'
        out[f"{P}dispatch_seconds_sum{{{labels}}}"] = float(total)
        out[f"{P}dispatch_seconds_count{{{labels}}}"] = float(count)
        out[f'{P}dispatch_seconds_bucket{{{labels},le="+Inf"}}'] = float(count)
    out[f'{P}dispatch_rowsteps_total{{kind="live"}}'] = float(live)
    out[f'{P}dispatch_rowsteps_total{{kind="computed"}}'] = float(computed)
    out[f"{P}admitted_total"] = float(admitted)
    # neighbours that must not be picked up
    out[f'{P}tokens_total{{model="bench"}}'] = 1e6
    out[f"{P}prefill_seconds_sum"] = 99.0
    return out


#: over a 50 s window: idle 1, admit 0.5, prefill 2, build 1.5, fetch 42,
#: emit 3; 100 dispatches (60 at width 16 taking 36 s, 30 fused at width 8
#: taking 6 s, 10 steps at width 8 taking 2 s); 40 rows admitted; 900 live
#: of 1200 computed row-steps. ``fetch`` has no series before the window.
W0 = _snap(
    {"idle": 10, "admit": 1.0, "prefill": 4, "build": 2.0, "emit": 5},
    {("step", 16): (10, 20), ("fused", 8): (3, 10)},
    live=100, computed=200, admitted=7,
)
W1 = _snap(
    {"idle": 11, "admit": 1.5, "prefill": 6, "build": 3.5, "fetch": 42, "emit": 8},
    {("step", 16): (46, 80), ("fused", 8): (9, 40), ("step", 8): (2, 10)},
    live=1000, computed=1400, admitted=47,
)
SERVE = {"kind": "serve", "seconds": 50.0, "snap_w0": W0, "snap_w1": W1}

EXPECTED = {
    "engine_host_pct": 100 * (0.5 + 1.5 + 3) / 50,
    "engine_emit_ms": 1e3 * 3 / 100,
    "engine_build_ms": 1e3 * 1.5 / 100,
    "engine_admit_ms": 1e3 * 0.5 / 40,
    "prefill_stall_pct": 100 * 2 / 50,
    "width_padding_pct": 100 * (1 - 900 / 1200),
    "decode_w16_time_pct": 100 * 36 / (36 + 6 + 2),
}


def _new_metrics():
    return [
        m for m in spec.load_benchmark()["per_layer"]
        if m["name"].rsplit(".", 1)[0] in EXPECTED
    ]


def test_the_twelve_entries_are_there_and_the_rules_hold():
    bench = spec.load_benchmark()
    assert spec.check_rules(bench) == []
    new = _new_metrics()
    assert len(new) == 12
    first = bench["per_layer"].index(new[0])
    assert bench["per_layer"][first : first + 12] == new  # added in one block
    for m in new:
        cell = "batch-saturate" if m["name"].endswith(".sat") else "chat-steady"
        # the cell it was made for comes first; a later PR may append
        # others, each of which has to report the metric this one moves
        assert m["workloads"][0] == cell and m["layer"] == "scheduler"
        assert len(set(m["workloads"])) == len(m["workloads"])
        assert m["better"] == "lower"
        assert m["moves"] == (
            "gen_tokens_per_s" if cell == "batch-saturate" else "norm_latency_p50"
        )
        for w in m["workloads"]:
            mine = {e["name"] for e in spec.metrics_for(bench, "end_to_end", w)}
            assert m["moves"] in mine, (m["name"], w)


@pytest.mark.parametrize("name", sorted(m["name"] for m in _new_metrics()))
def test_reader_on_hand_written_scrapes(name):
    read = spec.load_reader(name)
    assert read(SERVE) == pytest.approx(EXPECTED[name.rsplit(".", 1)[0]], rel=1e-12)
    # a training cell has no engine, and neither has the window of a
    # program without the clock (this PR's parent): nothing to read
    assert read({"kind": "train"}) is None
    bare = {k: v for k, v in W1.items() if "loop_seconds" not in k
            and "dispatch_" not in k and "admitted" not in k}
    assert read({"kind": "serve", "seconds": 50.0, "snap_w0": bare, "snap_w1": bare}) is None
    # a window in which nothing was dispatched or admitted divides by nothing
    still = {"kind": "serve", "seconds": 50.0, "snap_w0": W1, "snap_w1": W1}
    assert read(still) in (None, 0.0)


#: the device runs 0-10 ms, 12-20 ms and 20.05-30 ms; the engine thread is
#: in fetch until 10.5 ms, emit until 11 ms, build until 12.5 ms, fetch from
#: there. The 2 ms gap is fetch 0.5 + emit 0.5 + build 1.0 by overlap and
#: all fetch by its start; the 0.05 ms gap is too short to be split. A
#: second host line carries an annotation of another name: ignored.
HAND = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 1 offset_ps: 12000000000 duration_ps: 8000000000 }
    events { metadata_id: 2 offset_ps: 20050000000 duration_ps: 9950000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8]{0} fusion(%p)" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
}
planes {
  name: "/host:CPU"
  lines {
    name: "python3"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10500000000 }
    events { metadata_id: 2 offset_ps: 10500000000 duration_ps: 500000000 }
    events { metadata_id: 3 offset_ps: 11000000000 duration_ps: 1500000000 }
    events { metadata_id: 1 offset_ps: 12500000000 duration_ps: 17500000000 }
  }
  lines { name: "other" timestamp_ns: 1000 events { metadata_id: 4 offset_ps: 0 duration_ps: 30000000000 } }
  event_metadata { key: 1 value { id: 1 name: "engine.fetch" } }
  event_metadata { key: 2 value { id: 2 name: "engine.emit" } }
  event_metadata { key: 3 value { id: 3 name: "engine.build" } }
  event_metadata { key: 4 value { id: 4 name: "door.wait" } }
}
"""


def test_gaps_of_a_hand_written_trace_by_phase(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND))
    spans = hostgaps.engine_spans(path)
    assert [s[2] for s in spans] == ["fetch", "emit", "build", "fetch"]
    by_overlap = hostgaps.attribute(path)
    assert by_overlap == {
        "fetch": pytest.approx(0.5e-3), "emit": pytest.approx(0.5e-3),
        "build": pytest.approx(1.0e-3), "short_gaps": pytest.approx(0.05e-3),
    }
    by_start = hostgaps.attribute(path, rule="start")
    assert by_start == {
        "fetch": pytest.approx(2.0e-3), "short_gaps": pytest.approx(0.05e-3),
    }
    # a lower threshold splits the short gap too; no span, no name
    assert hostgaps.attribute(path, min_gap_s=1e-5)["fetch"] == pytest.approx(0.55e-3)
    assert hostgaps.split((40e-3, 41e-3), spans, "overlap") == {
        "unattributed": pytest.approx(1e-3)
    }
    assert hostgaps.split((40e-3, 41e-3), spans, "start") == {
        "unattributed": pytest.approx(1e-3)
    }
    # both rules account for all the gap time the plain reduction finds
    gaps = trace.reduce(trace.device_events(path), 0.030)["breakdown"]["idle_gaps"]
    assert sum(v for _, v in gaps) == pytest.approx(sum(by_overlap.values()))
    with pytest.raises(ValueError, match="neither overlap nor start"):
        hostgaps.attribute(path, rule="end")


def test_gaps_of_the_recorded_v5e_trace_by_phase():
    expected = json.loads((FIXTURES / "engine.expected.json").read_text())
    path = FIXTURES / "engine.xplane.pb"
    # the engine thread's line, found by its events' names alone
    assert [s[2] for s in hostgaps.engine_spans(path)] == expected["phases"]
    by_overlap = hostgaps.attribute(path)
    by_start = hostgaps.attribute(path, rule="start")
    assert by_overlap == pytest.approx(expected["overlap"], rel=1e-9)
    assert by_start == pytest.approx(expected["start"], rel=1e-9)
    # host and device lie on one clock: the 20 ms pause between requests
    # is idle time of the device under engine.idle, and nearly all of the
    # gap time is under some phase
    assert 0.019 < by_overlap["idle"] < 0.022
    assert by_overlap["unattributed"] < 0.01 * expected["gap_s"]
    for table in (by_overlap, by_start):
        assert sum(table.values()) == pytest.approx(expected["gap_s"], rel=1e-9)
    reduced = trace.reduce(trace.device_events(path), expected["window_s"], top=1000)
    gaps = reduced["breakdown"]["idle_gaps"]
    assert gaps[:4] == expected["after_op"]
    assert sum(v for _, v in gaps) == pytest.approx(expected["gap_s"], rel=1e-9)
