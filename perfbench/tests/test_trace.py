"""The trace reduction: union of device operation intervals, operations
ranked by their own time, idle gaps named by the operation before them.
Checked on a hand-written trace whose answers can be counted on paper and
on ``fixtures/small.xplane.pb``, recorded on a v5e (``record_trace.py``)."""

import json
from pathlib import Path

import pytest

from lib import trace

FIXTURES = Path(__file__).with_name("fixtures")

#: device 0: fusion 0-2 ms, a while 1-4 ms that spans a copy 2-3 ms, then
#: nothing until fusion 10-11 ms, convert 11.5-12 ms. Device 1: one 6 ms
#: fusion. A host plane and a "Steps" line that must be ignored.
HAND = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 3000000000 }
    events { metadata_id: 4 offset_ps: 2000000000 duration_ps: 1000000000 }
    events { metadata_id: 5 offset_ps: 10000000000 duration_ps: 1000000000 }
    events { metadata_id: 6 offset_ps: 11500000000 duration_ps: 500000000 }
  }
  lines { name: "Steps" timestamp_ns: 1000 events { metadata_id: 3 offset_ps: 0 duration_ps: 99000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.12 = bf16[8,16]{1,0} fusion(%p0)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.3" } }
  event_metadata { key: 3 value { id: 3 name: "step" } }
  event_metadata { key: 4 value { id: 4 name: "copy.7" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.99 = f32[4]{0} fusion(%p1)" } }
  event_metadata { key: 6 value { id: 6 name: "convert.1" } }
}
planes {
  name: "/device:TPU:1"
  lines { name: "XLA Ops" timestamp_ns: 1000 events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.5" } }
}
planes { name: "/host:CPU" lines { name: "XLA Ops" events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000000000 } } event_metadata { key: 1 value { id: 1 name: "x" } } }
"""


def test_merge_unions_overlapping_and_nested_intervals():
    merged = trace.merge([("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 2.0, 1.0), ("d", 10.0, 1.0)])
    assert merged == [(0.0, 4.0, "b"), (10.0, 11.0, "d")]
    assert trace.merge([]) == []
    assert trace.short_name("%fusion.12 = bf16[8,16]{1,0} fusion(%p0)") == "fusion_bf16_8_16"
    assert trace.short_name("slice_bitcast_fusion.3") == "slice_bitcast_fusion"
    assert trace.short_name("convert.325.remat") == "convert.remat"
    assert trace.short_name("%c.1 = (f32[4]{0}, u32[]) copy-start(%x)") == "c_f32_4"


def test_reduction_of_a_hand_written_trace(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND))
    per_device = trace.device_events(path)
    assert sorted(per_device) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(per_device["/device:TPU:0"]) == 5
    out = trace.reduce(per_device, window_s=0.020)
    # device 0 busy 4 + 1 + 0.5 = 5.5 ms, device 1 busy 6 ms: mean 5.75 ms
    assert out["busy_s"] == pytest.approx(5.75e-3)
    assert out["window_s"] == 0.020
    assert out["idle_pct"] == pytest.approx(100 * (1 - 5.75 / 20))
    ops = dict(out["breakdown"]["device_ops"])
    # the while is busy time but no operation's own; fusions of one kind add up
    assert not any(k.startswith("while") for k in ops)
    assert ops["fusion_bf16_8_16"] == pytest.approx(2e-3)
    assert ops["fusion_f32_4"] == pytest.approx(1e-3)
    assert ops["fusion"] == pytest.approx(6e-3)
    assert ops["copy"] == pytest.approx(1e-3) and ops["convert"] == pytest.approx(0.5e-3)
    # the whole table, with counts: what the breakdown was ranked from
    assert {k: v[0] for k, v in out["ops"].items()} == ops
    assert {k: v[1] for k, v in out["ops"].items()} == {
        "fusion_bf16_8_16": 1, "fusion_f32_4": 1, "fusion": 1, "copy": 1, "convert": 1,
    }
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps == {"after:while": pytest.approx(6e-3), "after:fusion_f32_4": pytest.approx(0.5e-3)}
    with pytest.raises(ValueError, match="no accelerator plane"):
        trace.reduce({}, 1.0)


def test_reduction_of_the_recorded_v5e_trace():
    expected = json.loads((FIXTURES / "small.expected.json").read_text())
    per_device = trace.device_events(FIXTURES / "small.xplane.pb")
    assert list(per_device) == ["/device:TPU:0"]
    assert {k: len(v) for k, v in per_device.items()} == expected["events"]
    out = trace.reduce(per_device, expected["window_s"])
    assert out["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert 0.0 < out["idle_pct"] < 100.0
    assert out["breakdown"] == expected["breakdown"]
    assert len(out["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("name", ["small", "engine"])
def test_ops_holds_every_operation_of_a_recorded_trace(name):
    expected = json.loads((FIXTURES / f"{name}.expected.json").read_text())
    per_device = trace.device_events(FIXTURES / f"{name}.xplane.pb")
    out = trace.reduce(per_device, expected["window_s"])
    assert {k: list(v) for k, v in out["ops"].items()} == expected["ops"]
    events = [e for evs in per_device.values() for e in evs if not trace.CONTAINERS.match(e[0])]
    assert set(out["ops"]) == {trace.short_name(e[0]) for e in events}
    assert sum(n for _, n in out["ops"].values()) == len(events)
    assert sum(s for s, _ in out["ops"].values()) == pytest.approx(sum(e[2] for e in events))
    # its ten largest are the breakdown's list, name for name and second for second
    ten = sorted(out["ops"].items(), key=lambda kv: -kv[1][0])[:10]
    assert [[k, v[0]] for k, v in ten] == out["breakdown"]["device_ops"]
    assert out["breakdown"]["device_ops"] == expected.get(
        "device_ops", expected.get("breakdown", {}).get("device_ops")
    )
    assert set(out) == {"busy_s", "window_s", "idle_pct", "breakdown", "ops"}
