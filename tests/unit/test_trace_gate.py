"""The rule the driver's gate holds a traced result line to, held here too:
``0 < device.busy_s <= device.window_s``. The benchmark's trace reduction
(``perfbench/lib/trace.py``, PR 38) cuts every device operation to the one
``perfbench.stretch`` annotation of the file, on the trace's own clock, so a
device that is busy from before the mark opens to after it closes reads a
busy time of exactly its window's length; a file without that one mark, or
with no operation under it, gives no result line at all (``CellFailure``).
PR 37 was refused on a cell it had not claimed because the old reading (every
operation of the file over the host's stopwatch) could pass the window by a
millisecond. ``perfbench/tests/test_trace.py`` holds the same cases beside
the reduction's own; these are the tier-1 copy, on files written by hand and
on the traces recorded on a v5e before the mark existed.
"""

from __future__ import annotations

import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "perfbench") not in sys.path:
    sys.path.append(str(ROOT / "perfbench"))
from lib import trace  # noqa: E402

FIXTURES = ROOT / "perfbench" / "tests" / "fixtures"

#: device 0: a fusion 0-1 ms, one 1.5-3 ms, a while 4-7 ms that spans a
#: copy 5-6 ms, a convert 11-13 ms, a fusion 13-14 ms. Device 1: one fusion
#: 0-20 ms. The mark, on a host thread, runs from 2 to 12 ms.
DEVICE_0 = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 1500000000 duration_ps: 1500000000 }
    events { metadata_id: 2 offset_ps: 4000000000 duration_ps: 3000000000 }
    events { metadata_id: 4 offset_ps: 5000000000 duration_ps: 1000000000 }
    events { metadata_id: 6 offset_ps: 11000000000 duration_ps: 2000000000 }
    events { metadata_id: 5 offset_ps: 13000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.12 = bf16[8,16]{1,0} fusion(%p0)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.3" } }
  event_metadata { key: 4 value { id: 4 name: "copy.7" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.99 = f32[4]{0} fusion(%p1)" } }
  event_metadata { key: 6 value { id: 6 name: "convert.1" } }
}
"""
DEVICE_1 = """
planes {
  name: "/device:TPU:1"
  lines { name: "XLA Ops" events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.5" } }
}
"""
HOST = """
planes {
  name: "/host:CPU"
  lines {
    name: "python3"
    events { metadata_id: 1 offset_ps: 3000000000 duration_ps: 500000000 }
    MARKS
  }
  event_metadata { key: 1 value { id: 1 name: "engine.fetch" } }
  event_metadata { key: 2 value { id: 2 name: "perfbench.stretch" } }
}
"""
MARK_2_12 = "events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 10000000000 }"
MARK_30_40 = "events { metadata_id: 2 offset_ps: 30000000000 duration_ps: 10000000000 }"


def _traced(tmp_path, marks: str, devices: str, host_window_s: float):
    """A ``Tracer`` over a file written by hand, as if its ``stop()`` had
    taken ``host_window_s`` on the host's stopwatch."""
    from jax.profiler import ProfileData

    text = devices + HOST.replace("MARKS", marks)
    (tmp_path / "marked.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text)
    )
    tracer = trace.Tracer(tmp_path)
    tracer.window_s = host_window_s
    return tracer


@pytest.mark.parametrize(
    "devices, busy_ms",
    [(DEVICE_1, 10.0), (DEVICE_0 + DEVICE_1, 7.5), (DEVICE_0, 5.0)],
    ids=["never-idle", "two-devices", "idle-inside"],
)
def test_a_traced_line_reads_busy_above_nought_and_at_most_its_window(
    tmp_path, devices, busy_ms
):
    """Whatever the device did around the mark: a stopwatch 0.1 ms SHORTER
    than the mark, and a device busy 2 ms before it and 8 ms after, read
    the mark's own 10 ms and not more."""
    out = _traced(tmp_path, MARK_2_12, devices, 9.9e-3).result()
    assert out["window_s"] == pytest.approx(10e-3)
    assert out["busy_s"] == pytest.approx(busy_ms * 1e-3)
    assert 0.0 < out["busy_s"] <= out["window_s"]
    assert out["idle_pct"] == pytest.approx(100.0 - 10.0 * busy_ms)
    if busy_ms == 10.0:
        # equal, not near: the gate compares the numbers as printed
        assert out["busy_s"] == out["window_s"] and out["idle_pct"] == 0.0
        # read the old way (the whole file over the stopwatch): refused
        old = trace.reduce(trace.device_events(tmp_path / "marked.xplane.pb"), 9.9e-3)
        assert old["busy_s"] > old["window_s"]


@pytest.mark.parametrize(
    "marks, says",
    [
        ("", "holds 0 'perfbench.stretch' annotations"),
        (MARK_2_12 + "\n" + MARK_30_40, "holds 2 'perfbench.stretch' annotations"),
        (MARK_30_40, "no device operation ran inside the traced stretch of 0.010 s"),
    ],
    ids=["no-mark", "two-marks", "no-operation-inside"],
)
def test_a_stretch_that_cannot_be_read_gives_no_line(tmp_path, marks, says):
    with pytest.raises(trace.CellFailure, match=says):
        _traced(tmp_path, marks, DEVICE_0 + DEVICE_1, 10e-3).result()


@pytest.mark.parametrize("name", ["small", "engine"])
def test_a_recorded_trace_without_the_mark_gives_no_line(tmp_path, name):
    """The traces recorded on a v5e before PR 38 hold device operations and
    no mark: nothing says which stretch of them was measured."""
    shutil.copy(FIXTURES / f"{name}.xplane.pb", tmp_path / f"{name}.xplane.pb")
    assert trace.device_events(tmp_path / f"{name}.xplane.pb")
    tracer = trace.Tracer(tmp_path)
    tracer.window_s = 1.0
    with pytest.raises(trace.CellFailure, match="holds 0"):
        tracer.result()
