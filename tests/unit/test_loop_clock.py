"""The engine thread's loop clock (telemetry/loopclock.py, serving/engine.py).

What has to hold: the six phases partition the worker thread's time (their
seconds add up to the thread's wall time); each phase shows on the path
that has it; a dispatch's live and computed row-steps match a count made
by hand for a fixed arrival order; the new families reach ``/metrics`` in
a form the strict parser accepts; and under ``jax.profiler`` the phases
are ``engine.<phase>`` events with their arguments.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax

from pygrid_tpu import telemetry
from pygrid_tpu.models import transformer as T
from pygrid_tpu.serving import EngineConfig, GenerationEngine
from pygrid_tpu.telemetry.bus import TelemetryBus
from pygrid_tpu.telemetry.loopclock import LoopClock

CFG = T.TransformerConfig(
    vocab=31, d_model=16, n_heads=2, n_layers=2, d_ff=32, max_len=32
)
PHASES = ("idle", "admit", "prefill", "build", "fetch", "emit")
PROMPT = np.arange(1, 6, dtype=np.int32)


@pytest.fixture(scope="module")
def params():
    return T.init(jax.random.PRNGKey(5), CFG)


@pytest.fixture(autouse=True)
def _clean_bus():
    telemetry.reset()
    yield
    telemetry.reset()


def _engine(params, **over):
    kw = dict(
        max_slots=16, slot_buckets=(1, 4, 8), min_prompt_bucket=8,
        block_size=8,
    )
    kw.update(over)
    return GenerationEngine(CFG, params, EngineConfig(**kw), model_id="clock")


def _gate(eng):
    """Hold the worker thread at its door until ``go`` is set, and time
    its life: ``wall`` gets the seconds ``_loop`` ran. With the thread
    held, everything enqueued before ``go`` is queued when it starts —
    the arrival order is fixed."""
    go, wall = threading.Event(), []
    loop = eng._loop

    def gated():
        go.wait(30)
        t0 = time.perf_counter()
        loop()
        wall.append(time.perf_counter() - t0)

    eng._loop = gated
    return go, wall


def _counter(name, **labels):
    want = tuple(sorted(labels.items()))
    return sum(
        v for (n, lab), v in telemetry.counters().items()
        if n == name and all(kv in lab for kv in want)
    )


def _dispatches(**labels):
    want = tuple(sorted(labels.items()))
    return [
        snap for (n, lab), snap in telemetry.histograms().items()
        if n == "serving_dispatch_seconds" and all(kv in lab for kv in want)
    ]


# ── the primitive ────────────────────────────────────────────────────────


def test_clock_sums_phases_and_flushes_under_one_lock(monkeypatch):
    flushed = []
    monkeypatch.setattr(
        telemetry.loopclock.bus, "incr_many",
        lambda name, label, amounts: flushed.append(
            (name, label, dict(amounts))
        ),
    )
    clock = LoopClock("unit_loop_seconds_total", "unit.")
    t0 = time.perf_counter()
    clock.enter("a")
    time.sleep(0.02)
    clock.enter("b", n=1)
    clock.annotate(width=4)  # legal with no profiler attached
    time.sleep(0.01)
    clock.enter("a")
    clock.flush()
    assert len(flushed) == 1
    name, label, sums = flushed[0]
    assert (name, label) == ("unit_loop_seconds_total", "phase")
    # the open phase is not handed over before it ends
    assert sorted(sums) == ["a", "b"]
    assert sums["a"] >= 0.02 and sums["b"] >= 0.01
    clock.flush()  # nothing closed since: no second call
    assert len(flushed) == 1
    clock.stop()
    wall = time.perf_counter() - t0
    total = sum(sum(f[2].values()) for f in flushed)
    assert total == pytest.approx(wall, rel=0.02)
    assert len(flushed) == 2 and list(flushed[1][2]) == ["a"]


def test_incr_many_adds_each_series_and_keeps_the_cardinality_guard():
    bus = TelemetryBus(max_labelsets=2)
    bus.incr_many("fam_total", "phase", {"a": 1.5, "b": 2.0})
    bus.incr_many("fam_total", "phase", {"a": 0.5, "c": 4.0})
    got = bus.counters()
    assert got[("fam_total", (("phase", "a"),))] == 2.0
    assert got[("fam_total", (("phase", "b"),))] == 2.0
    # the third label set folds, as it would through incr()
    assert got[("fam_total", (("other", "true"),))] == 4.0
    assert got[
        ("telemetry_labels_dropped_total", (("family", "fam_total"),))
    ] == 1


# ── the engine ───────────────────────────────────────────────────────────


@pytest.mark.parametrize("fused", [False, True], ids=["step", "fused"])
def test_phases_partition_the_engine_threads_time(params, fused):
    eng = _engine(params, max_slots=4, slot_buckets=(1, 2, 4), fused=fused)
    go, wall = _gate(eng)
    go.set()
    try:
        eng.submit(PROMPT[None, :], 6, timeout=120)
        time.sleep(0.05)  # nothing queued, nothing live: idle
        futures = [eng.enqueue(PROMPT[None, :], n) for n in (3, 12)]
        for f in futures:
            f.result(120)
    finally:
        eng.close()
    seconds = {p: _counter("serving_loop_seconds_total", phase=p) for p in PHASES}
    assert all(v > 0 for v in seconds.values()), seconds
    assert seconds["idle"] >= 0.04
    assert sum(seconds.values()) == pytest.approx(wall[0], rel=0.02)
    # nothing outside the six names
    assert _counter("serving_loop_seconds_total") == pytest.approx(
        sum(seconds.values())
    )
    assert _counter("serving_admitted_total") == 3
    path = "fused" if fused else "step"
    taken = _dispatches(path=path)
    assert taken and all(s["count"] > 0 for s in taken)
    if fused:
        # admission never waited, so no per-step dispatch was made
        assert not _dispatches(path="step")


def _hist_sum(name):
    return sum(
        snap["sum"] for (n, _), snap in telemetry.histograms().items()
        if n == name
    )


@pytest.mark.parametrize("fused", [False, True], ids=["step", "fused"])
def test_no_second_is_counted_twice_with_programs_queued(params, fused):
    """Nine requests on two slots, queued before the loop starts: steps
    are launched ahead of the tokens read and prefills behind steps in
    flight, so two or three programs are queued at once. A dispatch's
    (and a prefill's) seconds run from the later of its launch and the
    fetch before it to its own fetch: together they fit into the
    thread's wall time, which the six phases still partition."""
    eng = _engine(params, max_slots=2, slot_buckets=(1, 2), fused=fused)
    go, wall = _gate(eng)
    try:
        futures = [
            eng.enqueue(PROMPT[None, : 1 + i % 5], n)
            for i, n in enumerate((6, 1, 2, 12, 3, 1, 9, 2, 5))
        ]
        go.set()
        for f in futures:
            f.result(120)
    finally:
        eng.close()
    assert _counter("serving_dispatches_total", ahead="yes") > 0
    assert _counter("serving_admitted_total") == 9
    spans = _hist_sum("serving_dispatch_seconds") + _hist_sum(
        "serving_prefill_seconds"
    )
    assert 0 < spans <= wall[0]
    seconds = {p: _counter("serving_loop_seconds_total", phase=p) for p in PHASES}
    assert sum(seconds.values()) == pytest.approx(wall[0], rel=0.02)
    # every launched program was fetched: one observation each
    counts = sum(
        snap["count"] for (n, _), snap in telemetry.histograms().items()
        if n in ("serving_dispatch_seconds", "serving_prefill_seconds")
    )
    assert counts == 9 + _counter("serving_dispatches_total")


@pytest.mark.parametrize(
    "fused, n_long, dispatches, live, computed",
    [
        # step 1 carries all 16 rows; the 15 short ones leave, and the
        # long row, in the HIGHEST slot, holds the width at 16 alone
        (False, 4, 3, 16 + 1 + 1, 3 * 16),
        # scan 1: 16 rows x 8 steps; scan 2: the long row alone x 8
        (True, 12, 2, 16 * 8 + 8, 2 * 16 * 8),
    ],
    ids=["step", "fused"],
)
def test_rowsteps_match_a_hand_count(
    params, fused, n_long, dispatches, live, computed
):
    eng = _engine(params, fused=fused)
    go, _wall = _gate(eng)
    try:
        short = eng.enqueue(np.tile(PROMPT, (15, 1)), 2)  # slots 0..14
        long = eng.enqueue(PROMPT[None, :], n_long)  # slot 15
        go.set()
        short.result(120)
        long.result(120)
    finally:
        eng.close()
    assert _counter("serving_admitted_total") == 16
    assert _counter("serving_dispatch_rowsteps_total", kind="live") == live
    assert (
        _counter("serving_dispatch_rowsteps_total", kind="computed")
        == computed
    )
    path = "fused" if fused else "step"
    (only,) = _dispatches()  # one label set: this path at width 16
    (same,) = _dispatches(path=path, width="16")
    assert only == same and only["count"] == dispatches


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
@pytest.mark.parametrize(
    "fused, n_long, table, live_pages",
    [
        # 3 dispatches at width 16, tables of 32 / 8 = 4 pages. Every
        # row is under one page (prompt 5 + at most 3); once the short
        # rows left, 15 free slots read one trash page each
        (False, 4, 3 * 16 * 4, 16 + (1 + 15) + (1 + 15)),
        # 2 scans of 8 steps. Scan 1: the short rows park at 7 rows (15
        # x 8 pages), the long row grows 6..13 rows (3 steps in one
        # page, 5 in two). Scan 2: it grows 14..16, then parks at 17
        # rows (3 x 2 + 5 x 3 pages) beside 15 free slots
        (True, 12, 2 * 16 * 8 * 4, (120 + 3 + 10) + (6 + 15 + 120)),
    ],
    ids=["step", "fused"],
)
def test_kv_pages_match_a_hand_count(
    params, fused, n_long, table, live_pages, kernel
):
    """``table`` counts the pages the dispatched tables span; ``read``
    equals it on the gather path, and is the rows' live pages where the
    engine found its pool eligible for the kernel (set by hand here: the
    count is the host's own arithmetic, the programs still gather)."""
    eng = _engine(params, fused=fused)
    assert eng._kv_kernel is False  # tiny heads on a CPU
    eng._kv_kernel = kernel
    go, _wall = _gate(eng)
    try:
        short = eng.enqueue(np.tile(PROMPT, (15, 1)), 2)  # slots 0..14
        long = eng.enqueue(PROMPT[None, :], n_long)  # slot 15
        go.set()
        short.result(120)
        long.result(120)
    finally:
        eng.close()
    assert _counter("serving_kv_pages_total", kind="table") == table
    assert _counter("serving_kv_pages_total", kind="read") == (
        live_pages if kernel else table
    )


def test_new_families_reach_metrics_and_parse_strictly(params):
    from pygrid_tpu.telemetry import promtext
    from pygrid_tpu.utils.metrics import Exposition

    eng = _engine(params, max_slots=4, slot_buckets=(1, 2, 4))
    try:
        eng.submit(PROMPT[None, :], 10, timeout=120)
    finally:
        eng.close()
    exp = Exposition()
    telemetry.export(exp)
    families = promtext.parse(exp.render())
    loop = families["pygrid_serving_loop_seconds_total"]
    assert loop.type == "counter" and "phase" in loop.help
    assert {s[1]["phase"] for s in loop.samples} <= set(PHASES)
    assert {"admit", "prefill", "build", "fetch", "emit"} <= {
        s[1]["phase"] for s in loop.samples
    }
    disp = families["pygrid_serving_dispatch_seconds"]
    assert disp.type == "histogram"
    assert {(s[1]["path"], s[1]["width"]) for s in disp.samples} == {
        ("fused", "1")
    }
    rows = families["pygrid_serving_dispatch_rowsteps_total"]
    assert {s[1]["kind"] for s in rows.samples} == {"live", "computed"}
    assert families["pygrid_serving_admitted_total"].samples[0][2] == 1.0
    pages = families["pygrid_serving_kv_pages_total"]
    assert {s[1]["kind"] for s in pages.samples} == {"read", "table"}
    for name in (
        "serving_loop_seconds_total", "serving_dispatch_seconds",
        "serving_dispatch_rowsteps_total", "serving_admitted_total",
        "serving_kv_pages_total",
    ):
        assert not telemetry.bus.family_help(name).startswith(
            "pygrid telemetry metric"
        )


def test_profile_carries_the_phases_with_their_arguments(params, tmp_path):
    from jax.profiler import ProfileData

    eng = _engine(params, max_slots=4, slot_buckets=(1, 2, 4), fused=False)
    try:
        eng.submit(PROMPT[None, :], 2, timeout=120)  # compile outside
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            futures = [eng.enqueue(PROMPT[None, :], 4) for _ in range(3)]
            for f in futures:
                f.result(120)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    events = [
        ev
        for plane in ProfileData.from_file(str(xplane)).planes
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("engine.")
    ]
    names = {ev.name for ev in events}
    assert {
        "engine.admit", "engine.prefill", "engine.build", "engine.fetch",
        "engine.emit",
    } <= names
    prefills = [dict(ev.stats) for ev in events if ev.name == "engine.prefill"]
    assert len(prefills) == 3  # once per admitted row
    assert len({p["request_id"] for p in prefills}) == 3
    assert all(int(p["bucket"]) == 8 for p in prefills)
    builds = [dict(ev.stats) for ev in events if ev.name == "engine.build"]
    assert builds and all(b["path"] == "step" for b in builds)
    assert all(
        int(b["steps"]) == 1 and 1 <= int(b["live"]) <= int(b["width"]) <= 4
        for b in builds
    )
    # every launched program's answer is fetched once, a dispatch late
    fetches = [ev for ev in events if ev.name == "engine.fetch"]
    assert len(fetches) == len(builds) + len(prefills)
