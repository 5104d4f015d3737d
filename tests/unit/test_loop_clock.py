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

    def incr_many(name, label, amounts, *, more, instances):
        # copies: the clock empties its own sums once they are handed over
        family, gained = instances
        flushed.append((name, label, dict(amounts), {
            "more": [(f, dict(sums)) for f, sums in more],
            "instances": (family, {p: dict(g) for p, g in gained.items()}),
        }))

    monkeypatch.setattr(telemetry.loopclock.bus, "incr_many", incr_many)
    # whatever the probe makes of this machine's thread clock
    monkeypatch.setattr(
        telemetry.loopclock, "cpu_clock", lambda: time.thread_time
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
    name, label, sums, beside = flushed[0]
    assert (name, label) == ("unit_loop_seconds_total", "phase")
    # what the clock gathers beside the seconds rides in the same call
    assert sorted(beside) == ["instances", "more"]
    ((family, cpu),) = beside["more"]
    assert family == "unit_loop_cpu_seconds_total" and sorted(cpu) == ["a", "b"]
    family, gained = beside["instances"]
    assert family == "unit_loop_phase_seconds"
    assert {p: sum(g.values()) for p, g in gained.items()} == {"a": 1, "b": 1}
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


# ── the host account: instances, CPU seconds, the collector ──────────────


class _CountingLock:
    """A lock that counts how often it was taken."""

    def __init__(self):
        self._lock, self.taken = threading.Lock(), 0

    def __enter__(self):
        self._lock.acquire()
        self.taken += 1

    def __exit__(self, *exc):
        self._lock.release()


def _instances(family):
    """{phase: snapshot} of a clock's histogram of instances."""
    return {
        dict(lab)["phase"]: snap
        for (n, lab), snap in telemetry.histograms().items()
        if n == family
    }


def _script(monkeypatch, wall, cpu=None):
    """Give the clock's module scripted clocks: the readings it will
    get, in order. ``cpu`` None is a host whose thread clock gives no
    data: reading it at all fails the test."""
    import types

    wall_at, cpu_at = iter(wall), iter(cpu or ())

    def never():
        raise AssertionError("no CPU clock here: nothing may read one")

    monkeypatch.setattr(telemetry.loopclock, "time", types.SimpleNamespace(
        perf_counter=lambda: next(wall_at), thread_time=never,
    ))
    monkeypatch.setattr(
        telemetry.loopclock, "cpu_clock",
        lambda: None if cpu is None else (lambda: next(cpu_at)),
    )


def test_each_boundarys_readings_go_to_the_phase_that_was_open(monkeypatch):
    lock = _CountingLock()
    monkeypatch.setattr(telemetry.BUS, "_lock", lock)
    # a from 0 to 1 (0.25 of it on the CPU), b to 1.5 (all of it), a to 3.5
    # (none), b to 3.502, then the thread leaves
    _script(
        monkeypatch, wall=[0.0, 1.0, 1.5, 3.5, 3.502],
        cpu=[10.0, 10.25, 10.75, 10.75, 10.752],
    )
    clock = LoopClock("unit_loop_seconds_total", "unit.")
    clock.enter("a")
    clock.enter("b")
    clock.enter("a")
    clock.enter("b")
    clock.flush()
    # three families went over under one hold of the bus lock
    assert lock.taken == 1
    clock.stop()
    assert lock.taken == 2
    wall = {p: _counter("unit_loop_seconds_total", phase=p) for p in "ab"}
    cpu = {p: _counter("unit_loop_cpu_seconds_total", phase=p) for p in "ab"}
    assert wall == {"a": 3.0, "b": pytest.approx(0.502)}
    assert cpu == {"a": 0.25, "b": pytest.approx(0.502)}
    snaps = _instances("unit_loop_phase_seconds")
    # one observation an instance; the open phase is closed by stop()
    assert {p: s["count"] for p, s in snaps.items()} == {"a": 2, "b": 2}
    for phase, snap in snaps.items():
        assert snap["sum"] == pytest.approx(wall[phase], rel=1e-12)

    def gained(phase):
        """The buckets (by upper bound) that hold an instance."""
        buckets = snaps[phase]["buckets"]
        return [
            le for (le, n), (_, below) in zip(buckets, [(0, 0)] + buckets)
            if n > below
        ]

    # 1 s lies in (0.5, 1], 2 s in (1, 2.5]; 0.5 s in (0.25, 0.5], 2 ms
    # in (1, 2.5] ms: the highest is the longest single stretch
    assert gained("a") == [1.0, 2.5] and gained("b") == [0.0025, 0.5]


def test_a_host_without_a_cpu_clock_reads_none_and_has_no_family(monkeypatch):
    _script(monkeypatch, wall=[0.0, 1.0, 1.5], cpu=None)
    clock = LoopClock("unit_loop_seconds_total", "unit.")
    clock.enter("a")
    clock.enter("b")
    clock.stop()
    names = {n for (n, _) in telemetry.counters()}
    assert "unit_loop_seconds_total" in names
    # absent, not zero
    assert "unit_loop_cpu_seconds_total" not in names
    assert {p: s["count"] for p, s in _instances("unit_loop_phase_seconds").items()} == {
        "a": 1, "b": 1,
    }


@pytest.mark.parametrize(
    "tick, cost, kept",
    [
        (1e-9, 0.4e-6, True),  # a plain kernel: a system call, nanoseconds
        (1e-2, 0.4e-6, False),  # charged in scheduler ticks
        (1e-9, 6e-6, False),  # fine, but an emulated call a boundary
        (1e-2, 6e-6, False),  # a TPU v5e's host
    ],
    ids=["fine-cheap", "ticks", "dear", "ticks-dear"],
)
def test_the_probe_keeps_a_cpu_clock_only_where_it_gives_data(tick, cost, kept):
    now = [0.0]

    def read():  # a thread that spins: its CPU time is the time, in ticks
        now[0] += cost
        return now[0] // tick * tick

    probe = telemetry.loopclock.probe_cpu_clock
    assert probe(read, wall=lambda: now[0]) is (read if kept else None)


def test_one_interrupted_batch_does_not_cost_the_process_its_cpu_clock():
    now, reads = [0.0], [0]

    def read():
        reads[0] += 1
        # the machine takes the thread away for 5 ms in the first batch
        now[0] += 5e-3 if reads[0] == 7 else 0.4e-6
        return now[0]

    assert telemetry.loopclock.probe_cpu_clock(read, wall=lambda: now[0]) is read


def test_the_real_clocks_tell_a_sleep_from_a_spin_where_there_is_one():
    """The one test on the machine's own clocks, and loose: the rest of
    the account is arithmetic, held above on scripted ones."""
    clock = LoopClock("unit_loop_seconds_total", "unit.")
    clock.enter("sleep")
    time.sleep(0.05)
    clock.enter("spin")
    until = time.perf_counter() + 0.05
    while time.perf_counter() < until:
        pass
    clock.stop()
    wall = {p: _counter("unit_loop_seconds_total", phase=p) for p in ("sleep", "spin")}
    assert wall["sleep"] >= 0.05 and wall["spin"] >= 0.05
    snaps = _instances("unit_loop_phase_seconds")
    assert {p: s["count"] for p, s in snaps.items()} == {"sleep": 1, "spin": 1}
    names = {n for (n, _) in telemetry.counters()}
    if telemetry.loopclock.cpu_clock() is None:
        assert "unit_loop_cpu_seconds_total" not in names
    else:
        cpu = {p: _counter("unit_loop_cpu_seconds_total", phase=p) for p in ("sleep", "spin")}
        assert cpu["sleep"] < cpu["spin"]


def test_merged_instances_keep_the_cardinality_guard():
    bus = TelemetryBus(max_labelsets=1)
    bus.incr_many(
        "fam_total", "phase", {"a": 1.0, "b": 2.0},
        more=[("cpu_total", {"a": 0.5})],
        instances=("fam_seconds", {"a": {3: 2}, "b": {4: 1}}),
    )
    bus.incr_many(
        "fam_total", "phase", {"a": 700.0},
        instances=("fam_seconds", {"a": {3: 1, 27: 1}}),
    )
    assert bus.counters()[("cpu_total", (("phase", "a"),))] == 0.5
    hists = bus.histograms()
    a = hists[("fam_seconds", (("phase", "a"),))]
    # the histogram's sum is the counter's: they cannot part
    assert (a["count"], a["sum"]) == (4, 701.0)
    assert bus.counters()[("fam_total", (("phase", "a"),))] == 701.0
    # bucket 3 is (5 us, 10 us]; index 27 is past the last bound: +Inf
    assert dict(a["buckets"])[1e-5] == 3 and a["buckets"][-1][1] == 4
    assert a["buckets"][-2][1] == 3
    # the second label set folded, as it would through observe()
    other = hists[("fam_seconds", (("other", "true"),))]
    assert (other["count"], other["sum"]) == (1, 2.0)


@pytest.mark.parametrize("fused", [False, True], ids=["step", "fused"])
def test_cpu_seconds_and_instances_over_an_engine_run(params, fused, monkeypatch):
    import itertools

    # a CPU clock that gains one second a reading: a phase's CPU seconds
    # are then the boundaries that closed it, whatever the machine does
    reading = itertools.count()
    monkeypatch.setattr(
        telemetry.loopclock, "cpu_clock", lambda: lambda: float(next(reading))
    )
    eng = _engine(params, max_slots=4, slot_buckets=(1, 2, 4), fused=fused)
    go, wall = _gate(eng)
    entered = {}
    enter = eng._clock.enter

    def counted(phase, **args):
        entered[phase] = entered.get(phase, 0) + 1
        enter(phase, **args)

    eng._clock.enter = counted
    go.set()
    try:
        eng.submit(PROMPT[None, :], 6, timeout=120)
        time.sleep(0.05)
        for f in [eng.enqueue(PROMPT[None, :], n) for n in (3, 12)]:
            f.result(120)
    finally:
        eng.close()
    seconds = {p: _counter("serving_loop_seconds_total", phase=p) for p in PHASES}
    assert sum(seconds.values()) == pytest.approx(wall[0], rel=0.02)
    assert set(entered) == set(PHASES)
    cpu = {p: _counter("serving_loop_cpu_seconds_total", phase=p) for p in PHASES}
    assert cpu == entered
    snaps = _instances("serving_loop_phase_seconds")
    assert {p: s["count"] for p, s in snaps.items()} == entered
    for phase, snap in snaps.items():
        assert snap["sum"] == pytest.approx(seconds[phase], rel=1e-9)


def _gc_counts(generation):
    snap = telemetry.gcwatch.snapshots().get(generation)
    return (0, 0.0) if snap is None else (snap["count"], snap["sum"])


def test_a_collection_is_one_observation_and_takes_no_bus_lock(monkeypatch):
    import gc

    lock = _CountingLock()
    monkeypatch.setattr(telemetry.BUS, "_lock", lock)
    telemetry.gcwatch.start()
    telemetry.gcwatch.start()  # a second node in the process
    try:
        assert gc.callbacks.count(telemetry.gcwatch._on_gc) == 1
        full, young = _gc_counts(2), _gc_counts(0)
        gc.collect()
        gc.collect(0)
        assert _gc_counts(2)[0] == full[0] + 1 and _gc_counts(2)[1] > full[1]
        assert _gc_counts(0)[0] == young[0] + 1
        telemetry.gcwatch.stop()
        assert telemetry.gcwatch._on_gc in gc.callbacks  # one user left
    finally:
        telemetry.gcwatch.stop()
    assert telemetry.gcwatch._on_gc not in gc.callbacks
    assert lock.taken == 0
    seen = _gc_counts(2)
    gc.collect()  # nobody watches any more; what was counted stays
    assert _gc_counts(2) == seen


def test_a_hook_taken_down_inside_a_full_collection_leaves_nothing_open():
    watch = telemetry.gcwatch
    full = {"generation": watch.OLDEST}
    watch.start()
    try:
        watch._on_gc("start", full)  # a full collection begins ...
        assert watch._span is not None
        watch.stop()  # ... and its "stop" finds no hook to call
        assert watch._span is None
        watch.start()
        seen = _gc_counts(watch.OLDEST)
        # a "stop" whose "start" the new hook never saw: no length, and
        # no stale annotation to close
        watch._on_gc("stop", full)
        assert _gc_counts(watch.OLDEST) == seen and watch._span is None
    finally:
        watch.stop()
        watch.stop()  # one too many is harmless


def test_the_node_hangs_the_collectors_hook_and_takes_it_down():
    import asyncio
    import gc

    from aiohttp import web

    from pygrid_tpu.node import create_app
    from pygrid_tpu.telemetry import promtext
    from pygrid_tpu.utils.metrics import Exposition

    async def life():
        runner = web.AppRunner(create_app("gc-node"))
        await runner.setup()
        try:
            assert telemetry.gcwatch._on_gc in gc.callbacks
            before = _gc_counts(2)
            gc.collect()
            exp = Exposition()
            telemetry.export(exp)
            return before, promtext.parse(exp.render())
        finally:
            await runner.cleanup()

    before, families = asyncio.run(life())
    assert telemetry.gcwatch._on_gc not in gc.callbacks
    pauses = families["pygrid_process_gc_seconds"]
    assert pauses.type == "histogram" and "collector" in pauses.help
    full = {
        s[0]: s[2] for s in pauses.samples
        if s[1].get("generation") == "2" and "le" not in s[1]
    }
    # the forced one, and any the collector chose to make beside it
    assert full["pygrid_process_gc_seconds_count"] >= before[0] + 1
    assert full["pygrid_process_gc_seconds_sum"] > before[1]
    assert {s[1]["generation"] for s in pauses.samples} == {"0", "1", "2"}


def test_the_host_account_reaches_metrics_and_parses_strictly(
    params, monkeypatch
):
    from pygrid_tpu.telemetry import promtext
    from pygrid_tpu.utils.metrics import Exposition

    # half of every second on the CPU, whatever this machine's clock is
    monkeypatch.setattr(
        telemetry.loopclock, "cpu_clock",
        lambda: lambda: 0.5 * time.perf_counter(),
    )
    eng = _engine(params, max_slots=4, slot_buckets=(1, 2, 4))
    try:
        eng.submit(PROMPT[None, :], 10, timeout=120)
    finally:
        eng.close()
    exp = Exposition()
    telemetry.export(exp)
    families = promtext.parse(exp.render())
    wall = families["pygrid_serving_loop_seconds_total"]
    cpu = families["pygrid_serving_loop_cpu_seconds_total"]
    assert cpu.type == "counter" and "CPU" in cpu.help
    assert {s[1]["phase"] for s in cpu.samples} == {
        s[1]["phase"] for s in wall.samples
    }
    # the boundaries' readings telescope: the sum over the phases is the
    # clock's own gain from the first boundary to the last
    assert sum(s[2] for s in cpu.samples) == pytest.approx(
        0.5 * sum(s[2] for s in wall.samples), abs=0.01
    )
    instances = families["pygrid_serving_loop_phase_seconds"]
    assert instances.type == "histogram" and "instance" in instances.help
    sums = {
        s[1]["phase"]: s[2] for s in instances.samples
        if s[0].endswith("_sum")
    }
    for s in wall.samples:
        # the histogram's sum is written with six digits, the counter whole
        assert sums[s[1]["phase"]] == pytest.approx(s[2], rel=1e-5)
    for name in (
        "serving_loop_cpu_seconds_total", "serving_loop_phase_seconds",
        "process_gc_seconds",
    ):
        assert not telemetry.bus.family_help(name).startswith(
            "pygrid telemetry metric"
        )


def test_profile_carries_a_full_collection_beside_the_phases(params, tmp_path):
    import gc

    from jax.profiler import ProfileData

    eng = _engine(params, max_slots=4, slot_buckets=(1, 2, 4), fused=False)
    telemetry.gcwatch.start()
    try:
        eng.submit(PROMPT[None, :], 2, timeout=120)  # compile outside
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            future = eng.enqueue(PROMPT[None, :], 4)
            gc.collect()
            gc.collect(0)  # a young collection is no event
            future.result(120)
        finally:
            jax.profiler.stop_trace()
    finally:
        telemetry.gcwatch.stop()
        eng.close()
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    events = [
        ev
        for plane in ProfileData.from_file(str(xplane)).planes
        for line in plane.lines
        for ev in line.events
        if ev.name == "gc.full" or ev.name.startswith("engine.")
    ]
    assert {"engine.build", "engine.fetch", "engine.emit"} <= {
        ev.name for ev in events
    }
    (full,) = [ev for ev in events if ev.name == "gc.full"]
    assert full.duration_ns > 0
