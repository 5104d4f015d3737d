"""The observability engine (PR 5): per-jit-callsite profiler, flight
recorder crash dumps, and burn-rate SLOs — plus the engine-failure →
crash-dump integration the acceptance criteria name explicitly."""

from __future__ import annotations

import json
import math
import os
import time

import numpy as np
import pytest

from pygrid_tpu import telemetry
from pygrid_tpu.telemetry import profiler, recorder, slo
from pygrid_tpu.telemetry.bus import TelemetryBus
from pygrid_tpu.telemetry.slo import Objective, SLOEngine


@pytest.fixture(autouse=True)
def _clean(tmp_path, monkeypatch):
    monkeypatch.setenv("PYGRID_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setenv("PYGRID_FLIGHT_MIN_INTERVAL_S", "0")
    telemetry.reset()
    recorder.reset()
    profiler.reset()
    yield
    telemetry.reset()
    recorder.reset()
    profiler.reset()


# ── profiler ────────────────────────────────────────────────────────────


class _FakeJitted:
    """A jit-shaped callable with the ``_cache_size`` hook: the first
    call per distinct arg 'compiles' (grows the cache), the rest hit."""

    def __init__(self) -> None:
        self._seen: set = set()

    def __call__(self, x):
        self._seen.add(x)
        return x

    def _cache_size(self) -> int:
        return len(self._seen)


def test_wrap_splits_compile_from_execute():
    fn = profiler.wrap(_FakeJitted(), kind="decode", bucket=4, model_id="m")
    fn("a")          # compile (cache 0 → 1)
    fn("a")          # hit
    fn("b")          # compile (1 → 2)
    fn("a")          # hit
    (row,) = [
        r for r in profiler.programs_snapshot() if r["model"] == "m"
    ]
    assert row["program"] == "decode/4"
    assert row["compiles"] == 2
    assert row["hits"] == 2
    assert row["compile_ms"] >= 0 and row["execute_ms_total"] >= 0
    assert row["execute_ms_mean"] is not None
    # the split feeds the bus histograms too
    hists = telemetry.histograms()
    assert hists[
        ("profiler_compile_seconds", (("kind", "decode"),))
    ]["count"] == 2
    assert hists[
        ("profiler_execute_seconds", (("kind", "decode"),))
    ]["count"] == 2


def test_wrap_preserves_cache_size_hook_and_result():
    jitted = _FakeJitted()
    fn = profiler.wrap(jitted, kind="prefill", bucket=16)
    assert fn("payload") == "payload"
    assert fn._cache_size() == 1  # trace_count() keeps working


def test_wrap_disabled_is_identity(monkeypatch):
    monkeypatch.setenv("PYGRID_PROFILER", "off")
    fn = lambda x: x  # noqa: E731
    assert profiler.wrap(fn, kind="decode", bucket=1) is fn


def test_snapshot_cost_attribution_for_jitted_program():
    """XLA cost attribution: a REAL jitted program's snapshot row gains
    flops / bytes-accessed (from avals captured at first call — never
    the buffers themselves) and rows rank by total bytes accessed."""
    import jax
    import jax.numpy as jnp

    fn = profiler.wrap(
        jax.jit(lambda a, b: a @ b, donate_argnums=(0,)),
        kind="decode", bucket=2, model_id="cost",
    )
    x = jnp.ones((8, 8), jnp.float32)
    fn(x, jnp.ones((8, 8), jnp.float32))
    rows = [
        r
        for r in profiler.programs_snapshot(include_cost=True)
        if r["model"] == "cost"
    ]
    (row,) = rows
    assert row["flops"] and row["flops"] > 0
    assert row["bytes_accessed"] and row["bytes_accessed"] > 0
    assert row["bytes_accessed_total"] >= row["bytes_accessed"]
    # second snapshot serves the cached analysis (no re-lower)
    (again,) = [
        r
        for r in profiler.programs_snapshot(include_cost=True)
        if r["model"] == "cost"
    ]
    assert again["flops"] == row["flops"]
    # the plain snapshot keeps its stable (model, kind, bucket) order
    plain = [
        r for r in profiler.programs_snapshot() if r["model"] == "cost"
    ]
    assert "flops" not in plain[0]


def test_snapshot_cost_absent_for_non_jitted_wrappers():
    fn = profiler.wrap(_FakeJitted(), kind="decode", bucket=9, model_id="nc")
    fn("x")
    (row,) = [
        r
        for r in profiler.programs_snapshot(include_cost=True)
        if r["model"] == "nc"
    ]
    assert row["flops"] is None and row["bytes_accessed"] is None


def test_cost_disabled_by_env(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("PYGRID_PROFILER_COST", "off")
    fn = profiler.wrap(
        jax.jit(lambda a: a + 1), kind="decode", bucket=3, model_id="nc2",
    )
    fn(jnp.ones((4,), jnp.float32))
    (row,) = [
        r
        for r in profiler.programs_snapshot(include_cost=True)
        if r["model"] == "nc2"
    ]
    assert row["flops"] is None


def test_memory_sampler_shape_on_this_backend():
    # CPU backends report no memory_stats → empty list; an accelerator
    # yields dicts with the three byte gauges. Either way: no raise.
    for sample in profiler.DeviceMemorySampler.sample_once():
        assert {"device", "platform", "bytes_in_use"} <= set(sample)


# ── flight recorder ─────────────────────────────────────────────────────


def test_ring_is_bounded_and_ordered():
    rec = recorder.FlightRecorder(ring_size=3)
    for i in range(5):
        rec.note("tick", i=i)
    assert [e["i"] for e in rec.ring()] == [2, 3, 4]


def test_redaction_is_structural():
    payload = {
        "auth_token": "secret-jwt",
        "request_key": "abc",
        "nested": [{"password": "hunter2", "ok": 1}],
        "blob": b"\x00" * 100,
        "big": "x" * 5000,
        "weird": object(),
    }
    out = recorder.redact(payload)
    assert out["auth_token"] == "[redacted]"
    assert out["request_key"] == "[redacted]"
    assert out["nested"][0]["password"] == "[redacted]"
    assert out["nested"][0]["ok"] == 1
    assert out["blob"] == "<100 bytes>"
    assert len(out["big"]) < 5000
    json.dumps(out)  # everything left is JSON-serializable


def test_dump_writes_json_with_ring_events_and_stats_providers():
    class Provider:
        def stats(self):
            return [{"queue_depth": 3, "token": "leak-me"}]

    provider = Provider()
    recorder.register_stats_provider("serving", provider)
    recorder.note("engine.fail_all", model="m")
    telemetry.record("span", name="handler")
    path = recorder.dump("unit_test", snapshot={"x": 1}, error="boom")
    data = json.loads(open(path, encoding="utf-8").read())
    assert data["reason"] == "unit_test"
    assert data["error"] == "boom"
    assert data["snapshot"] == {"x": 1}
    assert any(e["kind"] == "engine.fail_all" for e in data["ring"])
    assert any(e.get("event") == "span" for e in data["events"])
    assert data["stats"]["serving"][0]["queue_depth"] == 3
    assert data["stats"]["serving"][0]["token"] == "[redacted]"
    assert telemetry.counters()[
        ("flightrecorder_dumps_total", (("reason", "unit_test"),))
    ] == 1


def test_dump_rate_limited_per_reason_and_force_overrides(monkeypatch):
    monkeypatch.setenv("PYGRID_FLIGHT_MIN_INTERVAL_S", "3600")
    assert recorder.RECORDER.should_dump("storm")  # nothing written yet
    assert recorder.dump("storm") is not None
    # the cheap peek agrees with dump() and changes no state
    assert not recorder.RECORDER.should_dump("storm")
    assert recorder.dump("storm") is None          # suppressed
    assert recorder.dump("other_reason") is not None  # per-reason limit
    assert recorder.dump("storm", force=True) is not None


def test_malformed_env_knobs_do_not_crash(monkeypatch):
    monkeypatch.setenv("PYGRID_PROFILER_INTERVAL_S", "not-a-number")
    sampler = profiler.DeviceMemorySampler()
    assert sampler.interval_s == profiler.DEFAULT_SAMPLE_INTERVAL_S
    monkeypatch.setenv("PYGRID_FLIGHT_MIN_INTERVAL_S", "garbage")
    assert recorder.RECORDER._min_interval() == (
        recorder.DEFAULT_MIN_INTERVAL_S
    )


def test_sampler_refcount_survives_disabled_holder(monkeypatch):
    sampler = profiler.DeviceMemorySampler(interval_s=60)
    sampler.start()                      # enabled holder: thread runs
    thread = sampler._thread
    assert thread is not None and thread.is_alive()
    monkeypatch.setenv("PYGRID_PROFILER", "off")
    sampler.start()                      # disabled holder
    sampler.stop()                       # disabled holder's cleanup...
    assert thread.is_alive()             # ...must not kill the thread
    monkeypatch.delenv("PYGRID_PROFILER")
    sampler.stop()                       # last holder: thread stops
    thread.join(timeout=2)
    assert not thread.is_alive()


def test_dump_dir_pruned_per_reason(monkeypatch, tmp_path):
    monkeypatch.setenv("PYGRID_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setattr(recorder, "MAX_DUMPS", 3)
    # a flood of one reason must not evict another reason's evidence
    crash = recorder.dump("engine_fail_all")
    for _ in range(5):
        recorder.dump("operator", force=True)
    dumps = sorted(f for f in os.listdir(tmp_path) if f.startswith("flight-"))
    assert os.path.basename(crash) in dumps  # the crash dump survived
    assert len([f for f in dumps if "operator" in f]) == 3


def test_off_switch_silences_note_and_auto_dump(monkeypatch):
    monkeypatch.setenv("PYGRID_FLIGHT", "off")
    recorder.note("ignored")
    assert recorder.ring() == []
    assert recorder.dump("auto") is None
    # the operator's explicit dump still works — asking IS consent
    assert recorder.dump("operator", force=True) is not None


# ── SLO engine ──────────────────────────────────────────────────────────


def _bus_with(values, family="lat_seconds", **labels):
    bus = TelemetryBus()
    for v in values:
        bus.observe(family, v, **labels)
    return bus


def test_compliance_counts_at_bucket_resolution():
    bus = _bus_with([0.005] * 15 + [5.0] * 5)
    eng = SLOEngine(
        [Objective("lat", "lat_seconds", threshold_s=0.01, target=0.9)],
        windows=(60.0,),
        source=bus,
    )
    (row,) = eng.evaluate(now=0.0)
    assert row["events"] == 20
    assert row["compliance"] == pytest.approx(0.75)
    # below-target compliance alone is ticket-worthy, never a page
    assert row["status"] == "warn"


def test_page_burn_needs_minimum_window_traffic():
    # one slow request in an otherwise-idle window burns at 100× but
    # must NOT page — below MIN_EVENTS the verdict degrades to warn
    bus = TelemetryBus()
    eng = SLOEngine(
        [Objective("lat", "lat_seconds", threshold_s=0.01, target=0.99)],
        windows=(60.0, 600.0),
        source=bus,
    )
    eng.tick(now=0.0)
    bus.observe("lat_seconds", 5.0)
    (row,) = eng.evaluate(now=30.0)
    assert row["events"] == 1
    assert row["burn"]["1m"] > slo.PAGE_BURN  # burning hard...
    assert row["status"] == "warn"            # ...but 1 event ≠ a page
    assert eng.healthy()  # deep /healthz stays 200


def test_breach_clears_when_burn_windows_clear():
    """A past incident must not latch breach: once the windows hold
    only good traffic again, the objective reads warn (compliance still
    dented) — deep health recovers with the service."""
    bus = TelemetryBus()
    obj = Objective("lat", "lat_seconds", threshold_s=0.01, target=0.99)
    eng = SLOEngine([obj], windows=(60.0, 600.0), source=bus)
    eng.tick(now=0.0)
    for _ in range(50):
        bus.observe("lat_seconds", 5.0)  # the incident
    (row,) = eng.evaluate(now=30.0)
    assert row["status"] == "breach"
    # an hour later: windows have rolled past the incident and hold
    # only fresh good traffic
    for _ in range(50):
        bus.observe("lat_seconds", 0.001)
    eng.tick(now=3620.0)
    (row,) = eng.evaluate(now=3650.0)
    assert row["compliance"] < obj.target  # the dent remains visible
    assert row["status"] == "warn"         # but nobody gets paged
    assert eng.healthy()


def test_burn_rates_over_windows_and_status_transitions():
    bus = TelemetryBus()
    obj = Objective("lat", "lat_seconds", threshold_s=0.01, target=0.9)
    eng = SLOEngine([obj], windows=(60.0, 600.0), source=bus)
    # minute 0: 100 good events land inside the first window → healthy
    eng.tick(now=0.0)
    for _ in range(100):
        bus.observe("lat_seconds", 0.001)
    (row,) = eng.evaluate(now=1.0)
    assert row["status"] == "ok"
    assert row["burn"]["1m"] == pytest.approx(0.0)
    # 50 bad land in the same window: bad-fraction 50/150 over the
    # window / budget 0.1 = burn 3.33 — budget on fire but below the
    # 14.4 page threshold → warn (compliance 0.67 dents it further,
    # but below-target compliance alone never pages)
    for _ in range(50):
        bus.observe("lat_seconds", 9.0)
    (row,) = eng.evaluate(now=30.0)
    assert row["burn"]["1m"] == pytest.approx(50 / 150 / 0.1, rel=0.01)
    assert row["compliance"] == pytest.approx(100 / 150)
    assert row["status"] == "warn"
    assert eng.healthy()  # warn does not fail deep health


def test_warn_when_budget_burning_but_compliance_still_met():
    bus = TelemetryBus()
    obj = Objective("lat", "lat_seconds", threshold_s=0.01, target=0.9)
    eng = SLOEngine([obj], windows=(60.0, 600.0), source=bus)
    for _ in range(1000):
        bus.observe("lat_seconds", 0.001)  # a long healthy history
    eng.tick(now=0.0)
    for _ in range(50):
        bus.observe("lat_seconds", 0.001)
    for _ in range(50):
        bus.observe("lat_seconds", 9.0)
    (row,) = eng.evaluate(now=30.0)
    # window: 50 bad / 100 → burn 5; lifetime compliance 1050/1100 ≈
    # 0.95 still over the 0.9 target → warn, not breach
    assert row["burn"]["1m"] == pytest.approx(5.0, rel=0.01)
    assert row["compliance"] > obj.target
    assert row["status"] == "warn"


def test_page_level_burn_breaches_before_compliance_falls():
    bus = TelemetryBus()
    # a tight 0.99 target: budget 0.01, so a half-bad window burns at
    # 50× — far past the 14.4 page threshold — while lifetime
    # compliance is still above target
    obj = Objective("lat", "lat_seconds", threshold_s=0.01, target=0.99)
    eng = SLOEngine([obj], windows=(60.0, 600.0), source=bus)
    for _ in range(10000):
        bus.observe("lat_seconds", 0.001)
    eng.tick(now=0.0)
    for _ in range(50):
        bus.observe("lat_seconds", 0.001)
    for _ in range(50):
        bus.observe("lat_seconds", 9.0)
    (row,) = eng.evaluate(now=30.0)
    assert row["compliance"] > obj.target
    assert row["burn"]["1m"] >= slo.PAGE_BURN
    assert row["status"] == "breach"


def test_no_traffic_is_no_data_not_breach():
    eng = SLOEngine(
        [Objective("lat", "lat_seconds", 0.01)],
        windows=(60.0,),
        source=TelemetryBus(),
    )
    (row,) = eng.evaluate(now=0.0)
    assert row["status"] == "no_data"
    assert row["compliance"] is None
    assert eng.healthy()


def test_label_filter_selects_series():
    bus = TelemetryBus()
    bus.observe("node_event_seconds", 9.0, event="model-centric/report")
    bus.observe("node_event_seconds", 0.001, event="socket-ping")
    eng = SLOEngine(
        [
            Objective(
                "report", "node_event_seconds", threshold_s=0.5,
                target=0.99, labels={"event": "model-centric/report"},
            )
        ],
        windows=(60.0,),
        source=bus,
    )
    (row,) = eng.evaluate(now=0.0)
    assert row["events"] == 1  # the ping series is filtered out
    assert row["compliance"] == 0.0


def test_group_burn_isolates_the_slow_node():
    bus = TelemetryBus()
    obj = Objective(
        "heartbeat_rtt", "heartbeat_rtt_seconds", threshold_s=0.5,
        target=0.5, group_by="node",
    )
    eng = SLOEngine([obj], windows=(60.0, 600.0), source=bus)
    eng.tick(now=0.0)
    for _ in range(10):
        bus.observe("heartbeat_rtt_seconds", 0.001, node="fast", transport="http")
        bus.observe("heartbeat_rtt_seconds", 9.0, node="slow", transport="http")
    eng.tick(now=30.0)
    burn = eng.group_burn("heartbeat_rtt", now=30.0)
    assert burn["fast"] == pytest.approx(0.0)
    assert burn["slow"] == pytest.approx(2.0)  # all bad / 0.5 budget
    # min_events filters thin groups: one slow heartbeat from a fresh
    # node is no verdict (the monitor's degraded guard)
    bus.observe("heartbeat_rtt_seconds", 9.0, node="fresh", transport="http")
    eng.tick(now=31.0)
    filtered = eng.group_burn("heartbeat_rtt", now=31.0, min_events=5)
    assert "fresh" not in filtered
    assert "slow" in filtered


def test_env_knobs_shape_default_objectives(monkeypatch):
    monkeypatch.setenv("PYGRID_SLO_TTFT_S", "0.25")
    monkeypatch.setenv("PYGRID_SLO_TTFT_TARGET", "0.5")
    monkeypatch.setenv("PYGRID_SLO_WINDOWS", "120,2400")
    objectives = {o.name: o for o in slo.node_objectives()}
    assert objectives["serving_ttft"].threshold_s == 0.25
    assert objectives["serving_ttft"].target == 0.5
    assert slo.windows_from_env() == (120.0, 2400.0)


def test_export_gauges_render_through_strict_parser():
    from pygrid_tpu.telemetry import promtext
    from pygrid_tpu.utils.metrics import Exposition

    bus = _bus_with([0.001] * 5, family="lat_seconds")
    eng = SLOEngine(
        [Objective("lat", "lat_seconds", 0.01)], windows=(60.0,),
        source=bus,
    )
    exp = Exposition()
    eng.export(exp)
    families = promtext.parse(exp.render())
    assert families["pygrid_slo_compliance"].samples[0][2] == 1.0


def test_handler_exception_reaches_ring_and_dump(tmp_path, monkeypatch):
    """An exception LEAKING past a WS handler must land on the
    flight-recorder ring AND trigger a dump — through the module-level
    ``telemetry.recorder`` aliases the dispatch path actually uses."""
    import json as _json
    import time as _time

    from pygrid_tpu.node import NodeContext
    from pygrid_tpu.node.events import Connection, route_requests

    ctx = NodeContext("flight-test")
    try:
        # list-models with no session: _authenticated raises out of the
        # handler (no try inside) — the dispatch-boundary leak path
        response = _json.loads(
            route_requests(
                ctx, _json.dumps({"type": "list-models"}), Connection(ctx)
            )
        )
        assert "error" in response  # the typed-error contract held
        notes = [
            e for e in recorder.ring() if e["kind"] == "handler.exception"
        ]
        assert notes and notes[0]["event"] == "list-models"
        # the dump writes on a side thread — wait for it
        deadline = _time.monotonic() + 10
        while _time.monotonic() < deadline:
            dumps = [
                f for f in os.listdir(tmp_path / "flight")
                if "handler_exception" in f
            ] if (tmp_path / "flight").exists() else []
            if dumps:
                break
            _time.sleep(0.05)
        assert dumps, "no handler-exception dump written"
        data = json.loads(
            open(tmp_path / "flight" / dumps[0], encoding="utf-8").read()
        )
        assert data["snapshot"]["event"] == "list-models"
    finally:
        ctx.serving.close()


# ── engine failure → crash dump (the acceptance-criteria integration) ───


def test_engine_fail_all_writes_crash_dump_with_request_ids(tmp_path):
    import jax

    from pygrid_tpu.models import transformer as T
    from pygrid_tpu.serving import EngineConfig, GenerationEngine

    cfg = T.TransformerConfig(
        vocab=17, d_model=8, n_heads=2, n_layers=1, d_ff=16, max_len=16
    )
    engine = GenerationEngine(
        cfg,
        T.init(jax.random.PRNGKey(0), cfg),
        EngineConfig(max_slots=2, slot_buckets=(1, 2), min_prompt_bucket=4),
        model_id="crashy",
    )
    try:
        future = engine.enqueue(np.array([[1, 2, 3]]), n_new=4)
        request_id = None
        with engine._lock:
            rows = [r for r in engine._slots if r is not None]
            rows.extend(engine._queue)
            request_id = rows[0].pending.request_id
        engine._fail_all(RuntimeError("injected device loss"))
        with pytest.raises(Exception, match="injected device loss"):
            future.result(timeout=5)
    finally:
        engine.close()
    # the dump exists, round-trips through json.loads, and names the
    # failing request ids + the engine's last slot/queue state
    dumps = sorted(
        f for f in os.listdir(tmp_path / "flight")
        if "engine_fail_all" in f
    )
    assert dumps, "no crash dump written"
    data = json.loads(
        open(tmp_path / "flight" / dumps[-1], encoding="utf-8").read()
    )
    assert data["reason"] == "engine_fail_all"
    assert "injected device loss" in data["error"]
    snap = data["snapshot"]
    assert snap["model_id"] == "crashy"
    assert request_id in snap["failed_request_ids"]
    assert isinstance(snap["slots"], list)
    assert telemetry.counters()[
        ("flightrecorder_dumps_total", (("reason", "engine_fail_all"),))
    ] == 1
