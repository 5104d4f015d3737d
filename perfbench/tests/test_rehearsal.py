"""Every cell rehearsed on the CPU at a tiny size through the runner's own
functions (Pallas interpreted), the controls, the timed path broken
underneath, and the command's refusals. No device metric comes from here:
a traced run on the CPU has no accelerator plane and raises."""

import json
import math
import shutil
import subprocess
import sys
import time

import pytest

import run as runner
from lib import spec
from tiny import tiny_cell

BENCH = spec.load_benchmark()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def rehearse(name, seed, seconds=2.0, control=False, **hooks):
    cell = tiny_cell(name)
    if cell["traffic"]["kind"] == "train":
        hooks.setdefault("interpret", True)
    runner.T_START = time.time()
    return cell, runner.run_cell(cell, seed, seconds, False, control, **hooks)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_rehearses(name):
    said = []
    cell, run = rehearse(name, seed=2**31 + 77, log=said.append)
    assert run["verdict"]["correct"] is True, run["verdict"]
    # every closed loop says how much of its list it used; the rehearsal's
    # list (tiny.py) is long enough that no caller finds it spent
    used = [line for line in said if "list used" in line]
    assert len(used) == (cell["traffic"].get("loop") == "closed")
    assert all(0 < float(line.split("list used ")[1].split("%")[0]) < 50 for line in used)
    assert run["compiles_in_window"] == 0
    assert run["failed"] == 0 and run["attempted"] > 0
    line = runner.result_line(BENCH, cell, run, CPU, trace=False)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    # what main() prints: the numbers compared, each beside its limit, last
    full = runner.compared_last({**line, "compiles_in_window": 0}, run["verdict"])
    assert list(full)[-1] == "compared" and list(full)[:5] == list(line)
    assert full["compared"] and all(
        len(pair) == 2 and pair[0] <= pair[1] for pair in full["compared"].values()
    )
    want = {m["name"] for m in spec.metrics_for(BENCH, "end_to_end", name)}
    assert set(line["metrics"]) == want and "setup_s" in want
    for m in line["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    json.dumps(line)
    # the per-layer readers that need no device find their numbers
    ctx = dict(run["ctx"], device_kind="TPU v5 lite")
    layer = spec.read_per_layer(BENCH, name, ctx)
    named = {m["name"] for m in spec.metrics_for(BENCH, "per_layer", name)}
    missing = named - set(layer)
    assert all(m.startswith(("device_idle_pct", "hbm_peak_gb")) for m in missing), missing
    for key, m in layer.items():
        assert math.isfinite(m["value"])
        if "_pct" in key:
            assert 0.0 <= m["value"] <= 100.0
    # ... and nothing is printed under a device metric's name from a CPU
    with pytest.raises((TypeError, KeyError)):
        runner.result_line(BENCH, cell, run, CPU, trace=True)


def test_a_closed_loop_that_runs_dry_fails_the_run():
    from lib import serving

    cell = tiny_cell("batch-saturate")
    cell["traffic"]["cycles"] = 1  # 16 requests for 8 callers and 3 seconds
    said = []
    runner.T_START = time.time()
    with pytest.raises(serving.CellFailure, match="spent before the window closed"):
        runner.run_cell(cell, 2**31 + 78, 2.0, False, log=said.append)
    assert [line for line in said if "list used" in line] == [
        "perfbench: list used 100% of 16 requests"
    ]


def test_a_traced_run_keeps_the_counters_at_the_traced_stretch_s_two_ends(monkeypatch):
    from lib import serving, trace

    calls = []

    class FakeTracer:  # the CPU has no device plane to reduce: fake the tracer alone
        def __init__(self, out_dir):
            self.window_s = 0.0

        def start(self):
            calls.append("start")

        def stop(self):
            calls.append("stop")
            self.window_s = 1.0

        def result(self):
            return {"busy_s": 0.5, "window_s": 1.0, "breakdown": {}, "ops": {"op": [0.5, 3]}}

    scrape = serving.scrape
    monkeypatch.setattr(trace, "Tracer", FakeTracer)
    monkeypatch.setattr(serving, "scrape", lambda node: calls.append("scrape") or scrape(node))
    cell = tiny_cell("batch-saturate")
    runner.T_START = time.time()
    run = runner.run_cell(cell, 2**31 + 79, 3.0, True)
    # go, window opens, tracer starts, [t0 ... t1], tracer stops, window closes, end
    assert calls == ["scrape", "scrape", "start", "scrape", "scrape", "stop", "scrape", "scrape"]
    ctx = run["ctx"]
    key = 'pygrid_serving_tokens_total{model="bench"}'
    made = [ctx[k].get(key, 0.0) for k in ("snap_w0", "snap_t0", "snap_t1", "snap_w1")]
    assert made == sorted(made) and made[2] > made[1], made
    assert ctx["snap_t0"]["engine"]["model_id"] == "bench"
    assert ctx["trace"]["ops"] == {"op": [0.5, 3]}
    # an untraced run has neither
    assert {"snap_t0", "snap_t1"} <= set(ctx)
    _, plain = rehearse("batch-saturate", seed=2**31 + 79)
    assert plain["ctx"]["snap_t0"] is None and plain["ctx"]["snap_t1"] is None


def test_same_seed_same_traffic_open_loop_count():
    _, a = rehearse("chat-steady", seed=5)
    _, b = rehearse("chat-steady", seed=6)
    assert a["attempted"] == b["attempted"] == 12  # 2 s at 6 requests/s


def test_serving_control_and_broken_path_come_out_not_correct():
    # the int8 control, read at the same prompts and served tokens
    cell, run = rehearse("chat-steady", seed=7, control=True)
    check = cell["traffic"]["check"]
    assert run["verdict"]["correct"] is True
    assert run["verdict"]["control"]["served_gap_max"] > check["gap_max_limit"]

    def alter_tokens(engine):
        emit = engine._emit
        engine._emit = lambda slot, row, tok: emit(slot, row, (tok + 1) % 128)

    cell, broken = rehearse("chat-steady", seed=7, engine_hook=alter_tokens)
    assert broken["verdict"]["correct"] is False
    assert runner.result_line(BENCH, cell, broken, CPU, False)["correct"] is False


def test_training_control_and_broken_step_come_out_not_correct():
    cell, run = rehearse("fed-silo-docs", seed=11, control=True)
    check = cell["traffic"]["check"]
    assert run["verdict"]["correct"] is True
    assert run["verdict"]["control"]["param_change_gap_worst_leaf"] > check["param_change_gap_limit"]

    def state_unchanged(rounds_fn):
        def broken(params, X, y, lr):
            _new, losses, accs = rounds_fn(params, X, y, lr)
            return list(params), losses, accs
        return broken

    cell, broken = rehearse("fed-silo-docs", seed=11, step_hook=state_unchanged)
    assert broken["verdict"]["correct"] is False
    over = {c["name"] for c in broken["verdict"]["compared"] if c["value"] > c["limit"]}
    assert "param_change_gap_worst_leaf" in over and "loss_not_falling" in over


def _command(cwd, env_platform="cpu"):
    import os

    env = dict(os.environ, JAX_PLATFORMS=env_platform)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "fed-device-short",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_the_command_refuses_a_cpu_by_name():
    got = _command(spec.ROOT)
    assert got.returncode != 0 and got.stdout == ""
    assert "'cpu'" in got.stderr and "tpu" in got.stderr


def test_the_command_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    got = _command(tmp_path, env_platform="tpu,cpu")
    assert got.returncode != 0 and got.stdout == ""
    assert "pygrid_tpu" in got.stderr
