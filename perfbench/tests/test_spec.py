"""BENCHMARK.json against the driver's rules, every file found by name, and
the data-driven promise: one of each kind added as new files only."""

import hashlib
import json
import shutil
from pathlib import Path

from lib import spec


def test_rules_hold_and_every_named_file_is_found():
    bench = spec.load_benchmark()
    assert spec.check_rules(bench) == []
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    for w in bench["workloads"]:
        cell = spec.cell(bench, w["name"])
        assert cell["traffic"]["kind"] in ("serve", "train")
        assert cell["chips"] == 1
        for key in cell["config"]["reduced"]:
            assert key in cell["config"]
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"]))
    for c in bench["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert c["source"] in cfg["source"]


def test_pct_metrics_are_percent_and_names_say_so():
    bench = spec.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "_pct" in m["name"]:
            assert m["unit"] == "%", m
        assert "share" not in m["name"], m


def _digest(root: Path) -> dict:
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts
    }


def test_one_of_each_is_added_as_new_files_only(tmp_path):
    shutil.copytree(spec.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(tmp_path / "perfbench")
    bench = spec.load_benchmark()
    # a configuration, a traffic mix, a per-layer metric: three new files
    cfg = json.loads((tmp_path / "perfbench/configs/cerebras-gpt-111m.json").read_text())
    cfg.update(n_layer=18, n_embd=1088, n_head=17, n_inner=4352,
               source="https://huggingface.co/cerebras/Cerebras-GPT-256M")
    (tmp_path / "perfbench/configs/cerebras-gpt-256m.json").write_text(json.dumps(cfg))
    mix = json.loads((tmp_path / "perfbench/traffic/chat-steady.json").read_text())
    mix.update(arrivals="gamma", arrival_cv=3.0)
    (tmp_path / "perfbench/traffic/chat-burst.json").write_text(json.dumps(mix))
    (tmp_path / "perfbench/metrics/requests_answered.py").write_text(
        "def read(ctx):\n    return sum(bool(r.get('ok')) for r in ctx.get('results', [])) or None\n"
    )
    # ... and entries in BENCHMARK.json
    bench["configs"].append({
        "name": "cerebras-gpt-256m", "source": cfg["source"],
        "file": "perfbench/configs/cerebras-gpt-256m.json", "reduced": cfg["reduced"],
        "why": "a third size of the same family",
    })
    bench["workloads"].append({
        "name": "chat-burst", "config": "cerebras-gpt-256m", "traffic": "chat-burst",
        "chips": 1, "why": "bursts",
    })
    bench["per_layer"].append({
        "name": "requests_answered", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "door", "moves": "norm_latency_p50",
        "workloads": ["chat-burst"],
    })
    for m in bench["end_to_end"]:
        if m["name"].startswith("norm_latency"):
            m["workloads"].append("chat-burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    again = spec.load_benchmark(tmp_path)
    assert spec.check_rules(again, tmp_path) == []
    cell = spec.cell(again, "chat-burst", tmp_path)
    assert cell["config"]["n_embd"] == 1088 and cell["traffic"]["arrivals"] == "gamma"
    from lib import traffic

    built = traffic.build(cell["traffic"], 3, 20.0)
    assert any(r["counted"] for r in built["requests"])
    ctx = {"kind": "serve", "results": [{"ok": True}, {"ok": False}, {"ok": True}]}
    got = spec.read_per_layer(
        {**again, "per_layer": [again["per_layer"][-1]]}, "chat-burst", ctx, tmp_path
    )
    assert got == {"requests_answered": {"value": 2.0, "unit": "requests"}}
    after = _digest(tmp_path / "perfbench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 3
