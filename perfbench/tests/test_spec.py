"""BENCHMARK.json against the driver's rules, every file found by name, and
the data-driven promise: one of each kind added as new files only, an
architecture among them."""

import hashlib
import json
import re
import shutil
from pathlib import Path

import pytest

from lib import spec

#: an adapter of another architecture, written into the temporary copy: an
#: embedding, one gated matrix, a tied head. None of GPT-2's keys.
TOY_ADAPTER = '''
import jax, jax.numpy as jnp
from lib import reference

def _draw(seed, cfg, dtype):
    k, (V, d) = reference.seed_key(seed), (cfg["vocab_size"], cfg["width"])
    w = {"embed": jax.random.normal(jax.random.fold_in(k, 0), (V, d)) * d**-0.5,
         "gate": jax.random.normal(jax.random.fold_in(k, 1), (d, d)) * d**-0.5,
         "up": jax.random.normal(jax.random.fold_in(k, 2), (d, d)) * d**-0.5}
    return {n: x.astype(dtype) for n, x in w.items()}

def make_weights(seed, cfg, dtype):
    return {n: x.astype(jnp.float32) for n, x in _draw(seed, cfg, dtype).items()}

def make_program_params(seed, cfg, dtype):
    return tuple(_draw(seed, cfg, dtype).values())

def to_reference(params, cfg):
    return dict(zip(("embed", "gate", "up"), (jnp.asarray(x, jnp.float32) for x in params)))

def logits(w, tokens, cfg, dtype=jnp.float32):
    with jax.default_matmul_precision("highest"):
        e = w["embed"].astype(dtype)
        h = jnp.cumsum(e[tokens], axis=1)  # causal: a position sees those before it
        h = h + jax.nn.silu(h @ w["gate"].astype(dtype)) * (h @ w["up"].astype(dtype))
        return h @ e.T

def served_gaps(w, cfg, request, answer, pad_to, control=None):
    return reference.served_gaps(logits, w, cfg, request["prompt"], answer["tokens"], pad_to, control)

def control_weights(w):
    return {n: reference.int8_values(x, -1 if n == "embed" else -2) for n, x in w.items()}

def leaf_norms(w):
    return reference.leaf_norms(w, ())

def hosted(cfg, params):
    raise NotImplementedError("the toy has no program to host")

def train_loss(cell, interpret):
    raise NotImplementedError("the toy has no program to train")

def train_flops_per_token(cfg, seq_len):
    return 6.0 * (2 * cfg["width"] ** 2 + cfg["vocab_size"] * cfg["width"])

def tiny(cfg):
    return {}
'''


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
    # (and below a fourth, an architecture with a configuration and a cell)
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
    (tmp_path / "perfbench/models/toy.py").write_text(TOY_ADAPTER)
    toy = {"source": "none: a self-test's toy", "model_type": "toy", "width": 16,
           "vocab_size": 64, "reduced": []}
    (tmp_path / "perfbench/configs/toy-16.json").write_text(json.dumps(toy))
    bench["configs"].append({
        "name": "toy-16", "source": toy["source"], "file": "perfbench/configs/toy-16.json",
        "reduced": [], "why": "another architecture",
    })
    bench["workloads"].append({
        "name": "toy-steady", "config": "toy-16", "traffic": "chat-steady",
        "chips": 1, "why": "the steady mix on another architecture",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"].startswith("norm_latency"):
            m["workloads"] += ["chat-burst", "toy-steady"]
        elif m["name"] == "queue_wait_mean_ms":
            m["workloads"].append("toy-steady")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    again = spec.load_benchmark(tmp_path)
    assert spec.check_rules(again, tmp_path) == []
    cell = spec.cell(again, "chat-burst", tmp_path)
    assert cell["config"]["n_embd"] == 1088 and cell["traffic"]["arrivals"] == "gamma"
    assert callable(spec.load_model(cell["config"]["model_type"], tmp_path, again).logits)
    from lib import traffic

    built = traffic.build(cell["traffic"], 3, 20.0)
    assert any(r["counted"] for r in built["requests"])
    ctx = {"kind": "serve", "results": [{"ok": True}, {"ok": False}, {"ok": True}]}
    got = spec.read_per_layer(
        {**again, "per_layer": [again["per_layer"][-1]]}, "chat-burst", ctx, tmp_path
    )
    assert got == {"requests_answered": {"value": 2.0, "unit": "requests"}}
    # the foreign architecture: found by its model_type, and the generic
    # reference runs through its logits
    import jax.numpy as jnp
    import numpy as np

    from lib import reference

    cfg = spec.cell(again, "toy-steady", tmp_path)["config"]
    assert not set(cfg) & {"n_embd", "n_inner", "n_head", "n_layer", "n_positions"}
    model = spec.load_model(cfg["model_type"], tmp_path, again)
    w = model.make_weights(5, cfg, "bfloat16")
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 64, (1, 9))
    seq = np.concatenate([prompt, np.zeros((1, 7), np.int64)], -1)
    served = []
    for t in range(9, 14):  # greedy, through the same logits
        served.append(int(np.asarray(model.logits(w, jnp.asarray(seq), cfg))[0, t - 1].argmax()))
        seq[0, t] = served[-1]
    request = {"prompt": prompt, "n_new": 5, "fields": {}}
    answer = {"tokens": np.asarray([served])}
    gaps, cgaps = model.served_gaps(w, cfg, request, answer, 16, model.control_weights(w))
    assert gaps.shape == cgaps.shape == (5,) and float(np.abs(gaps).max()) < 1e-5
    direct, _ = reference.served_gaps(model, w, cfg, prompt, answer["tokens"], 16)
    assert np.array_equal(gaps, direct)  # the module or its logits: one comparison
    X = jnp.asarray(rng.integers(0, 64, (4, 12)))
    w3, losses = reference.sgd_steps(model, w, X, jnp.roll(X, -1, -1), 0.5, cfg, 3, 2)
    assert len(losses) == 3 and losses[2] < losses[1] < losses[0]
    moved = model.leaf_norms({k: w3[k] - w[k] for k in w})
    assert set(moved) == set(w) and all(v[0] > 0 for v in moved.values())

    after = _digest(tmp_path / "perfbench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 5  # ... and the toy's adapter and configuration


def test_a_model_type_with_no_adapter_names_the_missing_file(tmp_path):
    with pytest.raises(spec.SpecError, match=r"perfbench/models/mamba9\.py"):
        spec.load_model("mamba9")
    # ... and the rules say so of a configuration that names it
    bench = spec.load_benchmark()
    shutil.copytree(spec.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = tmp_path / bench["configs"][0]["file"]
    path.write_text(json.dumps(dict(json.loads(path.read_text()), model_type="mamba9")))
    assert [b for b in spec.check_rules(bench, tmp_path) if "models/mamba9.py" in b]
    (tmp_path / "perfbench/models/mamba9.py").write_text("def logits(w, tokens, cfg):\n    pass\n")
    (bad,) = spec.check_rules(bench, tmp_path)
    missing = bad.rsplit("does not define", 1)[1]
    assert "make_weights" in bad and "tiny" in bad and "logits" not in missing
    # eleven names, the comparison that decides a served cell's ``correct``
    # among them: an adapter without it fails as one without ``logits`` does
    assert len(spec.MODEL_CONTRACT) == len(set(spec.MODEL_CONTRACT)) == 11
    assert "served_gaps" in missing
    assert {n.strip() for n in missing.split(",")} == set(spec.MODEL_CONTRACT) - {"logits"}


def test_the_runners_name_no_key_of_a_block_and_import_no_model():
    pattern = re.compile(
        r"n_embd|n_inner|n_head|n_layer|n_positions|LAYER_LEAVES|pygrid_tpu\.models|pallas_attention"
    )
    files = [spec.BENCH_DIR / "run.py", *sorted((spec.BENCH_DIR / "lib").glob("*.py"))]
    assert len(files) > 10
    hits = [
        f"{p.name}:{i}: {line}" for p in files
        for i, line in enumerate(p.read_text().splitlines(), 1) if pattern.search(line)
    ]
    assert hits == []
