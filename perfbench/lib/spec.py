"""Finding the benchmark's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is one JSON file
(``configs/<config>.json`` via the entry's ``file``, ``traffic/<traffic>.json``),
each per-layer metric is one reader, ``metrics/<name>.py``, and each
architecture one adapter, ``models/<model_type>.py``, named by the
configuration's ``model_type`` (its contract: ``models/gpt2.py``). Adding a
cell, a configuration, a mix, a metric or an architecture adds files and
entries and edits nothing that exists: nothing here switches on a name.

The comparison that decides a serving cell's ``correct`` is the adapter's
too (``served_gaps``): the runner samples the requests and hands each over
as it was sent and answered, named fields and all, and knows nothing of
how a token came to be. A causal decoder's is one call of
``lib/reference.served_gaps`` on its ``logits``; any other adapter replays
the states the answer names, in float32 at ``highest`` precision, and
returns one gap a served token.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
#: what an architecture's adapter defines (``models/gpt2.py`` says what each is)
MODEL_CONTRACT = (
    "make_weights", "make_program_params", "to_reference", "logits",
    "served_gaps", "control_weights", "leaf_norms", "hosted", "train_loss",
    "train_flops_per_token", "tiny",
)


class SpecError(Exception):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _read_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    return json.loads(path.read_text())


def cell(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration and traffic files read:
    ``{"name", "chips", "config": {...}, "traffic": {...}}``."""
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SpecError(f"no workload {name!r} in BENCHMARK.json (has: {known})")
    cfg_entry = next(
        (c for c in bench["configs"] if c["name"] == entry["config"]), None
    )
    if cfg_entry is None:
        raise SpecError(f"workload {name!r} names no known config")
    bench_dir = root / bench["paths"][0]
    return {
        "name": name,
        "chips": entry["chips"],
        "config_name": entry["config"],
        "config": _read_json(root / cfg_entry["file"], f"config {entry['config']}"),
        "traffic": _read_json(
            bench_dir / "traffic" / f"{entry['traffic']}.json",
            f"traffic {entry['traffic']}",
        ),
    }


def metrics_for(bench: dict, group: str, cell_name: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports:
    those that list it under ``workloads``, and those with no such key."""
    return [
        m for m in bench[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def _load(kind: str, name: str, what: str, needs, root: Path, bench: dict | None):
    """The module ``<paths[0]>/<kind>/<name>.py``, which defines ``needs``."""
    bench = bench or load_benchmark(root)
    path = root / bench["paths"][0] / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"{what} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_" + re.sub(r"\W", "_", name), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    missing = [n for n in needs if not callable(getattr(module, n, None))]
    if missing:
        raise SpecError(f"{path} does not define {', '.join(missing)}")
    return module


def load_reader(name: str, root: Path = ROOT, bench: dict | None = None):
    """The per-layer metric's reader: ``read(ctx) -> float | None`` from
    ``<paths[0]>/metrics/<name>.py``."""
    return _load("metrics", name, "per-layer metric", ("read",), root, bench).read


def load_model(model_type: str, root: Path = ROOT, bench: dict | None = None):
    """The architecture's adapter: the module ``<paths[0]>/models/
    <model_type>.py``, which defines every name of ``MODEL_CONTRACT``. Each
    call loads it anew: a run loads it once and hands it on."""
    return _load("models", model_type, "model_type", MODEL_CONTRACT, root, bench)


def read_per_layer(bench: dict, cell_name: str, ctx: dict, root: Path = ROOT) -> dict:
    """Every per-layer metric of the cell whose reader finds something to
    read in ``ctx``; a reader that returns None is left out."""
    out = {}
    for m in metrics_for(bench, "per_layer", cell_name):
        value = load_reader(m["name"], root, bench)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check_rules(bench: dict, root: Path = ROOT) -> list[str]:
    """The driver's character and reference rules that can be checked
    without a run; returns the breaches (empty when the file is sound)."""
    bad: list[str] = []

    def name_ok(value, what):
        if not isinstance(value, str) or not NAME_RE.match(value):
            bad.append(f"{what}: name {value!r}")

    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    cfgs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        name_ok(c["name"], "config")
        if not (root / c["file"]).is_file():
            bad.append(f"config {c['name']}: file {c['file']} missing")
        else:
            cfg = json.loads((root / c["file"]).read_text())
            try:
                load_model(str(cfg.get("model_type")), root, bench)
            except SpecError as err:
                bad.append(f"config {c['name']}: {err}")
            if not isinstance(cfg.get("vocab_size"), int):
                bad.append(f"config {c['name']}: no vocab_size")
        for key in c["reduced"]:
            name_ok(key, f"config {c['name']} reduced")
    for w in bench["workloads"]:
        name_ok(w["name"], "workload")
        name_ok(w["traffic"], "traffic")
        if w["config"] not in cfgs:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"workload {w['name']}: why")
        traffic = root / bench["paths"][0] / "traffic" / f"{w['traffic']}.json"
        if not traffic.is_file():
            bad.append(f"workload {w['name']}: no traffic file {traffic}")
    seen: set[str] = set()
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            name_ok(m["name"], group)
            if m["name"] in seen:
                bad.append(f"metric {m['name']} named twice")
            seen.add(m["name"])
            if not UNIT_RE.match(m["unit"]):
                bad.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"metric {m['name']}: better")
            if m["source"] not in SOURCES:
                bad.append(f"metric {m['name']}: source {m['source']}")
            for w in m.get("workloads", []):
                if w not in cells:
                    bad.append(f"metric {m['name']}: unknown workload {w}")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']}: source {m['source']}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"end-to-end {m['name']}: bound {m['bound']}")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            bad.append(f"per-layer {m['name']}: moves {m['moves']}")
        reader = root / bench["paths"][0] / "metrics" / f"{m['name']}.py"
        if not reader.is_file():
            bad.append(f"per-layer {m['name']}: no reader {reader}")
        moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        for w in m.get("workloads", []):
            if "workloads" in moved and w not in moved["workloads"]:
                bad.append(
                    f"per-layer {m['name']}: cell {w} does not report {m['moves']}"
                )
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for w in bench["workloads"]:
        mine = [m["name"] for m in metrics_for(bench, "end_to_end", w["name"])]
        if "setup_s" not in mine or len(mine) < 2:
            bad.append(f"workload {w['name']}: end-to-end metrics {mine}")
        if not metrics_for(bench, "per_layer", w["name"]):
            bad.append(f"workload {w['name']}: no per-layer metric")
    return bad
