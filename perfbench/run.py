"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerator this machine holds
and prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, traced, ``breakdown``; its last key, ``compared``, holds each
number compared as ``[value, limit]``, and the same go out as the last lines
of standard error. Everything else (how set-up splits, the generator's
lateness, requests by door, compiles inside the window) goes on earlier
lines.

It exits non-zero, with no result line, when JAX finds no TPU or fewer
chips than the cell asks for, and where the program is not beside it.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT))

from lib import spec  # noqa: E402

#: the platform a measured run needs; the self-tests rehearse on another
PLATFORM = "tpu"


def claim_device(platform: str, chips: int) -> dict:
    """Bring JAX up on ``platform`` or exit: a measurement never falls
    back to another device. Returns ``{"platform", "kind", "count"}``."""
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if pinned and platform not in pinned.split(","):
        sys.exit(
            f"perfbench: JAX_PLATFORMS pins this run to {pinned!r}; "
            f"a cell is measured on {platform!r} only"
        )
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a fixed path inside the checkout (the program's own choice, too)
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as err:
        sys.exit(f"perfbench: JAX found no accelerator: {err}")
    found = devices[0].platform
    if found != platform:
        sys.exit(
            f"perfbench: JAX runs on platform {found!r} "
            f"({devices[0].device_kind!r}); a cell is measured on {platform!r} only"
        )
    if len(devices) < chips:
        sys.exit(f"perfbench: the cell asks for {chips} chips, JAX has {len(devices)}")
    return {"platform": found, "kind": devices[0].device_kind, "count": len(devices)}


def result_line(bench: dict, cell: dict, run: dict, device: dict, trace: bool) -> dict:
    """The contract's result object from a runner's measurements."""
    name = cell["name"]
    if trace:
        metrics = spec.read_per_layer(bench, name, run["ctx"], ROOT)
    else:
        metrics = {
            m["name"]: {"value": float(run["e2e"][m["name"]]), "unit": m["unit"]}
            for m in spec.metrics_for(bench, "end_to_end", name)
        }
    dev = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    line = {
        "correct": bool(run["verdict"]["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": metrics,
        "device": dev,
    }
    if trace:
        dev["busy_s"] = run["trace"]["busy_s"]
        dev["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = run["trace"]["breakdown"]
    return line


def compared_last(line: dict, verdict: dict) -> dict:
    """The line with each number compared beside its limit, ``{name:
    [value, limit]}``, as its last key: with the last lines of standard
    error, what the driver keeps of a run that is not correct."""
    line = {k: v for k, v in line.items() if k != "compared"}
    # a number that is not finite has no place in JSON: null, and not correct
    line["compared"] = {
        c["name"]: [c["value"] if math.isfinite(c["value"]) else None, c["limit"]]
        for c in verdict["compared"]
    }
    return line


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, control: bool = False,
             **hooks) -> dict:
    """Dispatch on the traffic file's ``kind`` (``serve`` or ``train``)."""
    kind = cell["traffic"]["kind"]
    if kind == "serve":
        from lib import serving

        return serving.run_cell(cell, seed, seconds, trace, T_START, control, **hooks)
    if kind == "train":
        from lib import training

        return training.run_cell(cell, seed, seconds, trace, T_START, control, **hooks)
    raise spec.SpecError(f"traffic kind {kind!r} is neither serve nor train")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the builder's own: also compute the lower-precision control's numbers
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "pygrid_tpu").is_dir():
        sys.exit(f"perfbench: the program (pygrid_tpu/) is not at {ROOT}")
    try:
        bench = spec.load_benchmark(ROOT)
        cell = spec.cell(bench, args.workload, ROOT)
    except spec.SpecError as err:
        sys.exit(f"perfbench: {err}")
    device = claim_device(PLATFORM, cell["chips"])
    print(f"perfbench: cell {cell['name']} seed {args.seed} on {device}", flush=True)
    run = run_cell(cell, args.seed, args.seconds, bool(args.trace), bool(args.control))
    for c in run["verdict"]["compared"]:
        ok = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"perfbench: compared {c['name']} = {c['value']:.6g} (limit {c['limit']:.6g}) {ok}")
    extra = {k: v for k, v in run["verdict"].items() if k not in ("compared", "correct")}
    print("perfbench: check " + json.dumps(extra), flush=True)
    if not args.trace:
        # the per-layer numbers that need no trace, for whoever reads the log
        seen = spec.read_per_layer(bench, cell["name"], run["ctx"], ROOT)
        print("perfbench: layers " + json.dumps({k: v["value"] for k, v in seen.items()}))
    if run["compiles_in_window"]:
        print(f"perfbench: {run['compiles_in_window']} programs compiled INSIDE the window",
              file=sys.stderr)
    line = result_line(bench, cell, run, device, bool(args.trace))
    line["compiles_in_window"] = run["compiles_in_window"]
    if args.trace:
        # the other group too, for the builder's eye; the driver ignores it
        line["end_to_end_seen"] = run["e2e"]
    line = compared_last(line, run["verdict"])
    for c in run["verdict"]["compared"]:
        print(f"perfbench: compared {c['name']} = {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
