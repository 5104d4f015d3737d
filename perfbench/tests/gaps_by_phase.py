"""By hand, on the chip: the device's idle gaps by the engine thread's phase.

    python3 perfbench/tests/gaps_by_phase.py cell --workload <cell> --seed <n> --seconds <s>
    python3 perfbench/tests/gaps_by_phase.py record <out_dir>

``cell`` is ``run.py --trace 1`` with four more lines before the result:
``lib.hostgaps.attribute`` under both rules, the largest ``after:<op>``
groups of ``lib.trace.reduce`` beside them, and the gap time as a share of
the traced stretch. ``record`` is how ``fixtures/engine.xplane.pb`` was made
(one v5e, PR 24): the program's own engine at a toy width, three requests,
traced by ``lib.trace.Tracer``; ``test_loopclock.py`` pins what the
reduction makes of it.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
for p in (BENCH_DIR.parent, BENCH_DIR):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def gap_tables(xplane: Path) -> dict:
    from lib import hostgaps, trace

    out = {rule: hostgaps.attribute(xplane, rule) for rule in ("overlap", "start")}
    per_device = trace.device_events(xplane)
    out["after_op"] = trace.reduce(per_device, 1.0, top=1000)["breakdown"]["idle_gaps"]
    return out


def cell(argv: list[str]) -> int:
    import run
    from lib import trace

    reduced = trace.Tracer.result

    def with_gaps(self):
        xplane = trace.find_xplane(self.out_dir)
        tables = gap_tables(xplane)
        window = self.window_s
        print("perfbench: gaps by phase, overlap " + json.dumps(tables["overlap"]))
        print("perfbench: gaps by phase, at start " + json.dumps(tables["start"]))
        print("perfbench: gaps by operation before " + json.dumps(tables["after_op"][:12]))
        total = sum(tables["overlap"].values())
        print(f"perfbench: gap time {total:.6f} s of a traced {window:.3f} s "
              f"({100 * total / window:.3f}%)", flush=True)
        return reduced(self)

    trace.Tracer.result = with_gaps
    return run.main(argv + ["--trace", "1"])


def record(out_dir: str) -> None:
    import jax
    import numpy as np

    from lib import trace
    from pygrid_tpu.models import transformer as T
    from pygrid_tpu.serving import EngineConfig, GenerationEngine

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    cfg = T.TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_len=32)
    params = T.init(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(
        cfg, params,
        EngineConfig(max_slots=2, slot_buckets=(1, 2), min_prompt_bucket=8, block_size=8,
                     quantum=4),
        model_id="fixture",
    )
    prompt = np.arange(1, 7, dtype=np.int32)[None, :]
    try:
        engine.warmup((6,))
        engine.submit(prompt, 6)
        tracer = trace.Tracer(out / "raw")
        tracer.start()
        # two at once, then one alone after a pause (idle in between)
        both = [engine.enqueue(prompt, n) for n in (3, 5)]
        for f in both:
            f.result(60)
        time.sleep(0.02)
        engine.submit(prompt, 6)
        tracer.stop()
    finally:
        engine.close()
    found = trace.find_xplane(out / "raw")
    shutil.copy(found, out / "engine.xplane.pb")
    expected = gap_tables(found)
    expected["window_s"] = tracer.window_s
    expected["device"] = jax.devices()[0].device_kind
    expected["bytes"] = found.stat().st_size
    (out / "engine.expected.json").write_text(json.dumps(expected, indent=1))
    print(json.dumps(expected))


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "record":
        record(sys.argv[2])
    elif len(sys.argv) >= 2 and sys.argv[1] == "cell":
        sys.exit(cell(sys.argv[2:]))
    else:
        sys.exit(__doc__)
