"""How ``fixtures/small.xplane.pb`` was recorded (on one v5e, PR 23): three
jitted programs with pauses between them, traced by ``lib.trace.Tracer``.
Prints what the reduction makes of it; ``test_trace.py`` pins those
numbers. Usage, on the chip: ``python3 perfbench/tests/record_trace.py
<out_dir>``."""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from lib import trace

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)

    @jax.jit
    def work(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready(work(x))
    tracer = trace.Tracer(out / "raw")
    tracer.start()
    for _ in range(3):
        jax.block_until_ready(work(x))
        time.sleep(0.05)
    tracer.stop()
    found = trace.find_xplane(out / "raw")
    shutil.copy(found, out / "small.xplane.pb")
    events = trace.device_events(found)
    result = tracer.result()
    result["events"] = {k: len(v) for k, v in events.items()}
    result["names"] = sorted({e[0] for v in events.values() for e in v})[:40]
    (out / "small.expected.json").write_text(json.dumps(result, indent=1))
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1])
