"""The benchmark's self-tests run on the CPU at tiny sizes:
``python3 -m pytest perfbench/tests -q`` from the root of the repo."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH_DIR = Path(__file__).resolve().parents[1]
for p in (BENCH_DIR.parent, BENCH_DIR):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
