"""End-to-end model-centric FL demo: host + train + checkpoint.

Combines 01_create_plan and 02_execute_plan into one driver (what the
compose ``worker`` service runs): host the MNIST process on a node, run N
workers per cycle until the configured cycles finish, then pull the final
checkpoint. Equivalent to running the reference's two model-centric
notebooks back-to-back against the compose grid."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[0]))

from _grid import example_args, spawn_grid, wait_for

HERE = Path(__file__).resolve().parent


def main() -> int:
    parser = example_args("full FL round-trip demo")
    parser.add_argument("--workers", type=int, default=2)
    parser.add_argument("--cycles", type=int, default=2)
    args = parser.parse_args()
    node_url = args.node
    if args.spawn:
        _, nodes = spawn_grid(1)
        node_url = nodes["alice"]
    wait_for(node_url, args.wait)

    base = [sys.executable, "-u"]
    # the children trace plans and train MNIST steps with JAX. They are
    # FL clients, not the node: a chip belongs to one process at a time
    # and the node (this process under --spawn, or a neighbour on this
    # host) is the one that holds it — so the children are pinned to
    # the CPU instead of racing it for the device.
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    host = subprocess.run(
        [*base, str(HERE / "model_centric" / "01_create_plan.py"),
         "--node", node_url],
        timeout=600, env=env,
    )
    if host.returncode:
        return host.returncode
    execute = subprocess.run(
        [*base, str(HERE / "model_centric" / "02_execute_plan.py"),
         "--node", node_url, "--workers", str(args.workers),
         "--cycles", str(args.cycles)],
        timeout=600, env=env,
    )
    return execute.returncode


if __name__ == "__main__":
    sys.exit(main())
