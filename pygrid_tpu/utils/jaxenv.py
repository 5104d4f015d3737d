"""What this process's JAX runs on, and where it keeps compiled programs.

Two facts every JAX-using entry point (``python -m pygrid_tpu.node``,
``python -m pygrid_tpu.worker``, the storm CLI, ``bench.py``,
``chip_smoke.py``) states before it does any work, so a result is never
read against a device the process was not on:

- :func:`device_info` — the platform, device kind and device count as
  JAX reports them. Calling it initialises the backend, so an entry
  point that calls it at start-up fails there, loudly, when the
  accelerator it was given cannot be reached;
- :func:`configure_compile_cache` — JAX's persistent compilation cache,
  placed from OUTSIDE the program when ``JAX_COMPILATION_CACHE_DIR`` is
  set (JAX reads that variable itself; nothing is set in code) and at
  one fixed directory inside the checkout otherwise. The path never
  carries a pid, a timestamp or a temp name: a cache that moves never
  hits, and the node, trainer and benchmark processes of one machine
  are meant to share it.

A chip belongs to one process at a time. Neither function may run in a
process that should stay off the accelerator while a child owns it.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the in-checkout cache (git-ignored), used only when the environment
#: does not place the cache itself
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def device_info() -> dict:
    """``{"platform", "device_kind", "count"}`` of this process's default
    backend. Initialises the backend; raises what JAX raises when the
    platforms ``JAX_PLATFORMS`` names cannot be initialised."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable and
    return the directory in use. With ``JAX_COMPILATION_CACHE_DIR`` set
    this only reports it — JAX picked the variable up on import."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def describe(device: dict, cache_dir: str) -> str:
    """The start-up line every JAX-holding entry point prints."""
    return (
        f"platform={device['platform']} "
        f"device_kind={device['device_kind']!r} devices={device['count']} "
        f"(compile cache: {cache_dir})"
    )


def cache_entries(path: str) -> int:
    """How many compiled programs the cache directory holds (0 when it
    does not exist yet — JAX creates it on first write)."""
    try:
        # one ``<program>-<key>-cache`` file per entry (access-time
        # sidecars, where JAX keeps them, are not entries)
        return sum(
            1 for p in Path(path).iterdir() if p.name.endswith("-cache")
        )
    except FileNotFoundError:
        return 0
