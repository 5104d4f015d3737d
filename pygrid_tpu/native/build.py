"""Build the native library on demand.

The reference ships its native pieces as pip wheels (wsaccel, protobuf);
this framework compiles its single C++ translation unit at first use with
whatever ``g++``/``clang++`` is on PATH and caches the ``.so`` under
``_build/`` (git-ignored), named by a digest of the source and the
compile flags: a library is only ever loaded if it was built from exactly
this source, whatever a copied tree's file times say. No toolchain →
callers fall back to numpy paths."""

from __future__ import annotations

import hashlib
import logging
import os
import shutil
import subprocess
import sysconfig
from pathlib import Path

logger = logging.getLogger(__name__)

_SRC = Path(__file__).parent / "src" / "pygrid_native.cpp"
_FLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17")


def _lib_path() -> Path:
    digest = hashlib.sha256(
        _SRC.read_bytes() + " ".join(_FLAGS).encode()
    ).hexdigest()[:16]
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return (
        Path(__file__).parent / "_build"
        / f"libpygrid_native-{digest}{suffix}"
    )


def ensure_built(force: bool = False) -> Path | None:
    """Compile if this source has no library yet; returns the library
    path or None."""
    lib = _lib_path()
    if not force and lib.exists():
        return lib
    compiler = (
        os.environ.get("CXX") or shutil.which("g++") or shutil.which("clang++")
    )
    if compiler is None:
        logger.info("pygrid_tpu.native: no C++ compiler; using numpy paths")
        return None
    lib.parent.mkdir(parents=True, exist_ok=True)
    # build beside the target and rename into place: two processes
    # starting together (a node and a worker) must never load a
    # half-written library
    tmp = lib.with_name(f"{lib.name}.{os.getpid()}.tmp")
    cmd = [compiler, *_FLAGS, str(_SRC), "-o", str(tmp)]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, text=True, timeout=120
        )
        os.replace(tmp, lib)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as err:
        detail = getattr(err, "stderr", "") or str(err)
        logger.warning("pygrid_tpu.native build failed: %s", detail)
        tmp.unlink(missing_ok=True)
        return None
    return lib
