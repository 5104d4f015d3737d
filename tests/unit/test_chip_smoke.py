"""chip_smoke.py's control flow, walked at a tiny size on the CPU.

The chip run itself happens only on the chip (``python chip_smoke.py``);
what tier-1 can hold still is everything around it: the phase functions
run end to end (Pallas interpreted), ``main()`` refuses a CPU pin by
name, the compile cache is placed from outside or at one fixed path, and
the multi-chip dry run never provisions devices for itself."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from pygrid_tpu.models import transformer  # noqa: E402

TINY = transformer.TransformerConfig(
    vocab=127, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_len=64
)


def _fresh(code: str, **env_changes) -> subprocess.CompletedProcess:
    """Run ``code`` in a fresh interpreter from the repo root; an env
    value of None removes the variable."""
    env = dict(os.environ)
    for key, value in env_changes.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=str(REPO),
        capture_output=True, text=True, timeout=120,
    )


def test_phases_walk_at_tiny_size(monkeypatch):
    import jax

    from bench import _NodeServer

    # 16-token pages so a 64-token model can share two whole pages
    monkeypatch.setenv("PYGRID_KV_BLOCK", "16")
    params = [
        np.asarray(p)
        for p in transformer.init(jax.random.PRNGKey(chip_smoke.SEED), TINY)
    ]
    server = _NodeServer().start()
    try:
        row = chip_smoke.serve_phase(
            TINY, params, server.url, "cpu", block=16, timeout=120
        )
    finally:
        server.stop()
    assert row["platform"] == "cpu" and row["device_count"] == 8
    assert row["dtypes"] == {"kv": "float32", "compute": "float32"}
    assert {"paged_prefill", "paged_decode", "paged_decode_fused"} <= {
        p.split("/")[0] for p in row["programs"]
    }
    assert row["doors"] == ["http", "ws"]

    # every third answer: both passes, with and without the shared
    # prefix, but only two shapes for decode.generate to compile
    ref = chip_smoke.reference_phase(
        TINY, params, row["served"][::3], row["dtypes"]["kv"], tie_tol=1e-3
    )
    # on the CPU at f32 the engine's old contract still holds exactly
    assert ref["bit_identical_to_generate"] == ref["answers"] == 10

    train = chip_smoke.train_phase(
        TINY, interpret=True, clients=2, batch=2, rounds=3
    )
    assert train["pallas"] == "interpreted" and len(train["losses"]) == 3
    attn = chip_smoke.attention_phase(
        TINY, interpret=True, batch=1, dtype="float32", rel_tol=1e-4
    )
    assert set(attn["rel_err_vs_xla"]) == {"out", "dq", "dk", "dv"}
    assert chip_smoke.ring_phase(interpret=True)["exact"] is True
    sharded = chip_smoke.sharded_phase(
        TINY, interpret=True, clients=4, batch=1
    )
    assert sharded["ran"] and len(sharded["shard_devices"]) == 4


def test_a_failed_check_raises():
    """A phase reports failure by raising — there is no error key to
    overlook."""
    with pytest.raises(chip_smoke.SmokeFailure, match="differs from the XLA"):
        chip_smoke.attention_phase(
            TINY, interpret=True, batch=1, dtype="float32", rel_tol=0.0
        )


def test_main_refuses_a_cpu_pin_by_name():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert proc.stdout == ""  # no result line of any kind


def test_compile_cache_is_left_to_the_environment_when_placed(monkeypatch):
    import jax

    from pygrid_tpu.utils import jaxenv

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setattr(
        jax.config, "update",
        lambda *a, **k: pytest.fail(f"set in code: {a}"),
    )
    assert jaxenv.configure_compile_cache() == "/some/dir"


def test_compile_cache_default_is_one_fixed_path_in_the_checkout():
    code = (
        "import jax; from pygrid_tpu.utils import jaxenv; "
        "d = jaxenv.configure_compile_cache(); "
        "assert jax.config.jax_compilation_cache_dir == d; print(d)"
    )
    first, second = (
        _fresh(code, JAX_COMPILATION_CACHE_DIR=None) for _ in range(2)
    )
    assert first.returncode == 0, first.stderr
    assert first.stdout == second.stdout
    assert Path(first.stdout.strip()) == REPO / ".jax_cache"


def test_dryrun_multichip_raises_on_one_device():
    proc = _fresh(
        "import __graft_entry__ as g; g.dryrun_multichip(8)",
        XLA_FLAGS=None, JAX_PLATFORMS="cpu",
    )
    assert proc.returncode != 0
    assert "need 8 devices, have 1" in proc.stderr
