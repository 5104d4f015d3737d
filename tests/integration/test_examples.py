"""Examples as tests — the analog of the reference's papermill notebook
suite (``tests/notebooks/test_notebooks.py:24-98``, which executes the 5
example notebooks against the spawned grid). Each script runs in its own
process with ``--spawn`` (ephemeral in-process grid) on the CPU platform."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

EXAMPLES = Path(__file__).resolve().parents[2] / "examples"


def _run(script: str, *args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(EXAMPLES.parent)
    return subprocess.run(
        [sys.executable, str(EXAMPLES / script), *args],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )


def test_smpc_demo():
    result = _run("smpc_demo.py")
    assert result.returncode == 0, result.stderr
    assert "Beaver" in result.stdout


def test_model_centric_host_example():
    result = _run("model_centric/01_create_plan.py", "--spawn")
    assert result.returncode == 0, result.stderr
    assert "hosted mnist/1.0" in result.stdout


def test_data_centric_populate_example():
    result = _run("data_centric/01_populate_node.py", "--spawn")
    assert result.returncode == 0, result.stderr
    assert "8 pointers" in result.stdout


def test_full_fl_demo():
    """Host → 2 workers × 2 cycles → checkpoint (the compose demo service)."""
    result = _run("full_fl_demo.py", "--spawn", "--workers", "2",
                  "--cycles", "2")
    assert result.returncode == 0, result.stderr + result.stdout
    assert "latest checkpoint" in result.stdout


def test_data_centric_train_example():
    result = _run("data_centric/02_train_model.py", "--spawn")
    assert result.returncode == 0, result.stderr
    assert "max |w - w*|" in result.stdout


def test_encrypted_inference_example():
    result = _run("encrypted_inference.py", "--spawn")
    assert result.returncode == 0, result.stderr
    assert "encrypted inference OK" in result.stdout


def test_advanced_fl_example():
    result = _run("advanced_fl.py", "--spawn")
    assert result.returncode == 0, result.stderr
    assert "advanced FL OK" in result.stdout


def test_secagg_fl_example():
    result = _run("secagg_fl.py", "--spawn")
    assert result.returncode == 0, result.stderr
    assert "secure aggregation OK" in result.stdout


def test_async_fl_example():
    result = _run("async_fl.py", "--spawn")
    assert result.returncode == 0, result.stderr
    assert "async FL OK" in result.stdout


def test_fed_transformer_example():
    result = _run("fed_transformer.py", "--interpret")
    assert result.returncode == 0, result.stderr[-2000:]
    assert "federated transformer" in result.stdout
