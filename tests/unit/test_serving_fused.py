"""Fused multi-step decode contracts (docs/SERVING.md §Fused
multi-step decode).

The invariants that matter: (1) greedy output is BIT-IDENTICAL to
single-request ``decode.generate`` — fusing a quantum of steps into one
``lax.scan`` must not move a single bit, including for rows that finish
mid-scan and freeze; (2) sampling stays reproducible per (seed, row)
and seed-sensitive; (3) the compiled surface stays fixed — shape
variety within the bucket set triggers ZERO recompiles at both the
builder counter and the jit cache layer.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

import jax

from pygrid_tpu.models import decode
from pygrid_tpu.models import transformer as T
from pygrid_tpu.serving import EngineConfig, GenerationEngine
from pygrid_tpu.serving.pagedkv import fused_enabled

CFG = T.TransformerConfig(
    vocab=31, d_model=16, n_heads=2, n_layers=2, d_ff=32, max_len=32
)


@pytest.fixture(scope="module")
def params():
    return T.init(jax.random.PRNGKey(5), CFG)


def _ref(params, prompt, n_new, **kw):
    return np.asarray(
        decode.generate(params, np.asarray(prompt, np.int32), n_new, CFG, **kw)
    )


def _engine(params, model_id, **over):
    kw = dict(
        max_slots=4, slot_buckets=(1, 2, 4), min_prompt_bucket=8,
        block_size=8,
    )
    kw.update(over)
    return GenerationEngine(
        CFG, params, EngineConfig(**kw), model_id=model_id
    )


# ── knob resolution ──────────────────────────────────────────────────────


def test_knob_resolution(monkeypatch):
    assert fused_enabled() is True  # fused is the paged default
    monkeypatch.setenv("PYGRID_FUSED_DECODE", "off")
    assert fused_enabled() is False
    assert fused_enabled(True) is True  # explicit config wins


# ── fused multi-step decode ──────────────────────────────────────────────


def test_fused_greedy_bit_identical_incl_mid_scan_finish(params):
    """n_new both below and well past one quantum: rows freeze mid-scan
    (n_new=2 inside a quantum of 8) and span multiple scans (n_new=11)
    — every token still equals the unfused single-request reference."""
    eng = _engine(params, "fused", fused=True)
    try:
        for p, n in (
            ([[3, 5, 2, 9, 11]], 6), ([[1, 2]], 2), ([[7, 8, 9]], 11),
            ([[4]], 1), ([[6, 6, 6]], 8),
        ):
            got = eng.submit(np.array(p), n)
            np.testing.assert_array_equal(got, _ref(params, p, n))
        stats = eng.stats()
        assert stats["fused"] is True
        assert stats["fused_scans"] > 0
        # rows finishing mid-scan really did burn frozen steps — the
        # honest price of fusing, surfaced, not hidden
        assert stats["fused_wasted_steps"] > 0
    finally:
        eng.close()


def test_fused_concurrent_widths_and_finishes_match_reference(params):
    """More requests than slots with mixed n_new: the scan runs at
    varying width buckets while rows join/leave, and every result is
    bit-identical to its sequential twin."""
    eng = _engine(params, "fused-mix", fused=True)
    try:
        cases = [
            (np.array([[2 + i, 5, 1, 7][: 1 + i % 4]]), 1 + (i * 3) % 9)
            for i in range(10)
        ]
        results: list = [None] * len(cases)

        def go(i):
            prompt, n = cases[i]
            results[i] = eng.submit(prompt, n)

        threads = [
            threading.Thread(target=go, args=(i,))
            for i in range(len(cases))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for (prompt, n), got in zip(cases, results):
            np.testing.assert_array_equal(got, _ref(params, prompt, n))
    finally:
        eng.close()


def test_fused_zero_recompiles_and_sampling_reproducible(params):
    eng = _engine(params, "fused-rc", fused=True)
    try:
        eng.warmup(prompt_lens=(1, 8))
        before = eng.compile_count()
        prompt = np.array([[3, 5, 2]])
        a = eng.submit(prompt, 9, temperature=0.9, seed=123)
        b = eng.submit(prompt, 9, temperature=0.9, seed=123)
        np.testing.assert_array_equal(a, b)
        outs = {
            tuple(eng.submit(prompt, 9, temperature=0.9, seed=s)[0])
            for s in range(6)
        }
        assert len(outs) > 1, "different seeds must be able to differ"
        for p_len, n in ((1, 2), (5, 9), (8, 1), (2, 12)):
            eng.submit(np.full((1, p_len), 3), n)
        assert eng.compile_count() == before
        assert eng.programs.trace_count() == eng.compile_count()
    finally:
        eng.close()


def test_fused_off_env_reverts_to_per_step(params, monkeypatch):
    monkeypatch.setenv("PYGRID_FUSED_DECODE", "off")
    eng = _engine(params, "unfused")
    try:
        assert eng.stats()["fused"] is False
        got = eng.submit(np.array([[3, 5, 2]]), 6)
        np.testing.assert_array_equal(got, _ref(params, [[3, 5, 2]], 6))
    finally:
        eng.close()


def test_fused_telemetry_families_flow(params):
    from pygrid_tpu import telemetry

    eng = _engine(params, "tele-f", fused=True)
    try:
        eng.submit(np.array([[1, 2, 3]]), 9)
    finally:
        eng.close()
    counters = {name for (name, _), _ in telemetry.counters().items()}
    for family in (
        "serving_fused_scans_total",
        "serving_fused_steps_total",
    ):
        assert family in counters, family
