"""The hybrid state-space / attention family (``pygrid_tpu/models/jamba.py``)
through the serving engine, against the plain reference that the benchmark
keeps (``perfbench/models/jamba.py``: float32, ``highest``, the recurrence
a token at a time, nothing of the program imported).

Size: two periods of the layer pattern (4 layers, attention at ``i % 2 ==
1``), 4 query heads on 1 key/value head, ``d_state`` 16, float32 weights on
the CPU. Everything is compared on LOGITS: the program's against the
reference's, or, where only tokens come out of the engine, the reference's
logit of the served token against its best (teacher forced on what was
served).

``TOL``: program and reference run the same float32 mathematics in another
order (the program convolves and scans with ``d_inner`` on the lanes, norms
by ``rsqrt``, scans in chunks); logits of size ~4 differ by at most 8.2e-6
over six seeds (my CPU runs, PR 27). 5e-5 is six times that, and thousands
of times under what a fault does: the reference computed in bfloat16 moves
a logit by 0.17 at the least over those seeds, and the tests below hold a
dropped state and a bucket's garbage padding to the same limit.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pygrid_tpu import serde, telemetry
from pygrid_tpu.models import decode, jamba
from pygrid_tpu.serving import (
    DeviceBudget,
    EngineConfig,
    GenerationEngine,
    ServingManager,
    pagedkv,
)

ROOT = Path(__file__).resolve().parents[2]
# the reference lives with the benchmark; its adapter imports ``lib``
if str(ROOT / "perfbench") not in sys.path:
    sys.path.append(str(ROOT / "perfbench"))
from lib import reference, spec  # noqa: E402

TOL = 5e-5
BLOCK = 16


@pytest.fixture(scope="module")
def model():
    return spec.load_model("jamba")


@pytest.fixture(scope="module")
def cfg(model):
    cfg = json.loads((ROOT / "perfbench/configs/ai21-jamba2-3b.json").read_text())
    cfg.update(model.tiny(cfg))
    return cfg


@pytest.fixture(scope="module")
def jcfg(model, cfg):
    return model.jamba_config(cfg)


@pytest.fixture(scope="module")
def params(model, cfg):
    return model.make_program_params(3, cfg, "float32")


@pytest.fixture(scope="module")
def weights(model, cfg):
    return model.make_weights(3, cfg, "float32")


def _ref_logits(model, weights, cfg, seq):
    return np.asarray(model.logits(weights, jnp.asarray([seq], jnp.int32), cfg)[0])


def _tokens(seed, n, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _engine(jcfg, params, model_id="jamba", **over):
    kw = dict(
        max_slots=4, slot_buckets=(1, 2, 4), min_prompt_bucket=8,
        block_size=BLOCK, quantum=4,
    )
    kw.update(over)
    return GenerationEngine(jcfg, params, EngineConfig(**kw), model_id=model_id)


def _count(name, **labels):
    """The bus counter ``name``, summed over series carrying ``labels``."""
    return sum(
        v for (n, lab), v in telemetry.counters().items()
        if n == name and labels.items() <= dict(lab).items()
    )


def _gaps(model, weights, cfg, prompt, served):
    """The reference's best logit minus its logit of each served token."""
    pad_to = -(-(len(prompt) + len(served)) // 32) * 32
    gaps, _ = reference.served_gaps(
        model, weights, cfg, np.asarray(prompt)[None], np.asarray(served)[None],
        pad_to,
    )
    return gaps


# ── the config and the family's facts ────────────────────────────────────


def test_tiny_is_two_periods_with_one_kv_head(jcfg):
    assert jcfg.attn_layers == (1, 3) and jcfg.mamba_layers == (0, 2)
    assert (jcfg.n_heads, jcfg.n_kv_heads, jcfg.d_state) == (4, 1, 16)
    assert decode.family_of(jcfg) is jamba
    assert decode.family_of(decode.TransformerConfig()) is decode


def test_block_bytes_count_only_the_layers_that_hold_kv(jcfg):
    # 2 of 4 layers, k and v, 1 head of 16, float32
    assert pagedkv.block_bytes(jcfg, BLOCK, jnp.float32) == 2 * 2 * BLOCK * 16 * 4
    # a slot's state: 2 Mamba layers x d_inner 128 x (16 x 4 B + 3 taps x 4 B)
    assert pagedkv.state_bytes(jcfg, 1, jnp.float32) == 2 * 128 * (64 + 12)
    assert pagedkv.state_bytes(jcfg, 1, jnp.bfloat16) == 2 * 128 * (64 + 6)
    assert pagedkv.state_bytes(decode.TransformerConfig(), 8, jnp.float32) == 0


def test_published_sizes_by_hand(model):
    cfg = json.loads((ROOT / "perfbench/configs/ai21-jamba2-3b.json").read_text())
    assert model.layer_params(cfg, 0) == 104_161_472
    assert model.layer_params(cfg, 7) == 76_682_240
    assert [i for i in range(28) if model.is_attn(cfg, i)] == [7, 21]
    assert model.param_count(cfg) == 3_029_337_472
    deploy = cfg["deployment"]
    assert deploy["weights_bytes"] == 2 * model.param_count(cfg)
    assert deploy["kv_bytes_per_token"] == model.kv_bytes_per_token(cfg) == 1024
    assert deploy["state_bytes_per_slot"] == model.state_bytes_per_slot(cfg) == 9_318_400
    jcfg = model.jamba_config(cfg)
    assert pagedkv.state_bytes(jcfg, 1, jnp.bfloat16) == 9_318_400
    assert pagedkv.block_bytes(jcfg, 64, jnp.bfloat16) == 64 * 1024
    # the budget is the state of 64 slots and then 2,048 blocks
    assert deploy["env"]["PYGRID_KV_BUDGET"] == 64 * 9_318_400 + 2048 * 65_536
    # a step at 64 live rows over 40,000 cached positions
    assert model.decode_step_bytes(cfg, 64, 40_000) == (
        6_058_674_944 + 2 * 64 * 9_318_400 + 40_000 * 1024
    )


# ── the mathematics ──────────────────────────────────────────────────────


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_full_forward_matches_the_reference(model, cfg, jcfg, seed):
    params = model.make_program_params(seed, cfg, "float32")
    w = model.make_weights(seed, cfg, "float32")
    tokens = jnp.asarray(np.stack([_tokens(seed, 50), _tokens(seed + 9, 50)]))
    got = np.asarray(jamba.apply(params, tokens, jcfg))
    want = np.asarray(model.logits(w, tokens, cfg))
    assert np.abs(want).max() > 1.0  # logits of size ~1, not all but zero
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_bf16_arithmetic_in_the_reference_is_outside_the_tolerance(
    model, cfg, weights
):
    tokens = jnp.asarray(_tokens(4, 60))[None]
    want = np.asarray(model.logits(weights, tokens, cfg))
    low = np.asarray(model.logits(weights, tokens, cfg, jnp.bfloat16), np.float32)
    assert np.abs(low - want).max() > 10 * TOL


def _fresh_cache(jcfg, slots=4, poison=False):
    cache = jamba.init_paged_cache(jcfg, slots, 1 + slots * 8, BLOCK, jnp.float32)
    if poison:  # what a slot may hold before its prefill: anything
        cache = cache._replace(
            ssm=jnp.full_like(cache.ssm, jnp.nan),
            conv=jnp.full_like(cache.conv, jnp.nan),
        )
    table = np.zeros((slots, 8), np.int32)
    for s in range(slots):
        table[s] = 1 + s * 8 + np.arange(8)
    return cache, jnp.asarray(table)


def test_prefill_of_a_padded_bucket_then_decode_matches_on_logits(
    model, cfg, jcfg, params, weights
):
    """A prompt of 11 in a bucket of 16 whose padding is garbage tokens,
    into a slot whose state was NaN: the logits at the prompt's end and at
    each of 9 decode steps are the reference's full forward."""
    seq = _tokens(6, 20)
    want = _ref_logits(model, weights, cfg, seq)
    cache, table = _fresh_cache(jcfg, poison=True)
    chunk = np.concatenate([seq[:11], _tokens(7, 5)])  # garbage padding
    logits, cache = jamba.paged_prefill_chunk(
        params, cache, table, jnp.int32(2), jnp.asarray(chunk), jnp.int32(0),
        jnp.int32(11), jcfg,
    )
    np.testing.assert_allclose(logits, want[10], atol=TOL, rtol=0)
    assert int(cache.pos[2]) == 11
    assert np.isfinite(np.asarray(cache.ssm[:, 2])).all()
    for t in range(11, 20):
        tok = np.zeros(4, np.int32)
        tok[2] = seq[t]
        logits, cache = jamba.paged_decode_step(
            params, cache, table, jnp.asarray(tok), jcfg
        )
        np.testing.assert_allclose(logits[2], want[t], atol=TOL, rtol=0)


def test_a_dropped_state_and_a_frozen_row_show_on_the_logits(
    model, cfg, jcfg, params, weights
):
    seq = _tokens(8, 14)
    want = _ref_logits(model, weights, cfg, seq)
    cache, table = _fresh_cache(jcfg)
    chunk = np.zeros(16, np.int32)
    chunk[:12] = seq[:12]
    _, cache = jamba.paged_prefill_chunk(
        params, cache, table, jnp.int32(0), jnp.asarray(chunk), jnp.int32(0),
        jnp.int32(12), jcfg,
    )
    tok = jnp.asarray([seq[12], 0], jnp.int32)
    good, after = jamba.paged_decode_step(params, cache, table, tok, jcfg)
    np.testing.assert_allclose(good[0], want[12], atol=TOL, rtol=0)
    # the state dropped: the same step from a zeroed recurrent state
    dropped = cache._replace(ssm=jnp.zeros_like(cache.ssm))
    bad, _ = jamba.paged_decode_step(params, dropped, table, tok, jcfg)
    assert np.abs(np.asarray(bad[0]) - want[12]).max() > 10 * TOL
    # a frozen row: its position parks and its K/V write goes to trash; its
    # state runs on (garbage the next prefill overwrites), the live row's
    # logits are untouched
    tok2 = jnp.asarray([seq[13], 5], jnp.int32)
    live, _ = jamba.paged_decode_step(params, after, table, tok2, jcfg)
    frozen, parked = jamba.paged_decode_step(
        params, after, table, tok2, jcfg, active=jnp.asarray([True, False])
    )
    np.testing.assert_allclose(frozen[0], live[0], atol=1e-6, rtol=0)
    np.testing.assert_allclose(frozen[0], want[13], atol=TOL, rtol=0)
    assert parked.pos.tolist()[:2] == [14, int(after.pos[1])]


# ── through the engine ───────────────────────────────────────────────────


@pytest.mark.parametrize("fused", [False, True], ids=["per-step", "fused"])
def test_engine_serves_the_references_tokens_staggered_and_reused(
    model, cfg, jcfg, params, weights, fused
):
    """Six requests on two slots: staggered admissions, prompts that do
    not fill their bucket (5, 11, 19 in 8, 16, 32), different ``n_new`` so
    that rows finish mid-scan (quantum 4), and every slot reused by a
    later request. Each served token is the reference's best to ``TOL``."""
    eng = _engine(jcfg, params, max_slots=2, slot_buckets=(1, 2), fused=fused)
    shapes = [(5, 7), (11, 3), (19, 10), (8, 5), (13, 6), (30, 9)]
    try:
        prompts = [_tokens(20 + i, p) for i, (p, _) in enumerate(shapes)]
        futures = [
            eng.enqueue(p[None], n) for p, (_, n) in zip(prompts, shapes)
        ]
        served = [f.result(300)[0] for f in futures]
        for prompt, toks, (_, n) in zip(prompts, served, shapes):
            assert toks.shape == (n,)
            assert _gaps(model, weights, cfg, prompt, toks).max() <= TOL
        # two at once on an empty queue, 2 and 11 tokens: with the fused
        # scan on, the short row freezes mid-scan and its state runs on;
        # the request that then reuses its slot is right
        pair = [_tokens(30, 7), _tokens(31, 9)]
        futures = [eng.enqueue(p[None], n) for p, n in zip(pair, (2, 11))]
        for prompt, f in zip(pair, futures):
            assert _gaps(model, weights, cfg, prompt, f.result(300)[0]).max() <= TOL
        again = _tokens(32, 12)
        toks = eng.submit(again[None], 6, timeout=300)[0]
        assert _gaps(model, weights, cfg, again, toks).max() <= TOL
        stats = eng.stats()
        assert stats["fused"] is fused
        assert stats["kv_kernel"] == 0 and stats["prefix_hits"] == 0
        if fused:
            assert stats["fused_wasted_steps"] > 0  # rows froze mid-scan
        assert eng.ledger()["balanced"] and eng.ledger()["drained"]
    finally:
        eng.close()


def test_dispatch_ahead_keeps_the_state_and_the_keys_in_step(
    model, cfg, jcfg, params, weights
):
    """Seven requests queued on two slots before the loop starts, ``n_new``
    of 1 and 2 among them: steps are launched ahead of the tokens read, a
    slot's next prefill (which writes the slot's whole state) goes out
    behind the step that still advances its last row. Greedy, each served
    token is the reference's best to ``TOL``; sampled, each answer is the
    one the same seed gets alone (the key follows the scheduled count)."""
    shapes = [(5, 7), (11, 1), (19, 2), (8, 5), (13, 1), (30, 9), (3, 2)]
    prompts = [_tokens(50 + i, p) for i, (p, _) in enumerate(shapes)]
    telemetry.reset()
    eng = _engine(jcfg, params, max_slots=2, slot_buckets=(1, 2), fused=False)
    try:
        alone = [
            eng.submit(p[None], n, temperature=0.7, seed=9 + i, timeout=300)[0]
            for i, (p, (_, n)) in enumerate(zip(prompts, shapes))
        ]
        assert _count("serving_dispatches_total", ahead="yes") > 0
        for temperature in (0.0, 0.7):
            ahead = _count("serving_dispatches_total", ahead="yes")
            futures = [
                eng.enqueue(p[None], n, temperature=temperature, seed=9 + i)
                for i, (p, (_, n)) in enumerate(zip(prompts, shapes))
            ]
            served = [f.result(300)[0] for f in futures]
            for prompt, toks, want, (_, n) in zip(prompts, served, alone, shapes):
                assert toks.shape == (n,)
                if temperature:
                    np.testing.assert_array_equal(toks, want)
                else:
                    assert _gaps(model, weights, cfg, prompt, toks).max() <= TOL
            assert _count("serving_dispatches_total", ahead="yes") > ahead
        assert not eng._arrivals
        assert eng.ledger()["balanced"] and eng.ledger()["drained"]
    finally:
        eng.close()


def test_a_zeroed_state_between_dispatches_is_served_wrong(
    model, cfg, jcfg, params, weights
):
    eng = _engine(jcfg, params, max_slots=1, slot_buckets=(1,), fused=False)
    try:
        emit = eng._emit

        def drop_state(slot, row, tok):
            eng._state = [jnp.zeros_like(a) for a in eng._state]
            return emit(slot, row, tok)

        eng._emit = drop_state
        prompt = _tokens(31, 12)
        toks = eng.submit(prompt[None], 8, timeout=300)[0]
        assert _gaps(model, weights, cfg, prompt, toks).max() > 10 * TOL
    finally:
        eng.close()


def test_a_common_128_token_prefix_is_answered_as_alone(model, cfg, weights, params):
    """Nothing is shared for a model with recurrent state: both requests
    miss the prefix cache, prefill whole, and answer as they do alone."""
    long_cfg = dict(cfg, deployment=dict(cfg["deployment"], served_context=256))
    jcfg = model.jamba_config(long_cfg)
    prefix = _tokens(40, 128)
    prompts = [np.concatenate([prefix, _tokens(41 + i, 9 + i)]) for i in range(2)]
    telemetry.reset()
    eng = _engine(jcfg, params, model_id="shared")
    try:
        together = [f.result(300)[0] for f in [eng.enqueue(p[None], 6) for p in prompts]]
        stats = eng.stats()
        assert stats["prefix_hits"] == 0 and stats["prefix_misses"] == 2
        assert stats["prefix_tokens_saved"] == 0 and stats["kv_blocks_cached"] == 0
        alone = [eng.submit(p[None], 6, timeout=300)[0] for p in prompts]
    finally:
        eng.close()
    for prompt, a, b in zip(prompts, together, alone):
        np.testing.assert_array_equal(a, b)
        assert _gaps(model, weights, long_cfg, prompt, a).max() <= TOL
    assert _count("serving_prefix_lookups_total", outcome="miss") == 4
    assert _count("serving_prefix_lookups_total", outcome="hit") == 0


def test_the_new_counters_and_the_telemetry_row(jcfg, params):
    telemetry.reset()
    eng = _engine(jcfg, params, model_id="count", max_slots=2, slot_buckets=(1, 2),
                  fused=False)
    try:
        eng.submit(_tokens(50, 11)[None], 5, timeout=300)  # 11 in a bucket of 16
        stats, ledger = eng.stats(), eng.ledger()
    finally:
        eng.close()
    per_slot = pagedkv.state_bytes(jcfg, 1, jnp.float32)
    assert stats["state_bytes_per_slot"] == per_slot == ledger["state_bytes"] // 2
    assert stats["state_bytes"] == 2 * per_slot
    assert stats["weights_bytes"] == sum(x.nbytes for x in jax.tree.leaves(params))
    assert stats["kv_block_bytes"] == pagedkv.block_bytes(jcfg, BLOCK, jnp.float32)
    assert _count("serving_prefill_tokens_total", kind="true") == 11
    assert _count("serving_prefill_tokens_total", kind="padded") == 16
    # four decode steps of one live row read and write its state; the
    # prefill wrote it once
    assert _count("serving_state_bytes_total", kind="read") == 4 * per_slot
    assert _count("serving_state_bytes_total", kind="written") == 5 * per_slot


def test_prefix_sharing_is_the_one_thing_a_recurrent_state_switches_off(
    model, cfg, jcfg, params, weights
):
    """The same pair of requests over a shared two-page prefix, through
    the same engine code, on both families. The transformer maps the
    prefix's pages into the second request (one hit, two pages saved);
    the family with a recurrent state shares nothing, and is still
    right to ``TOL``. Everything else the stats row says of the two
    engines' paths is the same."""
    from pygrid_tpu.models import transformer as T

    prefix = _tokens(70, 2 * BLOCK)
    prompts = [np.concatenate([prefix, _tokens(71 + i, 4 + i)]) for i in range(2)]
    tcfg = T.TransformerConfig(
        vocab=jcfg.vocab, d_model=16, n_heads=2, n_layers=2, d_ff=32,
        max_len=jcfg.max_len,
    )
    tparams = T.init(jax.random.PRNGKey(7), tcfg)
    rows = {}
    for name, ecfg, eparams in (
        ("transformer", tcfg, tparams), ("recurrent", jcfg, params),
    ):
        eng = _engine(ecfg, eparams, model_id=name)
        try:
            served = [eng.submit(p[None], 4, timeout=300)[0] for p in prompts]
            rows[name] = eng.stats()
        finally:
            eng.close()
        for prompt, toks in zip(prompts, served):
            if name == "recurrent":
                assert _gaps(model, weights, cfg, prompt, toks).max() <= TOL
            else:
                ref = decode.generate(tparams, prompt[None], 4, tcfg)
                np.testing.assert_array_equal(toks, np.asarray(ref)[0])
    assert rows["transformer"]["prefix_hits"] == 1
    assert rows["transformer"]["prefix_tokens_saved"] == 2 * BLOCK
    assert rows["recurrent"]["prefix_hits"] == 0
    assert rows["recurrent"]["prefix_misses"] == 2
    assert rows["recurrent"]["prefix_tokens_saved"] == 0
    for key in ("fused", "kv_kernel", "block_size", "max_slots"):
        assert rows["transformer"][key] == rows["recurrent"][key], key
    assert rows["recurrent"]["fused_scans"] > 0  # the scan serves both


def test_the_engine_recovers_its_state_arrays_after_a_failed_dispatch(
    model, cfg, jcfg, params, weights
):
    eng = _engine(jcfg, params, max_slots=1, slot_buckets=(1,))
    try:
        original = eng.programs.paged_prefill
        eng.programs.paged_prefill = lambda bucket: (_ for _ in ()).throw(
            RuntimeError("injected device failure")
        )
        with pytest.raises(Exception, match="engine error"):
            eng.submit(_tokens(60, 9)[None], 3, timeout=60)
        eng.programs.paged_prefill = original
        assert len(eng._state) == 2 and eng._state[0].shape[1] == 1
        prompt = _tokens(61, 9)
        toks = eng.submit(prompt[None], 5, timeout=300)[0]
        assert _gaps(model, weights, cfg, prompt, toks).max() <= TOL
        assert eng.ledger()["balanced"]
    finally:
        eng.close()


# ── the budget, the bundle, the manager ──────────────────────────────────


def test_the_budget_charges_the_state_before_it_grants_blocks(jcfg):
    per_block = pagedkv.block_bytes(jcfg, BLOCK, jnp.float32)
    state = pagedkv.state_bytes(jcfg, 4, jnp.float32)
    budget = DeviceBudget(total_bytes=state + 10 * per_block)
    manager = ServingManager(
        EngineConfig(max_slots=4, block_size=BLOCK, cache_dtype=jnp.float32),
        budget=budget,
    )
    assert manager._config_for("m", jcfg).num_blocks == 10
    assert budget.snapshot()["allocated_bytes"]["m"] == state + 10 * per_block
    # the same bytes for a model without per-slot state are all blocks
    plain = decode.TransformerConfig(d_model=16, n_heads=1, n_layers=2, max_len=128)
    budget2 = DeviceBudget(total_bytes=state + 10 * per_block)
    manager2 = ServingManager(
        EngineConfig(max_slots=4, block_size=BLOCK, cache_dtype=jnp.float32),
        budget=budget2,
    )
    assert manager2._config_for("m", plain).num_blocks == (
        (state + 10 * per_block) // pagedkv.block_bytes(plain, BLOCK, jnp.float32)
    )
    # and the engine's own byte budget does the same
    eng = GenerationEngine(
        jcfg, jamba.init(jax.random.PRNGKey(0), jcfg),
        EngineConfig(max_slots=4, block_size=BLOCK, cache_dtype=jnp.float32,
                     kv_budget_bytes=state + 7 * per_block),
    )
    assert eng.stats()["kv_blocks_total"] == 6  # 7 less the trash block
    eng.close()


def test_bundle_round_trip_and_the_manager_builds_the_same_engine(
    model, cfg, jcfg, params, weights
):
    from pygrid_tpu.datacentric.model_storage import HostedModel

    blob = serde.serialize(jamba.bundle(jcfg, params))
    got_cfg, got = decode.from_bundle(serde.deserialize(blob))
    assert got_cfg == jcfg and isinstance(got_cfg, jamba.JambaConfig)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="layer 1"):
        bad = jamba.bundle(jcfg, params)
        bad["params"]["layers"][1].pop("wq")
        decode.from_bundle(bad)
    hosted = HostedModel(
        model_id="j", model=serde.deserialize(blob), allow_remote_inference=True
    )
    manager = ServingManager(
        EngineConfig(max_slots=2, slot_buckets=(1, 2), min_prompt_bucket=8,
                     block_size=BLOCK)
    )
    try:
        eng = manager.engine_for("j", hosted)
        assert type(eng) is GenerationEngine
        prompt = _tokens(70, 10)
        toks = eng.submit(prompt[None], 6, timeout=300)[0]
        assert _gaps(model, weights, cfg, prompt, toks).max() <= TOL
        assert manager.ledger()["balanced"]
        assert manager.stats()[0]["state_bytes"] > 0
    finally:
        manager.close()
