"""The short-convolution / attention family with a dense layer ahead of its
expert layers (``pygrid_tpu/models/lfm2_moe.py``, the ``sigmoid`` half of
``models/moe.py``'s router) through the serving engine, against the plain
reference that the benchmark keeps (``perfbench/models/lfm2_moe.py``:
float32, ``highest``, attention in query blocks, an expert over the
positions routed to it, nothing of the program imported).

Size: the first five of the nine layers the cell holds (a conv layer with
the dense MLP, an attention layer, three conv layers, the last four with 8
experts of 32, two a token), 4 heads of 16 on 2 K/V heads, float32 weights
on the CPU. Everything is compared on LOGITS.

``TOL``: program and reference run the same float32 mathematics in another
order (norms by ``rsqrt``, the expert rows sorted, K/V heads side by side
in the pool); logits of size ~3 differ by at most 1.3e-5 over four seeds
(my CPU runs, PR 47). 5e-5 is four times that, and far under what a fault
does: taps dropped or a bias ignored move a logit by more than 1e-3, bf16
arithmetic by more than 5e-3; a bucket's garbage padding and a poisoned
slot are held to the same limit.
"""

from __future__ import annotations

import functools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pygrid_tpu import serde, telemetry
from pygrid_tpu.models import decode, lfm2_moe, moe
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
CONFIG = ROOT / "perfbench/configs/lfm2-24b-a2b.json"


@pytest.fixture(scope="module")
def model():
    return spec.load_model("lfm2_moe")


@pytest.fixture(scope="module")
def cfg(model):
    cfg = json.loads(CONFIG.read_text())
    cfg.update(model.tiny(cfg))
    return cfg


@pytest.fixture(scope="module")
def lcfg(model, cfg):
    return model.lfm2_config(cfg)


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


def _engine(lcfg, params, model_id="lfm2", **over):
    kw = dict(
        max_slots=4, slot_buckets=(1, 2, 4), min_prompt_bucket=8,
        block_size=BLOCK, quantum=4,
    )
    kw.update(over)
    return GenerationEngine(lcfg, params, EngineConfig(**kw), model_id=model_id)


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


_apply = jax.jit(lfm2_moe.apply, static_argnames=("cfg",))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill(params, cache, table, slot, chunk, length, cfg):
    return lfm2_moe.paged_prefill_chunk(
        params, cache, table, slot, chunk, jnp.int32(0), length, cfg
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def _step(params, cache, table, token, cfg, active=None):
    return lfm2_moe.paged_decode_step(
        params, cache, table, token, cfg, active=active
    )


def _fresh_cache(lcfg, slots=4, poison=False):
    cache = lfm2_moe.init_paged_cache(lcfg, slots, 1 + slots * 8, BLOCK)
    if poison:
        cache = cache._replace(conv=jnp.full_like(cache.conv, jnp.nan))
    table = np.zeros((slots, 8), np.int32)
    for s in range(slots):
        table[s] = 1 + s * 8 + np.arange(8)
    return cache, jnp.asarray(table)


def _prefilled(lcfg, params, lengths, seqs):
    """Slot ``i`` holds the first ``lengths[i]`` tokens of ``seqs[i]``,
    prefilled over taps that were NaN."""
    cache, table = _fresh_cache(lcfg, poison=True)
    for slot, (n, seq) in enumerate(zip(lengths, seqs)):
        chunk = np.zeros(32, np.int32)
        chunk[:n] = seq[:n]
        _, cache, _ = _prefill(
            params, cache, table, jnp.int32(slot), jnp.asarray(chunk),
            jnp.int32(n), lcfg,
        )
    return cache, table


# ── the router: a sigmoid an expert, a bias that chooses and weighs nothing


def _router_case(seed=0, T=64, d=32, E=16):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (T, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, E)) * d**-0.5
    bias = 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (E,))
    return x, w, bias


def test_sigmoid_routing_scores_each_expert_alone():
    x, w, _ = _router_case()
    idx, p = moe.route_topk(x, w, 4, sigmoid=True)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, w, precision="highest")))
    want = np.argsort(-s, axis=1)[:, :4]
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(want, 1))
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(p, chosen / (chosen.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
    # the 1e-6: the weights sum to just under one
    total = np.asarray(p).sum(1)
    assert (total < 1.0).all() and (total > 1.0 - 1e-5).all()


def test_the_bias_changes_the_choice_and_not_the_weights():
    x, w, bias = _router_case()
    plain, _ = moe.route_topk(x, w, 4, sigmoid=True)
    idx, p = moe.route_topk(x, w, 4, sigmoid=True, bias=bias)
    moved = (np.sort(plain, 1) != np.sort(idx, 1)).any(1)
    assert moved.mean() > 0.5  # a bias this large moves most tokens
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, w, precision="highest")))
    want = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :4]
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(want, 1))
    # weighed by the scores WITHOUT the bias
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(p, chosen / (chosen.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
    biased = np.take_along_axis(s + np.asarray(bias), np.asarray(idx), 1)
    assert np.abs(np.asarray(p) - biased / biased.sum(1, keepdims=True)).max() > 1e-2


@pytest.mark.parametrize("key, value", [("routed_scaling_factor", 2.5), ("conv_bias", True)])
def test_the_adapter_refuses_what_the_program_does_not_express(model, cfg, key, value):
    """The family's ``routed_scaling_factor`` is 1: the router multiplies
    by nothing, so a configuration with another scale is refused where a
    convolution with a bias is."""
    with pytest.raises(ValueError, match=key):
        model.lfm2_config(dict(cfg, **{key: value}))


def test_a_zero_bias_is_no_bias():
    x, w, _ = _router_case(2)
    a = moe.route_topk(x, w, 4, sigmoid=True)
    b = moe.route_topk(x, w, 4, sigmoid=True, bias=jnp.zeros((16,)))
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_the_program_s_router_is_the_reference_s(model, cfg):
    x, w, bias = _router_case(3, d=64, E=8)
    idx, p = moe.route_topk(x, w, 2, sigmoid=True, bias=bias)
    with jax.default_matmul_precision("highest"):
        want_idx, want_p = model.route(x, w, bias, cfg)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(p, want_p, rtol=1e-6)


@pytest.mark.parametrize("kernel", [True, False], ids=["pallas-interpreted", "ragged-dot"])
def test_routed_experts_with_a_bias_against_every_expert_computed(kernel):
    """The layer whole: each token's chosen experts (by the biased score),
    weighed by the unbiased ones, against a loop over every expert."""
    key = jax.random.PRNGKey(5)
    T, d, f, E, k = 24, 128, 128, 8, 2
    x = jax.random.normal(key, (T, d))
    draw = lambda i, *shape: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape
    ) * shape[-2] ** -0.5
    w_r, wg, wu, wd = draw(1, d, E), draw(2, E, d, f), draw(3, E, d, f), draw(4, E, f, d)
    bias = 0.2 * jax.random.normal(jax.random.fold_in(key, 5), (E,))
    y, touched, landed = moe.routed_experts(
        x, w_r, wg, wu, wd, k, kernel=kernel, interpret=True, sigmoid=True,
        bias=bias,
    )
    idx, p = moe.route_topk(x, w_r, k, sigmoid=True, bias=bias)
    want = np.zeros((T, d), np.float32)
    for t in range(T):
        for e, pe in zip(np.asarray(idx[t]), np.asarray(p[t])):
            h = jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wu[e])
            want[t] += pe * np.asarray(h @ wd[e])
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=0)
    assert int(landed) == T * k and int(touched) == len(set(np.asarray(idx).ravel()))
    blind, _, _ = moe.routed_experts(
        x, w_r, wg, wu, wd, k, kernel=kernel, interpret=True, sigmoid=True,
    )
    assert np.abs(np.asarray(blind) - want).max() > 1e-2


def test_the_softmax_families_routing_is_what_it_was():
    """``route_topk``'s new arguments leave the softmax callers' program
    as it was: the same lowered text with and without them spelled out,
    and the same values as the formula it has always been."""
    x, w, _ = _router_case(4)
    old = jax.jit(lambda x, w: moe.route_topk(x, w, 4))
    new = jax.jit(lambda x, w: moe.route_topk(x, w, 4, False, None))
    assert old.lower(x, w).as_text() == new.lower(x, w).as_text()
    text = old.lower(x, w).as_text()
    assert "logistic" not in text and "exponential" in text
    idx, p = old(x, w)
    probs = np.asarray(jax.nn.softmax(jnp.dot(x, w, precision="highest"), -1))
    want = np.argsort(-probs, axis=1)[:, :4]
    np.testing.assert_array_equal(idx, want)
    top = np.take_along_axis(probs, want, 1)
    np.testing.assert_allclose(p, top / top.sum(1, keepdims=True), rtol=1e-6)


@pytest.mark.parametrize("family", ["sdar_moe", "solar_open2"])
def test_a_softmax_family_s_expert_layer_never_scores_by_sigmoid(family):
    import importlib

    module = importlib.import_module(f"pygrid_tpu.models.{family}")
    cfg = module.SdarConfig() if family == "sdar_moe" else module.SolarConfig()
    params = module.init(jax.random.PRNGKey(0), cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (8, cfg.d_model))
    c = lambda w: w  # noqa: E731
    text = jax.jit(
        lambda h: module._experts(h, params["layers"][0], c, cfg)[0]
    ).lower(h).as_text()
    route = text[: text.index("moe.experts")] if "moe.experts" in text else text
    assert "logistic" not in route


# ── the family's facts ───────────────────────────────────────────────────


def test_the_family_says_what_the_engine_asks(lcfg):
    assert decode.family_of(lcfg) is lfm2_moe
    assert decode.FAMILIES["lfm2_moe"] == ("Lfm2Config", "pygrid_tpu.models.lfm2_moe")
    assert lfm2_moe.RECURRENT is True and lfm2_moe.BLOCK_LEN == 1
    assert lcfg.attn_ids == (1,) and lcfg.conv_ids == (0, 2, 3, 4)
    assert [lcfg.is_dense(i) for i in range(5)] == [True, False, False, False, False]
    assert (lcfg.n_experts, lcfg.top_k, lcfg.head_dim, lcfg.d_conv) == (8, 2, 16, 3)
    assert lfm2_moe.kv_layers(lcfg) == 1 and lfm2_moe.kv_heads(lcfg) == 2
    assert lfm2_moe.PagedCache._fields == ("k", "v", "pos", "conv")
    assert lfm2_moe.kv_kernel(None, 8) is False
    # two taps of 64 channels a conv layer: the whole of a slot's state
    per_slot = 4 * 2 * 64 * 4
    assert lfm2_moe.state_bytes_per_slot(lcfg, jnp.float32) == per_slot
    assert lfm2_moe.state_bytes_per_slot(lcfg, jnp.bfloat16) == per_slot // 2
    assert pagedkv.state_bytes(lcfg, 4, jnp.float32) == 4 * per_slot
    assert pagedkv.block_bytes(lcfg, BLOCK, jnp.float32) == 2 * 1 * BLOCK * 2 * 16 * 4
    cache = lfm2_moe.init_paged_cache(lcfg, 4, 9, BLOCK)
    # ONE tensor of taps; the pool's heads side by side on the lanes
    assert cache.conv.shape == (4, 2, 4, 64) and cache.k.shape == (1, 9, BLOCK, 32)
    assert cache.k.nbytes == 9 * pagedkv.block_bytes(lcfg, BLOCK, jnp.float32) // 2
    assert lfm2_moe.cache_elements(lcfg, 1) == 2 * 128 * 2 * 16 + 4 * 2 * 64


@pytest.mark.parametrize("which, want", [("cut", 5_177_950_976), ("whole", 23_843_661_440)])
def test_the_program_s_leaves_count_the_published_sizes(model, which, want):
    cfg = json.loads(CONFIG.read_text())
    if which == "whole":
        cfg.update(cfg["deployment"]["published"])
    big = model.lfm2_config(cfg)
    shapes = jax.eval_shape(lambda: lfm2_moe.init(jax.random.PRNGKey(0), big))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == want == model.param_count(cfg)
    if which == "cut":
        assert (big.d_model, big.n_heads, big.n_kv_heads, big.head_dim) == (2048, 32, 8, 64)
        assert (big.d_ff, big.n_experts, big.d_expert, big.top_k) == (11776, 64, 1536, 4)
        assert (big.vocab, big.d_conv, big.max_len, big.n_dense) == (65536, 3, 4608, 1)
        assert big.attn_ids == (1, 5) and len(big.conv_ids) == 7
        assert lfm2_moe.state_bytes_per_slot(big, jnp.bfloat16) == 57_344
        assert pagedkv.block_bytes(big, 64, jnp.bfloat16) == 262_144
        assert lfm2_moe.flash_eligible(big) is False  # on the CPU
    else:
        assert big.attn_ids == tuple(range(2, 40, 4)) and big.n_dense == 2


def test_the_dense_layer_counts_in_no_expert_count(lcfg, params):
    cache, table = _fresh_cache(lcfg)
    chunk = jnp.asarray(_tokens(1, 16))
    _, _, counts = _prefill(params, cache, table, jnp.int32(0), chunk, jnp.int32(16), lcfg)
    read, made, landed = np.asarray(counts)
    # four expert layers of five: the dense layer routes nothing
    assert made == landed == 4 * 16 * 2
    assert read % lfm2_moe.expert_bytes(params, lcfg) == 0
    assert 0 < read <= 4 * 8 * lfm2_moe.expert_bytes(params, lcfg)
    assert lfm2_moe.expert_bytes(params, lcfg) == 3 * 64 * 32 * 4
    only_dense = lcfg._replace(n_layers=1, attn_layers=0)
    assert lfm2_moe.expert_bytes({"layers": params["layers"][:1]}, only_dense) == 0


# ── the full forward, and through the cache ──────────────────────────────


@pytest.mark.parametrize("seed", [3, 4, 5, 6])
def test_full_forward_matches_the_reference(model, cfg, lcfg, seed):
    params = model.make_program_params(seed, cfg, "float32")
    weights = model.make_weights(seed, cfg, "float32")
    seq = _tokens(seed, 96)
    got = _apply(params, jnp.asarray([seq]), lcfg)[0]
    want = _ref_logits(model, weights, cfg, seq)
    assert np.abs(want).max() > 2.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_bf16_arithmetic_in_the_reference_is_outside_the_tolerance(model, cfg, weights):
    seq = _tokens(3, 96)
    want = _ref_logits(model, weights, cfg, seq)
    low = np.asarray(
        model.logits(weights, jnp.asarray([seq]), cfg, jnp.bfloat16)[0], np.float32
    )
    assert np.abs(low - want).max() > 100 * TOL


def test_a_program_that_ignores_the_bias_is_outside_the_tolerance(
    model, cfg, lcfg, params, weights, monkeypatch
):
    seq = _tokens(3, 96)
    want = _ref_logits(model, weights, cfg, seq)
    route = moe.route_topk
    monkeypatch.setattr(
        moe, "route_topk",
        lambda x, w, k, sigmoid=False, bias=None: route(x, w, k, sigmoid, None),
    )
    # a function of its own: ``_apply``'s trace is cached by the function
    blind = jax.jit(lambda p, t: lfm2_moe.apply(p, t, lcfg))(
        params, jnp.asarray([seq])
    )[0]
    assert np.abs(np.asarray(blind) - want).max() > 20 * TOL


@pytest.mark.parametrize("length, bucket", [
    (1, 8), (2, 8), (3, 8), (11, 16), (70, 128), (64, 64),
])
def test_prefill_of_a_padded_bucket_then_decode_matches_on_logits(
    model, cfg, lcfg, params, weights, length, bucket
):
    """A prompt in a bucket whose padding is garbage tokens (prompts of one
    and two tokens among them: a history shorter than the taps), into a
    slot whose taps were NaN: the logits at the prompt's end and at each of
    9 decode steps are the reference's full forward."""
    seq = _tokens(6 + length, length + 9)
    want = _ref_logits(model, weights, cfg, seq)
    cache, table = _fresh_cache(lcfg, poison=True)
    chunk = np.concatenate([seq[:length], _tokens(7, bucket - length)])
    logits, cache, counts = _prefill(
        params, cache, table, jnp.int32(2), jnp.asarray(chunk),
        jnp.int32(length), lcfg,
    )
    np.testing.assert_allclose(logits, want[length - 1], atol=TOL, rtol=0)
    assert int(cache.pos[2]) == length
    taps = np.asarray(cache.conv[:, :, 2])
    assert np.isfinite(taps).all()
    if length == 1:
        assert (taps[:, 0] == 0).all() and (taps[:, 1] != 0).any()
    assert np.asarray(counts)[1] == 4 * bucket * 2
    for t in range(length, length + 9):
        tok = np.zeros(4, np.int32)
        tok[2] = seq[t]
        logits, cache, counts = _step(params, cache, table, jnp.asarray(tok), lcfg)
        np.testing.assert_allclose(logits[2], want[t], atol=TOL, rtol=0)
        assert np.asarray(counts)[1] == 4 * 4 * 2


def test_several_slots_of_unequal_lengths_decode_side_by_side(
    model, cfg, lcfg, params, weights
):
    lengths = (1, 2, 13, 29)
    seqs = [_tokens(40 + i, n + 6) for i, n in enumerate(lengths)]
    wants = [_ref_logits(model, weights, cfg, s) for s in seqs]
    cache, table = _prefilled(lcfg, params, lengths, seqs)
    for step in range(6):
        tok = jnp.asarray([s[n + step] for n, s in zip(lengths, seqs)], jnp.int32)
        logits, cache, _ = _step(params, cache, table, tok, lcfg)
        for slot, n in enumerate(lengths):
            np.testing.assert_allclose(logits[slot], wants[slot][n + step], atol=TOL, rtol=0)
    # slot 1 reused by another prompt while the others go on
    seq = _tokens(50, 12)
    want = _ref_logits(model, weights, cfg, seq)
    chunk = np.zeros(16, np.int32)
    chunk[:7] = seq[:7]
    logits, cache, _ = _prefill(
        params, cache, table, jnp.int32(1), jnp.asarray(chunk), jnp.int32(7), lcfg
    )
    np.testing.assert_allclose(logits, want[6], atol=TOL, rtol=0)
    tok = jnp.asarray([0, seq[7], 0, 0], jnp.int32)
    logits, cache, _ = _step(params, cache, table, tok, lcfg)
    np.testing.assert_allclose(logits[1], want[7], atol=TOL, rtol=0)


def test_a_slot_s_state_after_prefill_is_independent_of_what_it_held(lcfg, params):
    """The invariant the engine relies on: a prefill writes ALL of the
    slot's taps from the prompt alone, whatever ran in the slot before."""
    chunk = np.zeros(32, np.int32)
    chunk[:21] = _tokens(12, 21)
    states = []
    for before in (0.0, 7.0, jnp.nan):
        cache, table = _fresh_cache(lcfg)
        cache = cache._replace(
            conv=cache.conv + before,
            k=cache.k + (0.0 if before != before else before),
        )
        _, cache, _ = _prefill(
            params, cache, table, jnp.int32(1), jnp.asarray(chunk), jnp.int32(21),
            lcfg,
        )
        states.append(np.asarray(cache.conv[:, :, 1]))
        # and nobody else's taps were touched
        other = np.asarray(cache.conv[:, :, 0])
        assert (other == before).all() or (before != before and np.isnan(other).all())
    for conv in states[1:]:
        np.testing.assert_array_equal(conv, states[0])


def test_dropped_taps_and_a_frozen_row_show_on_the_logits(
    model, cfg, lcfg, params, weights
):
    seq = _tokens(8, 14)
    want = _ref_logits(model, weights, cfg, seq)
    cache, table = _fresh_cache(lcfg)
    chunk = np.zeros(16, np.int32)
    chunk[:12] = seq[:12]
    _, cache, _ = _prefill(
        params, cache, table, jnp.int32(0), jnp.asarray(chunk), jnp.int32(12), lcfg
    )
    tok = jnp.asarray([seq[12], 0], jnp.int32)
    good, after, _ = _step(params, cache, table, tok, lcfg)
    np.testing.assert_allclose(good[0], want[12], atol=TOL, rtol=0)
    # the taps dropped: the same step from zeroed taps
    dropped = cache._replace(conv=jnp.zeros_like(cache.conv))
    bad, _, _ = _step(params, dropped, table, tok, lcfg)
    assert np.abs(np.asarray(bad[0]) - want[12]).max() > 10 * TOL
    # the taps in the wrong order: as far off
    swapped = cache._replace(conv=cache.conv[:, ::-1])
    bad, _, _ = _step(params, swapped, table, tok, lcfg)
    assert np.abs(np.asarray(bad[0]) - want[12]).max() > 10 * TOL
    tok2 = jnp.asarray([seq[13], 5], jnp.int32)
    live, _, _ = _step(params, after, table, tok2, lcfg)
    frozen, parked, _ = _step(
        params, after, table, tok2, lcfg, active=jnp.asarray([True, False])
    )
    np.testing.assert_allclose(frozen[0], live[0], atol=1e-6, rtol=0)
    np.testing.assert_allclose(frozen[0], want[13], atol=TOL, rtol=0)
    assert parked.pos.tolist()[:2] == [14, int(after.pos[1])]


def test_the_flash_kernel_gives_the_prompt_s_attention_its_scores_unbuilt(lcfg, params):
    """The attention layer through ``flash_attention`` (interpreted; what a
    TPU takes at a head of 64, two heads a lane tile) against the scores
    built whole."""
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (48, 64))
    c = lambda w: w  # noqa: E731
    plain, k, v = lfm2_moe._attn_seq(x, lp, c, lcfg, jnp.float32, flash=False)
    fused, k2, v2 = lfm2_moe._attn_seq(x, lp, c, lcfg, jnp.float32, flash=True)
    np.testing.assert_allclose(fused, plain, atol=2e-6, rtol=0)
    np.testing.assert_array_equal(k, k2)
    assert k.shape == (48, 2, 16) and v2.shape == (48, 2, 16)


def test_prefill_and_decode_round_the_taps_alike(lcfg, params):
    """With a bfloat16 cache the convolution's input is rounded where it
    is produced: the taps a prefill leaves are bit for bit what decode
    steps over the same tokens leave."""
    seq = _tokens(9, 10)
    cache = lfm2_moe.init_paged_cache(lcfg, 2, 17, BLOCK, jnp.bfloat16)
    table = jnp.asarray(1 + np.arange(16).reshape(2, 8), jnp.int32)
    # an array of its own each: JAX may read a numpy array's own memory
    # while its program is still in flight
    ten, eight = np.zeros(16, np.int32), np.zeros(16, np.int32)
    ten[:10], eight[:8] = seq, seq[:8]
    _, whole, _ = _prefill(
        params, cache, table, jnp.int32(0), jnp.asarray(ten), jnp.int32(10), lcfg
    )
    _, stepped, _ = _prefill(
        params, cache, table, jnp.int32(0), jnp.asarray(eight), jnp.int32(8), lcfg
    )
    for t in (8, 9):
        _, stepped, _ = _step(
            params, stepped, table, jnp.asarray([seq[t], 0], jnp.int32), lcfg
        )
    assert whole.conv.dtype == jnp.bfloat16
    # layer 0 sees the embeddings alone: its taps must be identical
    np.testing.assert_array_equal(
        np.asarray(whole.conv[0, :, 0], np.float32),
        np.asarray(stepped.conv[0, :, 0], np.float32),
    )


# ── through the engine ───────────────────────────────────────────────────


@pytest.mark.parametrize("fused", [False, True], ids=["per-step", "fused"])
def test_engine_serves_the_references_tokens_staggered_and_reused(
    model, cfg, lcfg, params, weights, fused
):
    """Six requests on two slots: staggered admissions, prompts that do
    not fill their bucket, prompts of one and two tokens, different
    ``n_new`` so that rows finish mid-scan (quantum 4), every slot reused.
    Each served token is the reference's best to ``TOL``."""
    eng = _engine(lcfg, params, max_slots=2, slot_buckets=(1, 2), fused=fused)
    shapes = [(1, 7), (2, 3), (70, 10), (8, 5), (13, 6), (30, 9)]
    try:
        prompts = [_tokens(20 + i, p) for i, (p, _) in enumerate(shapes)]
        futures = [eng.enqueue(p[None], n) for p, (_, n) in zip(prompts, shapes)]
        served = [f.result(300)[0] for f in futures]
        for prompt, toks, (_, n) in zip(prompts, served, shapes):
            assert toks.shape == (n,)
            assert _gaps(model, weights, cfg, prompt, toks).max() <= TOL
        again = _tokens(32, 12)
        toks = eng.submit(again[None], 6, timeout=300)[0]
        assert _gaps(model, weights, cfg, again, toks).max() <= TOL
        stats = eng.stats()
        assert stats["fused"] is fused
        assert stats["kv_kernel"] == 0 and stats["prefix_hits"] == 0
        assert eng.ledger()["balanced"] and eng.ledger()["drained"]
    finally:
        eng.close()


# ── decode attention by the wide kernel (the rule patched true) ─────────


def _step_by(monkeypatch, kernel, lcfg):
    """The decode step traced anew with the family's rule answering
    ``kernel``: off the TPU the kernel path interprets the same kernel."""
    monkeypatch.setattr(lfm2_moe, "kv_kernel", lambda *a: kernel)
    return jax.jit(
        lambda params, cache, table, token, active=None:
        lfm2_moe.paged_decode_step(
            params, cache, table, token, lcfg, active=active
        )
    )


def test_the_kernel_path_decodes_the_gather_path_s_logits(
    model, cfg, lcfg, params, weights, monkeypatch
):
    """Three prompts of unequal lengths prefilled through the cache, a
    free slot (zeroed table row, a stale position) beside them, six steps
    on either path: the same logits to float32 rounding, the reference's
    to ``TOL``, and the same cache."""
    lengths = (1, 13, 29)
    seqs = [_tokens(60 + i, n + 6) for i, n in enumerate(lengths)]
    wants = [_ref_logits(model, weights, cfg, s) for s in seqs]
    start, table = _prefilled(lcfg, params, lengths, seqs)
    table = table.at[3].set(0)
    start = start._replace(pos=start.pos.at[3].set(41))
    caches = {}
    for kernel in (True, False):
        step, cache, rows = _step_by(monkeypatch, kernel, lcfg), start, []
        for t in range(6):
            tok = [s[n + t] for n, s in zip(lengths, seqs)] + [0]
            logits, cache, _ = step(params, cache, table, jnp.asarray(tok, jnp.int32))
            rows.append(np.asarray(logits[:3]))
        caches[kernel] = (np.stack(rows), cache)
    got, want = caches[True][0], caches[False][0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    for slot, n in enumerate(lengths):
        np.testing.assert_allclose(
            got[:, slot], wants[slot][n : n + 6], atol=TOL, rtol=0
        )
    ours, theirs = caches[True][1], caches[False][1]
    np.testing.assert_array_equal(ours.pos, theirs.pos)
    live = np.asarray(table[:3]).ravel()
    np.testing.assert_allclose(ours.k[:, live], theirs.k[:, live], rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(ours.v[:, live], theirs.v[:, live], rtol=2e-5, atol=2e-6)


def test_a_frozen_row_on_the_kernel_path_keeps_its_pages_and_position(
    lcfg, params, monkeypatch
):
    lengths = (5, 17, 30, 2)
    seqs = [_tokens(70 + i, n + 1) for i, n in enumerate(lengths)]
    cache, table = _prefilled(lcfg, params, lengths, seqs)
    active = jnp.asarray([True, False, True, True])
    tok = jnp.asarray([s[n] for n, s in zip(lengths, seqs)], jnp.int32)
    logits, new, _ = _step_by(monkeypatch, True, lcfg)(params, cache, table, tok, active)
    want, ref, _ = _step_by(monkeypatch, False, lcfg)(params, cache, table, tok, active)
    live = np.array([0, 2, 3])
    np.testing.assert_allclose(logits[live], want[live], rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(new.pos, [6, 17, 31, 3])
    np.testing.assert_array_equal(new.pos, ref.pos)
    frozen = np.asarray(table[1])
    np.testing.assert_array_equal(new.k[:, frozen], cache.k[:, frozen])
    np.testing.assert_array_equal(new.v[:, frozen], cache.v[:, frozen])
    # an active row did append: its page changed at its old position
    own = int(table[0, 0])
    assert not np.array_equal(new.k[:, own, 5], cache.k[:, own, 5])


@pytest.mark.parametrize("fused", [False, True], ids=["per-step", "fused"])
def test_an_engine_on_the_kernel_path_counts_live_pages(
    model, cfg, lcfg, params, weights, monkeypatch, fused
):
    """The engine reads the family's rule when it is built: the gauge's
    row says 1, a dispatch counts the pages its rows' lengths span (fewer
    than their tables hold), and the served tokens are the reference's."""
    monkeypatch.setattr(lfm2_moe, "kv_kernel", lambda *a: True)
    before = {k: _count("serving_kv_pages_total", kind=k) for k in ("read", "table")}
    eng = _engine(lcfg, params, model_id="lfm2-wide", max_slots=2,
                  slot_buckets=(1, 2), fused=fused)
    shapes = [(3, 7), (40, 9), (13, 6)]
    try:
        prompts = [_tokens(80 + i, p) for i, (p, _) in enumerate(shapes)]
        futures = [eng.enqueue(p[None], n) for p, (_, n) in zip(prompts, shapes)]
        served = [f.result(300)[0] for f in futures]
        stats = eng.stats()
        assert eng.ledger()["balanced"] and eng.ledger()["drained"]
    finally:
        eng.close()
    for prompt, toks, (_, n) in zip(prompts, served, shapes):
        assert toks.shape == (n,)
        assert _gaps(model, weights, cfg, prompt, toks).max() <= TOL
    assert stats["kv_kernel"] == 1
    read, held = (
        _count("serving_kv_pages_total", kind=k) - before[k]
        for k in ("read", "table")
    )
    assert 0 < read < held


def test_the_counters_and_the_telemetry_row(lcfg, params):
    before = {
        (name, kind): _count(name, kind=kind)
        for name in (
            "serving_prefill_tokens_total", "serving_state_bytes_total",
            "serving_expert_rows_total",
        )
        for kind in ("true", "padded", "read", "written", "routed", "held")
    }
    paths = {p: _count("serving_expert_bytes_total", kind="read", path=p)
             for p in ("prefill", "step")}
    eng = _engine(lcfg, params, model_id="lfm2-count", max_slots=2,
                  slot_buckets=(1, 2), fused=False)
    try:
        eng.submit(_tokens(50, 11)[None], 5, timeout=300)  # 11 in a bucket of 16
        stats, ledger = eng.stats(), eng.ledger()
    finally:
        eng.close()
    grew = lambda name, kind: _count(name, kind=kind) - before[name, kind]  # noqa: E731
    per_slot = pagedkv.state_bytes(lcfg, 1, jnp.float32)
    assert stats["state_bytes_per_slot"] == per_slot == ledger["state_bytes"] // 2
    assert stats["weights_bytes"] == sum(x.nbytes for x in jax.tree.leaves(params))
    assert grew("serving_prefill_tokens_total", "true") == 11
    assert grew("serving_prefill_tokens_total", "padded") == 16
    # four decode steps of one live row read and write its taps; the
    # prefill wrote them once
    assert grew("serving_state_bytes_total", "read") == 4 * per_slot
    assert grew("serving_state_bytes_total", "written") == 5 * per_slot
    # the experts, as the programs counted them: a prefill of 16 positions
    # and four steps of width 1, FOUR expert layers, two choices a position
    routed = grew("serving_expert_rows_total", "routed")
    assert routed == 4 * 2 * (16 + 4) == grew("serving_expert_rows_total", "held")
    expert = lfm2_moe.expert_bytes(params, lcfg)
    for path, most in (("prefill", 4 * 8), ("step", 4 * 4 * 2)):
        read = _count("serving_expert_bytes_total", kind="read", path=path) - paths[path]
        assert 0 < read <= most * expert and read % expert == 0


def test_the_budget_charges_the_taps_before_it_grants_blocks(lcfg):
    per_block = pagedkv.block_bytes(lcfg, BLOCK, jnp.float32)
    state = pagedkv.state_bytes(lcfg, 4, jnp.float32)
    budget = DeviceBudget(total_bytes=state + 10 * per_block)
    manager = ServingManager(
        EngineConfig(max_slots=4, block_size=BLOCK, cache_dtype=jnp.float32),
        budget=budget,
    )
    assert manager._config_for("m", lcfg).num_blocks == 10
    assert budget.snapshot()["allocated_bytes"]["m"] == state + 10 * per_block
    eng = GenerationEngine(
        lcfg, lfm2_moe.init(jax.random.PRNGKey(0), lcfg),
        EngineConfig(max_slots=4, block_size=BLOCK, cache_dtype=jnp.float32,
                     kv_budget_bytes=state + 7 * per_block),
    )
    assert eng.stats()["kv_blocks_total"] == 6  # 7 less the trash block
    eng.close()


def test_bundle_round_trip_and_the_manager_builds_the_same_engine(
    model, cfg, lcfg, params, weights
):
    from pygrid_tpu.datacentric.model_storage import HostedModel

    blob = serde.serialize(lfm2_moe.bundle(lcfg, params))
    got_cfg, got = decode.from_bundle(serde.deserialize(blob))
    assert got_cfg == lcfg and isinstance(got_cfg, lfm2_moe.Lfm2Config)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="layer 1"):
        bad = lfm2_moe.bundle(lcfg, params)
        bad["params"]["layers"][1].pop("expert_bias")
        decode.from_bundle(bad)
    with pytest.raises(ValueError, match="layer 0"):
        bad = lfm2_moe.bundle(lcfg, params)
        bad["params"]["layers"][0]["router"] = np.zeros((64, 8), np.float32)
        decode.from_bundle(bad)
    hosted = HostedModel(
        model_id="l", model=serde.deserialize(blob), allow_remote_inference=True
    )
    manager = ServingManager(
        EngineConfig(max_slots=2, slot_buckets=(1, 2), min_prompt_bucket=8,
                     block_size=BLOCK)
    )
    try:
        eng = manager.engine_for("l", hosted)
        assert type(eng) is GenerationEngine
        prompt = _tokens(70, 10)
        toks = eng.submit(prompt[None], 6, timeout=300)[0]
        assert _gaps(model, weights, cfg, prompt, toks).max() <= TOL
        assert manager.ledger()["balanced"]
        assert manager.stats()[0]["state_bytes"] > 0
    finally:
        manager.close()
