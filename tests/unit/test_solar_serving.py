"""The delta-rule / softmax family with a chip's share of its experts
(``pygrid_tpu/models/solar_open2.py``, ``models/kda.py``, the ``held`` half
of ``models/moe.py``) through the serving engine, against the plain
reference that the benchmark keeps (``perfbench/models/solar_open2.py``:
float32, ``highest``, the recurrence a token at a time, attention in query
blocks, nothing of the program imported).

Size: one period of the layer pattern (softmax at layer 0, three
delta-rule layers), 4 heads of 16 on 2 K/V heads, 2 of the router's 16
experts held (an eighth, as the cell's 40 of 320) beside the shared one,
float32 weights on the CPU. Everything is compared on LOGITS.

``TOL``: program and reference run the same float32 mathematics in another
order (the program solves a chunk's 64 positions as one triangular system
and carries the state transposed, norms by ``rsqrt``, sorts its expert
rows); logits of size ~4 differ by at most 2.0e-5 over four seeds (my CPU
runs, PR 36). 5e-5 is 2.5 times that, and thousands of times under what a
fault does: a dropped state moves a logit by more than 5e-4 at the least,
a bucket's garbage padding and a poisoned slot are held to the same limit.
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
from pygrid_tpu.models import decode, kda, moe, solar_open2
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
    return spec.load_model("solar_open2")


@pytest.fixture(scope="module")
def cfg(model):
    cfg = json.loads((ROOT / "perfbench/configs/solar-open2-250b.json").read_text())
    cfg.update(model.tiny(cfg))
    return cfg


@pytest.fixture(scope="module")
def scfg(model, cfg):
    return model.solar_config(cfg)


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


def _engine(scfg, params, model_id="solar", **over):
    kw = dict(
        max_slots=4, slot_buckets=(1, 2, 4), min_prompt_bucket=8,
        block_size=BLOCK, quantum=4,
    )
    kw.update(over)
    return GenerationEngine(scfg, params, EngineConfig(**kw), model_id=model_id)


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


# ── the recurrence: chunks against a token at a time ─────────────────────


def _per_token(q, k, v, g, beta):
    """The delta rule as published, one position at a time; the state
    ``[H, d_k, d_v]``."""

    def step(S, xs):
        q, k, v, g, b = xs
        S = jnp.exp(g)[:, :, None] * S
        u = v - jnp.einsum("hkv,hk->hv", S, k)
        S = S + b[:, None, None] * k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q)

    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    S, o = jax.lax.scan(step, jnp.zeros((H, dk, dv)), (q, k, v, g, beta))
    return o, S


def _draw(seed, P, length, decay=1.0, H=2, d=16, tweak=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (P, H, d))) * d**-0.5
    k = unit(jax.random.normal(ks[1], (P, H, d)))
    v = jax.random.normal(ks[2], (P, H, d))
    g = -decay * jnp.exp(1.5 * jax.random.normal(ks[3], (P, H, d)) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (P, H)))
    if tweak == "fast-channel":
        # two channels of every head lose e^-20 a position, give or take
        g = g.at[:, :, :2].set(-20.0 + g[:, :, :2])
    elif tweak == "beta-2":
        beta = jnp.full_like(beta, 2.0)
    real = jnp.arange(P) < length
    return (
        q, k, v, jnp.where(real[:, None, None], g, 0.0),
        jnp.where(real[:, None], beta, 0.0),
    )


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas-interpreted"])
@pytest.mark.parametrize("P, length, decay, tweak", [
    (64, 64, 1.0, None),      # one whole chunk
    (16, 9, 1.0, None),       # a bucket under a chunk, mostly padding
    (128, 100, 1.0, None),    # the second chunk part padding
    (200, 137, 0.02, None),   # not a multiple of the chunk; slow decays: N is dense
    (192, 70, 1.0, None),     # a whole chunk of nothing but padding
    (128, 128, 40.0, None),   # decays of e^-40 a position: nothing overflows
    # what the grouping by levels and by pairs of chunks could break (PR 42)
    (128, 128, 1.0, "fast-channel"),  # every split exponent stays finite
    (128, 128, 0.0, None),    # g = 0 throughout: N as dense as it gets
    (128, 128, 1.0, "beta-2"),  # every beta at its ceiling
    (128, 75, 1.0, None),     # the length ends inside a block of every level
    (384, 70, 1.0, None),     # two whole groups of padding behind a part chunk
])
def test_chunks_equal_the_recurrence_a_token_at_a_time(
    kernel, P, length, decay, tweak
):
    """``kda.chunked`` (through XLA and as the Pallas kernels, interpreted)
    against the published recurrence, for lengths that are no multiple of
    the chunk, padding behind the sequence, slow, absent and violent
    decays: the outputs of the real positions and the state after the
    last of them, and not one of them anything but a number."""
    q, k, v, g, beta = _draw(P + length, P, length, decay, tweak=tweak)
    with jax.default_matmul_precision("highest"):
        want_o, want_S = _per_token(q, k, v, g, beta)
    o, St = kda.chunked(
        q, k, v, g, beta, jnp.int32(length), kernel=kernel, interpret=True
    )
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(St).all())
    np.testing.assert_allclose(o[:length], want_o[:length], atol=1e-5, rtol=0)
    np.testing.assert_allclose(St, want_S.transpose(0, 2, 1), atol=2e-5, rtol=0)


def test_a_decode_step_continues_where_the_chunks_stopped():
    q, k, v, g, beta = _draw(5, 80, 80)
    with jax.default_matmul_precision("highest"):
        want_o, _ = _per_token(q, k, v, g, beta)
    _, St = kda.chunked(
        q[:70], k[:70], v[:70], g[:70], beta[:70], jnp.int32(70), kernel=False
    )
    St = St[None]
    for t in range(70, 80):
        o, St = kda.step(St, q[t][None], k[t][None], v[t][None], g[t][None], beta[t][None])
        np.testing.assert_allclose(o[0], want_o[t], atol=5e-6, rtol=0)


# ── the share of the experts ─────────────────────────────────────────────


def test_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(model, cfg):
    """The test that ties the share to the model: the routed parts that
    all 8 shares of 2 experts give, plus the shared expert counted once,
    are the reference's uncut 16-expert layer; and a share's own result
    is the reference given the same share."""
    uncut = dict(cfg, n_routed_experts=16)
    w = model.make_weights(5, uncut, "float32")
    lw = w["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(9), (40, 64))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model._experts(x, lw, uncut, False))
    shared = moe.shared_expert(x, lw["s_gate"], lw["s_up"], lw["s_down"])
    total, rows = shared, 0
    for share in range(8):
        at = slice(2 * share, 2 * share + 2)
        y, touched, landed = moe.routed_experts(
            x, lw["router"], lw["w_gate"][at], lw["w_up"][at], lw["w_down"][at],
            2, interpret=True, held=(2 * share, 2),
        )
        assert 0 <= int(touched) <= 2 and int(landed) >= int(touched)
        total, rows = total + y, rows + int(landed)
        one = dict(cfg, deployment=dict(cfg["deployment"], held_first=2 * share))
        lw_share = dict(lw, **{n: lw[n][at] for n in ("w_gate", "w_up", "w_down")})
        with jax.default_matmul_precision("highest"):
            mine = np.asarray(model._experts(x, lw_share, one, False))
        np.testing.assert_allclose(shared + y, mine, atol=2e-6, rtol=0)
    assert rows == 40 * 2  # every assignment fell on exactly one share
    np.testing.assert_allclose(total, want, atol=5e-6, rtol=0)


@pytest.mark.parametrize("kernel", [True, False], ids=["pallas-interpreted", "ragged-dot"])
def test_a_share_takes_no_row_for_an_expert_it_does_not_hold(params, kernel):
    lp = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 64))
    idx, p = moe.route_topk(x, lp["router"], 2)
    here = np.asarray(idx) < 2  # experts 0 and 1 of 16 are held
    y, touched, landed = jax.jit(lambda x: moe.routed_experts(
        x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], 2,
        kernel=kernel, interpret=True, held=(0, 2),
    ))(x)
    assert int(landed) == here.sum() and 0 < here.sum() < 100
    assert int(touched) == len(np.unique(np.asarray(idx)[here]))
    ffn = lambda e: (  # noqa: E731
        jax.nn.silu(x @ lp["w_gate"][e]) * (x @ lp["w_up"][e])
    ) @ lp["w_down"][e]
    want = sum(
        jnp.where((idx == e).any(-1)[:, None], ffn(e), 0.0)
        * jnp.where(idx == e, p, 0.0).sum(-1)[:, None]
        for e in range(2)
    )
    np.testing.assert_allclose(y, want, atol=2e-6, rtol=0)
    # a position none of whose choices is held gets exactly nothing
    assert not np.asarray(y)[~here.any(-1)].any()


def test_a_router_that_sends_every_token_here_takes_more_rounds_and_drops_nothing(params):
    """The tile layout is sized for twice the even share of the
    assignments (32 rows of these 100); a router that sends every token
    to the two held experts fills it four times over, and every
    assignment is computed all the same."""
    lp = params["layers"][3]
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (50, 64)))
    router = 1e-3 * lp["router"] + jnp.zeros_like(lp["router"]).at[:, :2].set(5.0)
    args = (x, router, lp["w_gate"], lp["w_up"], lp["w_down"], 2)
    y, touched, landed = jax.jit(lambda *a: moe.routed_experts(
        *a, 2, kernel=True, interpret=True, held=(0, 2)
    ))(*args[:5])
    assert int(landed) == 100 and int(touched) == 2
    every, _, _ = moe.routed_experts(*args, kernel=False)  # all of them here
    plain, _, _ = moe.routed_experts(*args, kernel=False, held=(0, 2))
    np.testing.assert_allclose(y, plain, atol=2e-6, rtol=0)
    np.testing.assert_allclose(y, every, atol=2e-6, rtol=0)


@pytest.mark.parametrize("kernel", [True, False], ids=["pallas-interpreted", "ragged-dot"])
def test_a_forward_whose_every_choice_is_absent_adds_nothing(params, kernel):
    """A decode step of one row lands on no held expert one time in three:
    the layout then has no live tile, and the kernel's every grid step
    stays on the first block (an index of -1 halts the chip: PERF.md §6,
    PR 36)."""
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 64))
    idx, _ = moe.route_topk(x, lp["router"], 2)
    first = int(np.asarray(idx).max()) + 1  # a share above every choice
    assert first < 16
    y, touched, landed = moe.routed_experts(
        x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], 2,
        kernel=kernel, interpret=True, held=(first, 2),
    )
    assert int(touched) == int(landed) == 0 and not np.asarray(y).any()
    ids = jnp.full((6,), 2, jnp.int32)
    _, tile_expert, n_live, _ = moe.grouped_layout(ids, 2, tile=4)
    assert int(n_live) == 0 and tile_expert.tolist() == [0] * 4
    assert [int(moe._row_block(t, n_live)) for t in range(4)] == [0] * 4
    assert [int(moe._row_block(t, jnp.int32(2))) for t in range(4)] == [0, 1, 1, 1]


def test_the_layout_gives_an_absent_expert_s_assignments_no_row():
    ids = jnp.asarray([3, 0, 3, 1, 3, 3, 0, 3], jnp.int32)  # 3: not held
    dest, tile_expert, n_live, sizes = moe.grouped_layout(ids, 3, tile=4)
    assert sizes.tolist() == [2, 1, 0] and int(n_live) == 2
    n_rows = tile_expert.shape[0] * 4
    assert n_rows == (8 // 4 + 3) * 4  # sized for every assignment held
    held = np.asarray(ids) < 3
    assert (np.asarray(dest)[~held] == n_rows).all()  # past the layout's end
    assert sorted(np.asarray(dest)[held].tolist()) == [0, 1, 4]
    assert tile_expert.tolist()[:2] == [0, 1]


# ── the family's facts ───────────────────────────────────────────────────


def test_the_family_says_what_the_engine_asks(scfg):
    assert decode.family_of(scfg) is solar_open2
    assert solar_open2.RECURRENT is True and solar_open2.BLOCK_LEN == 1
    assert scfg.gqa_layers == (0,) and scfg.kda_layers == (1, 2, 3)
    assert (scfg.n_experts, scfg.held_first, scfg.held_count) == (16, 0, 2)
    assert solar_open2.kv_layers(scfg) == 1 and solar_open2.kv_heads(scfg) == 2
    assert solar_open2.PagedCache._fields == ("k", "v", "pos", "delta", "conv")
    # a float32 matrix a head and three taps of q, k and v, a delta-rule layer
    per_slot = 3 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert solar_open2.state_bytes_per_slot(scfg, jnp.float32) == per_slot
    assert pagedkv.state_bytes(scfg, 4, jnp.float32) == 4 * per_slot
    assert pagedkv.block_bytes(scfg, BLOCK, jnp.float32) == 2 * 1 * BLOCK * 2 * 16 * 4
    cache = solar_open2.init_paged_cache(scfg, 4, 9, BLOCK)
    assert cache.delta.shape == (3, 4, 4, 16, 16) and cache.delta.dtype == jnp.float32
    assert cache.conv.shape == (3, 3, 4, 192) and cache.k.shape == (1, 9, BLOCK, 2, 16)
    assert solar_open2.cache_elements(scfg, 1) == (
        2 * 128 * 2 * 16 + 3 * 64 * (16 + 9)
    )


def test_published_sizes_by_hand(model):
    cfg = json.loads((ROOT / "perfbench/configs/solar-open2-250b.json").read_text())
    big = model.solar_config(cfg)
    assert (big.n_experts, big.held_count, big.vocab, big.n_layers) == (320, 40, 24576, 4)
    assert solar_open2.state_bytes_per_slot(big, jnp.bfloat16) == 13_025_280
    assert pagedkv.block_bytes(big, 64, jnp.bfloat16) == 262_144
    shapes = jax.eval_shape(lambda: solar_open2.init(jax.random.PRNGKey(0), big))
    # the configuration file's byte arithmetic, against the program's leaves
    leaves = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert leaves == cfg["deployment"]["parameters"] == 3_308_352_064
    cache = jax.eval_shape(lambda: solar_open2.init_paged_cache(big, 64, 8449, 64, jnp.bfloat16))
    assert cache.delta.shape == (3, 64, 64, 128, 128)
    held = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in (cache.delta, cache.conv, cache.k, cache.v)
    )
    assert held == cfg["deployment"]["env"]["PYGRID_KV_BUDGET"]


# ── the forward, prefill and decode, against the reference ───────────────


_apply = jax.jit(solar_open2.apply, static_argnames=("cfg",))


@pytest.mark.parametrize("seed", [3, 4, 5, 6])
def test_full_forward_matches_the_reference(model, cfg, scfg, seed):
    params = model.make_program_params(seed, cfg, "float32")
    weights = model.make_weights(seed, cfg, "float32")
    seq = _tokens(seed, 96)
    got = _apply(params, jnp.asarray([seq]), scfg)[0]
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


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill(params, cache, table, slot, chunk, length, cfg):
    return solar_open2.paged_prefill_chunk(
        params, cache, table, slot, chunk, jnp.int32(0), length, cfg
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def _step(params, cache, table, token, cfg, active=None):
    return solar_open2.paged_decode_step(
        params, cache, table, token, cfg, active=active
    )


def _fresh_cache(scfg, slots=4, poison=False):
    cache = solar_open2.init_paged_cache(scfg, slots, 1 + slots * 8, BLOCK)
    if poison:
        cache = cache._replace(
            delta=jnp.full_like(cache.delta, jnp.nan),
            conv=jnp.full_like(cache.conv, jnp.nan),
        )
    table = np.zeros((slots, 8), np.int32)
    for s in range(slots):
        table[s] = 1 + s * 8 + np.arange(8)
    return cache, jnp.asarray(table)


@pytest.mark.parametrize("length, bucket", [(11, 16), (70, 128), (64, 64)])
def test_prefill_of_a_padded_bucket_then_decode_matches_on_logits(
    model, cfg, scfg, params, weights, length, bucket
):
    """A prompt in a bucket whose padding is garbage tokens, into a slot
    whose state was NaN: the logits at the prompt's end and at each of 9
    decode steps are the reference's full forward."""
    seq = _tokens(6 + length, length + 9)
    want = _ref_logits(model, weights, cfg, seq)
    cache, table = _fresh_cache(scfg, poison=True)
    chunk = np.concatenate([seq[:length], _tokens(7, bucket - length)])
    logits, cache, counts = _prefill(
        params, cache, table, jnp.int32(2), jnp.asarray(chunk),
        jnp.int32(length), scfg,
    )
    np.testing.assert_allclose(logits, want[length - 1], atol=TOL, rtol=0)
    assert int(cache.pos[2]) == length
    assert np.isfinite(np.asarray(cache.delta[:, 2])).all()
    # what the forward counted: bytes of whole experts, every assignment,
    # and the ~eighth of them that fell on the two experts held
    read, made, landed = np.asarray(counts)
    assert read % solar_open2.expert_bytes(params) == 0 and read > 0
    assert made == 4 * bucket * 2 and 0 < landed < made / 2
    for t in range(length, length + 9):
        tok = np.zeros(4, np.int32)
        tok[2] = seq[t]
        logits, cache, counts = _step(params, cache, table, jnp.asarray(tok), scfg)
        np.testing.assert_allclose(logits[2], want[t], atol=TOL, rtol=0)
        assert np.asarray(counts)[1] == 4 * 4 * 2


def test_a_slot_s_state_after_prefill_is_independent_of_what_it_held(scfg, params):
    """The invariant the engine relies on: a prefill writes the slot's
    WHOLE state from zero, whatever ran in the slot before."""
    chunk = np.zeros(32, np.int32)
    chunk[:21] = _tokens(12, 21)
    states = []
    for before in (0.0, 7.0, jnp.nan):
        cache, table = _fresh_cache(scfg)
        cache = cache._replace(
            delta=cache.delta + before, conv=cache.conv + before,
            k=cache.k + (0.0 if before != before else before),
        )
        _, cache, _ = _prefill(
            params, cache, table, jnp.int32(1), jnp.asarray(chunk), jnp.int32(21),
            scfg,
        )
        states.append((np.asarray(cache.delta[:, 1]), np.asarray(cache.conv[:, :, 1])))
    for delta, conv in states[1:]:
        np.testing.assert_array_equal(delta, states[0][0])
        np.testing.assert_array_equal(conv, states[0][1])


def test_a_dropped_state_and_a_frozen_row_show_on_the_logits(
    model, cfg, scfg, params, weights
):
    seq = _tokens(8, 14)
    want = _ref_logits(model, weights, cfg, seq)
    cache, table = _fresh_cache(scfg)
    chunk = np.zeros(16, np.int32)
    chunk[:12] = seq[:12]
    _, cache, _ = _prefill(
        params, cache, table, jnp.int32(0), jnp.asarray(chunk), jnp.int32(12), scfg
    )
    tok = jnp.asarray([seq[12], 0], jnp.int32)
    good, after, _ = _step(params, cache, table, tok, scfg)
    np.testing.assert_allclose(good[0], want[12], atol=TOL, rtol=0)
    # the state dropped: the same step from a zeroed delta-rule state
    dropped = cache._replace(delta=jnp.zeros_like(cache.delta))
    bad, _, _ = _step(params, dropped, table, tok, scfg)
    assert np.abs(np.asarray(bad[0]) - want[12]).max() > 10 * TOL
    tok2 = jnp.asarray([seq[13], 5], jnp.int32)
    live, _, _ = _step(params, after, table, tok2, scfg)
    frozen, parked, _ = _step(
        params, after, table, tok2, scfg, active=jnp.asarray([True, False])
    )
    np.testing.assert_allclose(frozen[0], live[0], atol=1e-6, rtol=0)
    np.testing.assert_allclose(frozen[0], want[13], atol=TOL, rtol=0)
    assert parked.pos.tolist()[:2] == [14, int(after.pos[1])]


def test_the_flash_kernel_gives_the_prompt_s_attention_its_scores_unbuilt(scfg, params):
    """The softmax layer through ``flash_attention`` (interpreted; what a
    TPU takes at a head of 128) against the scores built whole."""
    lp = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (48, 64))
    c = lambda w: w  # noqa: E731
    plain, k, v = solar_open2._gqa_seq(x, lp, c, scfg, jnp.float32, flash=False)
    fused, k2, v2 = solar_open2._gqa_seq(x, lp, c, scfg, jnp.float32, flash=True)
    np.testing.assert_allclose(fused, plain, atol=2e-6, rtol=0)
    np.testing.assert_array_equal(k, k2)


# ── through the engine ───────────────────────────────────────────────────


@pytest.mark.parametrize("fused", [False, True], ids=["per-step", "fused"])
def test_engine_serves_the_references_tokens_staggered_and_reused(
    model, cfg, scfg, params, weights, fused
):
    """Six requests on two slots: staggered admissions, prompts that do
    not fill their bucket, one longer than a chunk, different ``n_new`` so
    that rows finish mid-scan (quantum 4), every slot reused. Each served
    token is the reference's best to ``TOL``."""
    eng = _engine(scfg, params, max_slots=2, slot_buckets=(1, 2), fused=fused)
    shapes = [(5, 7), (11, 3), (70, 10), (8, 5), (13, 6), (30, 9)]
    try:
        prompts = [_tokens(20 + i, p) for i, (p, _) in enumerate(shapes)]
        futures = [eng.enqueue(p[None], n) for p, (_, n) in zip(prompts, shapes)]
        served = [f.result(300)[0] for f in futures]
        for prompt, toks, (_, n) in zip(prompts, served, shapes):
            assert toks.shape == (n,)
            assert _gaps(model, weights, cfg, prompt, toks).max() <= TOL
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
        assert eng.ledger()["balanced"] and eng.ledger()["drained"]
    finally:
        eng.close()


def test_the_counters_and_the_telemetry_row(scfg, params):
    telemetry.reset()
    eng = _engine(scfg, params, model_id="count", max_slots=2, slot_buckets=(1, 2),
                  fused=False)
    try:
        eng.submit(_tokens(50, 11)[None], 5, timeout=300)  # 11 in a bucket of 16
        stats, ledger = eng.stats(), eng.ledger()
    finally:
        eng.close()
    per_slot = pagedkv.state_bytes(scfg, 1, jnp.float32)
    assert stats["state_bytes_per_slot"] == per_slot == ledger["state_bytes"] // 2
    assert stats["weights_bytes"] == sum(x.nbytes for x in jax.tree.leaves(params))
    assert _count("serving_prefill_tokens_total", kind="true") == 11
    assert _count("serving_prefill_tokens_total", kind="padded") == 16
    # four decode steps of one live row read and write its state; the
    # prefill wrote it once
    assert _count("serving_state_bytes_total", kind="read") == 4 * per_slot
    assert _count("serving_state_bytes_total", kind="written") == 5 * per_slot
    # the experts, as the programs counted them: a prefill of 16 positions
    # and four steps of width 1, four layers, two choices a position
    routed = _count("serving_expert_rows_total", kind="routed")
    held = _count("serving_expert_rows_total", kind="held")
    assert routed == 4 * 2 * (16 + 4) and 0 < held < routed / 2
    expert = solar_open2.expert_bytes(params)
    by_path = {
        path: _count("serving_expert_bytes_total", kind="read", path=path)
        for path in ("prefill", "step")
    }
    assert by_path["prefill"] > 0 and by_path["prefill"] % expert == 0
    assert by_path["step"] % expert == 0 and by_path["step"] <= 4 * 4 * 2 * expert
    assert _count("serving_dispatches_total") == 4


def test_a_scan_answers_the_sum_of_its_steps_counts(scfg, params):
    telemetry.reset()
    eng = _engine(scfg, params, model_id="scan", max_slots=1, slot_buckets=(1,),
                  fused=True, quantum=4)
    try:
        eng.submit(_tokens(51, 9)[None], 9, timeout=300)
    finally:
        eng.close()
    # one token of the prefill, then two scans of four steps at width 1
    assert _count("serving_fused_scans_total") == 2
    assert _count("serving_expert_rows_total", kind="routed") == 4 * 2 * (16 + 8)
    assert _count("serving_expert_bytes_total", path="fused") % solar_open2.expert_bytes(params) == 0


def test_the_budget_charges_the_matrix_state_before_it_grants_blocks(scfg):
    per_block = pagedkv.block_bytes(scfg, BLOCK, jnp.float32)
    state = pagedkv.state_bytes(scfg, 4, jnp.float32)
    budget = DeviceBudget(total_bytes=state + 10 * per_block)
    manager = ServingManager(
        EngineConfig(max_slots=4, block_size=BLOCK, cache_dtype=jnp.float32),
        budget=budget,
    )
    assert manager._config_for("m", scfg).num_blocks == 10
    assert budget.snapshot()["allocated_bytes"]["m"] == state + 10 * per_block
    eng = GenerationEngine(
        scfg, solar_open2.init(jax.random.PRNGKey(0), scfg),
        EngineConfig(max_slots=4, block_size=BLOCK, cache_dtype=jnp.float32,
                     kv_budget_bytes=state + 7 * per_block),
    )
    assert eng.stats()["kv_blocks_total"] == 6  # 7 less the trash block
    eng.close()


def test_bundle_round_trip_and_the_manager_builds_the_same_engine(
    model, cfg, scfg, params, weights
):
    from pygrid_tpu.datacentric.model_storage import HostedModel

    blob = serde.serialize(solar_open2.bundle(scfg, params))
    got_cfg, got = decode.from_bundle(serde.deserialize(blob))
    assert got_cfg == scfg and isinstance(got_cfg, solar_open2.SolarConfig)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="layer 1"):
        bad = solar_open2.bundle(scfg, params)
        bad["params"]["layers"][1].pop("A_log")
        decode.from_bundle(bad)
    hosted = HostedModel(
        model_id="s", model=serde.deserialize(blob), allow_remote_inference=True
    )
    manager = ServingManager(
        EngineConfig(max_slots=2, slot_buckets=(1, 2), min_prompt_bucket=8,
                     block_size=BLOCK)
    )
    try:
        eng = manager.engine_for("s", hosted)
        assert type(eng) is GenerationEngine
        prompt = _tokens(70, 10)
        toks = eng.submit(prompt[None], 6, timeout=300)[0]
        assert _gaps(model, weights, cfg, prompt, toks).max() <= TOL
        assert manager.ledger()["balanced"]
        assert manager.stats()[0]["state_bytes"] > 0
    finally:
        manager.close()
