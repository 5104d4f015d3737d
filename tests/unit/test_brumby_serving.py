"""The power-retention family (``pygrid_tpu/models/brumby.py``,
``models/retention.py``) through the serving engine, against the plain
reference that the benchmark keeps (``perfbench/models/brumby.py``: the
quadratic sum over earlier positions, float32, ``highest``, no feature map,
no state, nothing of the program imported).

Size: two layers, 4 heads of 16 on 2 K/V heads, float32 weights on the CPU;
the kernels at heads of 128, interpreted. Everything is compared on LOGITS.

``TOL``: program and reference run the same float32 mathematics in another
order (the program sums over earlier positions through a feature map of
144 numbers a head and a carried state, norms by ``rsqrt``); logits of size
~4 differ by at most 8e-6 over four seeds (my CPU runs, PR 44). 5e-5 is
six times that, and far under what a fault does: the carried state zeroed
moves a logit by more than 1e-2.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pygrid_tpu import telemetry
from pygrid_tpu.models import brumby, decode, retention
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
from lib import spec  # noqa: E402

TOL = 5e-5
CONFIG = ROOT / "perfbench/configs/brumby-14b-base.json"


@pytest.fixture(scope="module")
def model():
    return spec.load_model("brumby")


@pytest.fixture(scope="module")
def published():
    return json.loads(CONFIG.read_text())


@pytest.fixture(scope="module")
def cfg(model, published):
    return {**published, **model.tiny(published), "deployment": dict(
        published["deployment"], served_context=512
    )}


@pytest.fixture(scope="module")
def bcfg(model, cfg):
    return model.brumby_config(cfg)


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


def _engine(bcfg, params, model_id="brumby", **over):
    kw = dict(max_slots=3, slot_buckets=(1, 2), min_prompt_bucket=8, quantum=4)
    kw.update(over)
    return GenerationEngine(bcfg, params, EngineConfig(**kw), model_id=model_id)


def _count(name, **labels):
    """The bus counter ``name``, summed over series carrying ``labels``."""
    return sum(
        v for (n, lab), v in telemetry.counters().items()
        if n == name and labels.items() <= dict(lab).items()
    )


def _gaps(model, weights, cfg, prompt, served):
    """The reference's best logit minus its logit of each served token."""
    seq = np.concatenate([prompt, served[:-1]])
    rows = _ref_logits(model, weights, cfg, seq)[len(prompt) - 1 :]
    return rows.max(-1) - rows[np.arange(len(served)), served]


# ── the feature map and the recurrence's forms ───────────────────────────


@pytest.mark.parametrize("dk", [4, 16, 128])
def test_phi_turns_a_square_of_a_dot_into_a_dot(dk):
    """To float32's rounding of the terms: they are of either sign and of
    size |x|^2 |y|^2 together, so a small square is a difference of large
    numbers (what the state's type has to carry)."""
    x, y = jax.random.normal(jax.random.PRNGKey(dk), (2, 7, dk))
    phi = jax.jit(retention.phi)
    want = jnp.sum(x * y, -1) ** 2
    got = jnp.sum(phi(x) * phi(y), (-2, -1))
    assert phi(x).shape == (7, dk // 2 + 1, dk)
    size = jnp.sum(x * x, -1) * jnp.sum(y * y, -1)
    assert float(jnp.max(jnp.abs(got - want) / size)) < 1e-6


def _quadratic(q, k, v, log_g, scale):
    """The first form, whole: ``a_tj`` for every pair."""
    P = q.shape[0]
    run = jnp.cumsum(log_g, 0).T  # [G, P]
    s = jnp.einsum("tgrk,jgk->grtj", q, k) * scale
    seen = jnp.arange(P)[:, None] >= jnp.arange(P)[None, :]
    a = jnp.where(seen, s * s * jnp.exp(run[:, :, None] - run[:, None, :])[:, None], 0.0)
    den = a.sum(-1).transpose(2, 0, 1)
    return jnp.einsum("grtj,jgv->tgrv", a, v) / (den[..., None] + retention.EPS)


def _sequence(P, G, R, dk, dv, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(ks[0], (P, G, R, dk)), jax.random.normal(ks[1], (P, G, dk)),
        jax.random.normal(ks[2], (P, G, dv)),
        -jnp.exp(jax.random.uniform(ks[3], (P, G), minval=-6.0, maxval=-1.0)),
    )


@pytest.mark.parametrize(
    "dk, G, P, kernel", [(16, 2, 300, False), (128, 1, 256, True)]
)
def test_chunked_form_is_step_form_is_the_quadratic_sum(dk, G, P, kernel):
    """Across a chunk boundary, with a prompt prefilled in two pieces, and
    from a bucket's padding into decode steps; the kernels interpreted at
    heads of 128. Slots and layers the call does not name are untouched,
    and whatever a slot held before a whole prompt is ignored."""
    R, scale = 2, dk**-0.5
    q, k, v, log_g = _sequence(P, G, R, dk, dk)
    kw = dict(kernel=kernel, interpret=True)
    slot, i32 = jnp.int32(2), jnp.int32
    with jax.default_matmul_precision("highest"):
        want = _quadratic(q, k, v, log_g, scale)
        S, z = (jnp.full(s, 7.0) for s in retention.state_shapes(2, 3, G, dk, dk))
        y, S1, z1 = retention.chunked(
            S, z, 1, slot, q, k, v, log_g, i32(P), i32(0), scale, **kw
        )
        np.testing.assert_allclose(y, want, atol=TOL)
        assert float(jnp.abs(S1[0] - 7.0).max()) == 0.0
        assert float(jnp.abs(S1[1, :2] - 7.0).max()) == 0.0
        # two pieces: the second continues from the state the first left
        h = 128 if kernel else P // 2 + 5
        ya, Sa, za = retention.chunked(
            S, z, 1, slot, q[:h], k[:h], v[:h], log_g[:h], i32(h), i32(0), scale, **kw
        )
        yb, Sb, zb = retention.chunked(
            Sa, za, 1, slot, q[h:], k[h:], v[h:], log_g[h:], i32(P - h), i32(h),
            scale, **kw
        )
        np.testing.assert_allclose(jnp.concatenate([ya, yb]), want, atol=TOL)
        np.testing.assert_allclose(Sb[1, 2], S1[1, 2], rtol=1e-4, atol=1e-5)
        assert float(jnp.abs(Sb[0] - 7.0).max()) == 0.0
        # a padded prompt, then its next positions a step at a time
        L = P - 37
        yp, Sp, zp = retention.chunked(
            S, z, 1, slot, q, k.at[L:].set(0.0), v, log_g.at[L:].set(0.0),
            i32(L), i32(0), scale, **kw
        )
        np.testing.assert_allclose(yp[:L], want[:L], atol=TOL)
        for t in range(L, L + 2):
            row = lambda x: jnp.zeros((3,) + x.shape[1:]).at[2].set(x[t])  # noqa: E731
            ys, Sp, zp = retention.step(
                Sp, zp, 1, row(q), row(k), row(v), row(log_g), scale, **kw
            )
            np.testing.assert_allclose(ys[2], want[t], atol=TOL)


def test_chunks_run_says_what_the_kernel_skips():
    held, ran = retention.chunks_run(jnp.int32(300), 1024, 128, 128, kernel=True)
    assert (int(held), int(ran)) == (3, 3)
    held, ran = retention.chunks_run(jnp.int32(300), 1024, 16, 16)
    assert (int(held), int(ran)) == (3, 8)


# ── the family's facts and the published widths ──────────────────────────


def test_the_family_is_found_by_config_and_by_bundle(bcfg, params):
    assert decode.family_of(bcfg) is brumby
    cfg2, params2 = decode.from_bundle(brumby.bundle(bcfg, params))
    assert cfg2 == bcfg
    assert jax.tree.structure(params2) == jax.tree.structure(params)
    assert brumby.RECURRENT and brumby.BLOCK_LEN == 1 and brumby.kv_layers(bcfg) == 0
    with pytest.raises(ValueError, match="layer 0"):
        bad = brumby.bundle(bcfg, params)
        bad["params"]["layers"][0].pop("bg")
        decode.from_bundle(bad)


def test_the_published_widths_count_what_the_configuration_states(model, published):
    """``jax.eval_shape`` of the program's leaves: the published form's
    4,198,652,928 parameters and the 64 assumed gate biases beside them;
    the state a slot as the mathematics needs it and as the program lays it
    out; no K/V byte anywhere."""
    deploy = published["deployment"]
    shapes = jax.eval_shape(
        lambda: model.make_program_params(0, published, deploy["weights_dtype"])
    )
    leaves = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert deploy["parameters"] == 4_198_652_928 == model.param_count(published)
    assert leaves == deploy["parameters"] + deploy["gate_biases"]
    assert model.gate_biases(published) == deploy["gate_biases"] == 64
    assert deploy["weights_bytes"] == model.weights_bytes(published) == 8_397_305_856
    assert deploy["state_bytes_per_slot"] == model.state_bytes_per_slot(published)
    assert deploy["state_bytes_per_slot"] == 272_646_144
    pcfg = model.brumby_config(published)
    held = brumby.state_bytes_per_slot(pcfg, jnp.bfloat16)
    assert held == deploy["state_bytes_per_slot_held"] == 274_759_680
    assert deploy["env"]["PYGRID_KV_BUDGET"] == deploy["env"]["PYGRID_SERVING_SLOTS"] * held
    assert pagedkv.block_bytes(pcfg, 64, jnp.bfloat16) == 0
    assert deploy["kv_bytes_per_token"] == model.kv_bytes_per_token(published) == 0
    assert published["reduced"] == ["num_hidden_layers"]
    assert pcfg.max_len == 5120 and published["max_position_embeddings"] == 32768
    # a step of 20 live rows: 17.7 GB, three fifths of it the state
    moved = model.decode_step_bytes(published, 20)
    assert 17.7e9 < moved < 17.8e9
    assert 0.60 < 40 * model.state_bytes_per_slot(published) / moved < 0.62


def test_the_gates_forget_between_a_sentence_and_a_prompt(weights):
    spans = np.concatenate(
        [1.0 / (1.0 - jax.nn.sigmoid(lw["bg"])) for lw in weights["layers"]]
    )
    assert spans.min() >= 16.0 * 0.999 and spans.max() <= 8192.0 * 1.001


# ── an engine whose cache is state alone ─────────────────────────────────


def test_an_engine_with_no_kv_layer_builds_under_a_byte_budget_and_admits_by_slot(
    bcfg, params
):
    """No division by a block's zero bytes; a pool of the trash block
    alone, a table of one column; nothing is refused for pages, and a
    fourth request waits for a slot."""
    per_slot = brumby.state_bytes_per_slot(bcfg, jnp.float32)
    pages_before = _count("serving_kv_pages_total")
    eng = _engine(bcfg, params, kv_budget_bytes=3 * per_slot)
    try:
        row = eng.stats()
        assert row["kv_block_bytes"] == 0 and row["kv_blocks_total"] == 0
        assert row["state_bytes_per_slot"] == per_slot
        assert row["state_bytes"] == 3 * per_slot
        assert eng._table().shape == (3, 1) and eng._k.size == 0
        futures = [eng.enqueue(_tokens(i, 40 + 60 * i)[None], 6) for i in range(5)]
        for f in futures:
            assert f.result(300).shape == (1, 6)
        led = eng.ledger()
        assert led["balanced"] and led["drained"] and led["usable"] == 0
        assert eng.stats()["kv_demand_pages"] == 0
        assert _count("serving_kv_pages_total") == pages_before
        assert eng.chaos_hold_blocks() == 0
    finally:
        eng.close()


def test_the_node_budget_holds_the_slots_state_and_nothing_else(bcfg, params):
    from pygrid_tpu.datacentric.model_storage import HostedModel

    per_slot = brumby.state_bytes_per_slot(bcfg, pagedkv.default_cache_dtype())
    manager = ServingManager(
        EngineConfig(max_slots=2, slot_buckets=(1,)),
        budget=DeviceBudget(total_bytes=10 * per_slot),
    )
    try:
        hosted = HostedModel(
            model_id="b", model=brumby.bundle(bcfg, params),
            allow_remote_inference=True, generation_cache=(bcfg, params),
        )
        eng = manager.engine_for("b", hosted)
        assert manager.budget.snapshot()["allocated_bytes"] == {"b": 2 * per_slot}
        assert eng.stats()["kv_blocks_total"] == 0
        assert manager.repartition() == {}
    finally:
        manager.close()


@pytest.fixture(scope="module")
def served(bcfg, params):
    """Requests through one engine: prompts shorter and longer than a chunk,
    more of them than slots, so slots are taken again."""
    telemetry.reset()
    eng = _engine(bcfg, params)
    asks = [(150, 12), (33, 20), (270, 9), (7, 16), (129, 12)]
    prompts = [_tokens(10 + i, p) for i, (p, _) in enumerate(asks)]
    try:
        futures = [eng.enqueue(p[None], n) for p, (_, n) in zip(prompts, asks)]
        answers = [f.result(300)[0] for f in futures]
        row = eng.stats()
    finally:
        eng.close()
    return prompts, answers, row


def test_prefill_then_decode_is_the_references_full_forward(model, weights, cfg, served):
    """Logits, not tokens: every served token's gap to the reference's best,
    teacher forced on what was served; a slot taken again starts from a
    zero state (five requests on three slots)."""
    prompts, answers, row = served
    for prompt, tokens in zip(prompts, answers):
        assert _gaps(model, weights, cfg, prompt, tokens).max() <= TOL
    assert row["compiles_total"] <= 9


def test_the_bus_counts_state_and_chunks(bcfg, served):
    prompts, answers, row = served
    per_slot = brumby.state_bytes_per_slot(bcfg, jnp.float32)
    steps = sum(len(a) - 1 for a in answers)
    # a live row's state a step (a scan counts its whole quantum), and a
    # slot's whole state a prefill
    rowsteps = _count("serving_dispatch_rowsteps_total", kind="live")
    assert rowsteps >= steps
    assert _count("serving_state_bytes_total", kind="read") == rowsteps * per_slot
    assert _count("serving_state_bytes_total", kind="written") == (
        (rowsteps + len(prompts)) * per_slot
    )
    held = sum(-(-len(p) // retention.CHUNK) for p in prompts)
    assert _count("serving_retention_chunks_total", kind="true") == held
    # through XLA every chunk of a bucket runs: 16 of the 512-bucket's rows
    assert _count("serving_retention_chunks_total", kind="computed") >= held


def test_the_carry_is_seen(model, weights, cfg, bcfg, params):
    """The same comparison with the carried state zeroed after the prefill
    FAILS the limit: the reference's agreement rests on the carry."""
    prompt = _tokens(5, 200)
    cache = brumby.init_paged_cache(bcfg, 2, 1, 16)
    table = jnp.zeros((2, 1), jnp.int32)
    chunk = jnp.zeros((256,), jnp.int32).at[:200].set(prompt)
    prefill = jax.jit(brumby.paged_prefill_chunk, static_argnames=("cfg",))
    step = jax.jit(brumby.paged_decode_step, static_argnames=("cfg",))
    logits, cache, ran = prefill(
        params, cache, table, jnp.int32(1), chunk, jnp.int32(0), jnp.int32(200),
        cfg=bcfg,
    )
    assert [int(x) for x in ran] == [2, 2]

    def decode_from(cache, first, n):
        toks, rows, tok = [], [], first
        for _ in range(n):
            toks.append(int(tok))
            lg, cache = step(
                params, cache, table, jnp.array([0, tok], jnp.int32), cfg=bcfg
            )
            rows.append(np.asarray(lg[1]))
            tok = int(np.argmax(rows[-1]))
        return np.array(toks), np.array(rows)

    first = int(np.argmax(logits))
    toks, rows = decode_from(cache, first, 6)
    want = _ref_logits(model, weights, cfg, np.concatenate([prompt, toks]))
    np.testing.assert_allclose(rows, want[200:], atol=TOL)
    np.testing.assert_allclose(logits, want[199], atol=TOL)
    wiped = cache._replace(
        state=jnp.zeros_like(cache.state), norm=jnp.zeros_like(cache.norm)
    )
    _, lost = decode_from(wiped, first, 6)
    assert np.abs(lost - want[200:]).max() > 200 * TOL


def test_a_bfloat16_state_is_seen_too(model, weights, cfg, bcfg, params):
    """A state rounded to bfloat16 after every step walks away from the
    reference by far more than ``TOL``: the limit can tell."""
    prompt = _tokens(6, 150)
    cache = brumby.init_paged_cache(bcfg, 1, 1, 16)
    table = jnp.zeros((1, 1), jnp.int32)
    chunk = jnp.zeros((256,), jnp.int32).at[:150].set(prompt)
    _, cache, _ = jax.jit(brumby.paged_prefill_chunk, static_argnames=("cfg",))(
        params, cache, table, jnp.int32(0), chunk, jnp.int32(0), jnp.int32(150),
        cfg=bcfg,
    )
    step = jax.jit(brumby.paged_decode_step, static_argnames=("cfg",))
    toks = _tokens(7, 12)
    round16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    rows = []
    for tok in toks:
        lg, cache = step(params, cache, table, jnp.array([tok], jnp.int32), cfg=bcfg)
        cache = cache._replace(state=round16(cache.state), norm=round16(cache.norm))
        rows.append(np.asarray(lg[0]))
    want = _ref_logits(model, weights, cfg, np.concatenate([prompt, toks]))[150:]
    assert np.abs(np.array(rows) - want).max() > 20 * TOL
