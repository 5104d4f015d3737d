"""KV-cache decoding == teacher-forced full forward.

The decode path shares parameters and math with ``transformer.apply``;
greedy generation through the cache must reproduce argmax-of-full-
forward token by token, and the cache logits must match the full
forward's last-position logits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pygrid_tpu.models import transformer as T
from pygrid_tpu.models import decode

CFG = T.TransformerConfig(
    vocab=61, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=24
)


@pytest.fixture(scope="module")
def setup():
    params = T.init(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 6), 0, CFG.vocab)
    return params, prompt


def test_prefill_logits_match_full_forward(setup):
    params, prompt = setup
    cache = decode.init_cache(CFG, prompt.shape[0])
    logits, cache = decode.prefill(params, cache, prompt, CFG)
    full = T.apply(params, prompt, CFG)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, -1]), atol=2e-5
    )
    assert int(cache.pos) == prompt.shape[1]


def test_greedy_generate_matches_teacher_forced(setup):
    params, prompt = setup
    n_new = 8
    toks = decode.generate(params, prompt, n_new, CFG)
    assert toks.shape == (prompt.shape[0], n_new)

    # teacher-forced reference: re-run the FULL forward on the growing
    # sequence; each generated token must equal argmax of the previous
    # sequence's last-position logits
    seq = prompt
    for t in range(n_new):
        full = T.apply(params, seq, CFG)
        expect = jnp.argmax(full[:, -1], axis=-1)
        np.testing.assert_array_equal(
            np.asarray(toks[:, t]), np.asarray(expect)
        )
        seq = jnp.concatenate([seq, expect[:, None]], axis=1)


def test_generate_is_jittable(setup):
    params, prompt = setup
    fn = jax.jit(
        lambda p, x: decode.generate(p, x, 4, CFG)
    )
    t1 = fn(params, prompt)
    t2 = decode.generate(params, prompt, 4, CFG)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


def test_sampling_reproducible_and_validated(setup):
    params, prompt = setup
    key = jax.random.PRNGKey(7)
    a = decode.generate(params, prompt, 5, CFG, temperature=0.8, key=key)
    b = decode.generate(params, prompt, 5, CFG, temperature=0.8, key=key)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) >= 0).all() and (np.asarray(a) < CFG.vocab).all()
    with pytest.raises(ValueError, match="PRNG key"):
        decode.generate(params, prompt, 2, CFG, temperature=0.5)


def test_length_validation(setup):
    params, prompt = setup
    with pytest.raises(ValueError, match="max_len"):
        decode.generate(params, prompt, CFG.max_len, CFG)


def test_bf16_decode_close_to_f32(setup):
    """Mixed-precision decode drifts only by bf16 resolution; greedy
    tokens may legitimately differ at near-ties, so compare logits."""
    params, prompt = setup
    cache_f = decode.init_cache(CFG, prompt.shape[0])
    lf, _ = decode.prefill(params, cache_f, prompt, CFG)
    cache_b = decode.init_cache(CFG, prompt.shape[0])
    lb, _ = decode.prefill(
        params, cache_b, prompt, CFG, compute_dtype="bfloat16"
    )
    scale = float(jnp.max(jnp.abs(lf))) + 1e-9
    assert float(jnp.max(jnp.abs(lf - lb))) / scale < 0.05


def test_traced_temperature_zero_falls_back_to_greedy(setup):
    """A traced temperature that is 0 at runtime must serve the greedy
    tokens — not NaN logits through jax.random.categorical (ADVICE #4).
    One compiled program serves every temperature INCLUDING zero."""
    params, prompt = setup
    key = jax.random.PRNGKey(11)
    fn = jax.jit(
        lambda p, x, k, t: decode.generate(
            p, x, 5, CFG, temperature=t, key=k
        )
    )
    zero_t = fn(params, prompt, key, jnp.float32(0.0))
    greedy = decode.generate(params, prompt, 5, CFG)
    np.testing.assert_array_equal(np.asarray(zero_t), np.asarray(greedy))
    # and the same program still samples at a positive temperature
    hot = fn(params, prompt, key, jnp.float32(0.8))
    eager = decode.generate(params, prompt, 5, CFG, temperature=0.8, key=key)
    np.testing.assert_array_equal(np.asarray(hot), np.asarray(eager))


def test_run_generation_validates_seed_and_temperature(setup):
    """The serving endpoint bounces hostile seed/temperature values as
    typed {success: False} frames: seeds past int64 (ADVICE #1, formerly
    an uncaught OverflowError) and non-finite temperatures (ADVICE #2,
    formerly silently-uniform tokens)."""
    import base64
    from types import SimpleNamespace

    from pygrid_tpu.node import NodeContext
    from pygrid_tpu.node.events import Connection, run_generation
    from pygrid_tpu.serde import serialize

    params, _ = setup
    ctx = NodeContext("decode-validation")
    conn = Connection(ctx, socket=object())
    conn.session = SimpleNamespace(worker=None)
    hosted = ctx.models.save(
        ctx.local_worker.id,
        serialize(decode.bundle(CFG, params)),
        "gen-val",
        allow_download=False,
        allow_remote_inference=True,
        mpc=False,
    )
    assert hosted.get("success"), hosted
    prompt = base64.b64encode(
        serialize(np.array([[1, 2]], np.int32))
    ).decode()

    def gen(**fields):
        return run_generation(
            ctx,
            {"model_id": "gen-val", "data": prompt, "n_new": 2, **fields},
            conn,
        )

    for bad in (
        dict(temperature=float("inf")),
        dict(temperature=float("-inf")),
        # JSON true/numeric strings float()-coerce (true → 1.0 silently
        # samples) — the contract is a JSON number, all else bounces
        dict(temperature=True),
        dict(temperature="0.5"),
        dict(temperature=0.5, seed=2**63),
        dict(temperature=0.5, seed=10**30),
        dict(temperature=0.5, seed=-(2**64)),
        dict(temperature=0.5, seed=-1),
        dict(temperature=0.5, seed=True),
        dict(temperature=0.5, seed="5"),
        dict(temperature=0.5, seed=1.5),
        dict(n_new=True),
        dict(n_new="8"),
        dict(n_new=2.5),
    ):
        out = gen(**bad)
        assert out.get("success") is False and "error" in out, (bad, out)
    # in-range values still serve
    ok = gen(temperature=0.5, seed=2**62)
    assert ok.get("success") is True, ok
