"""Pallas flash attention (parallel/pallas_attention.py) vs the XLA
reference, kernel run in interpret mode on CPU (the house pattern from
test_pallas_kernels.py). No reference analog — the reference has no
attention anywhere (SURVEY §5.7)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pygrid_tpu.parallel.pallas_attention import flash_attention
from pygrid_tpu.parallel.ring_attention import attention


def _qkv(B, Lq, Lk, H, D, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (B, Lq, H, D), dtype),
        jax.random.normal(ks[1], (B, Lk, H, D), dtype),
        jax.random.normal(ks[2], (B, Lk, H, D), dtype),
    )


@pytest.mark.parametrize(
    "B,Lq,Lk,H,D,causal",
    [
        (2, 128, 128, 2, 64, False),
        (1, 256, 256, 4, 64, True),
        (2, 200, 200, 2, 32, True),    # ragged lengths, tiny head dim
        (1, 100, 300, 2, 64, False),   # cross-attention, ragged
        (1, 384, 384, 1, 128, True),   # full-width head dim
    ],
)
def test_matches_xla_reference(B, Lq, Lk, H, D, causal):
    q, k, v = _qkv(B, Lq, Lk, H, D)
    ref = attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)


def test_block_sizes_do_not_change_the_answer():
    q, k, v = _qkv(1, 300, 300, 2, 64)
    base = flash_attention(q, k, v, causal=True, interpret=True)
    for bq, bk in [(128, 128), (256, 128), (128, 256)]:
        other = flash_attention(
            q, k, v, causal=True, interpret=True, block_q=bq, block_k=bk
        )
        np.testing.assert_allclose(
            np.asarray(other), np.asarray(base), atol=2e-5
        )


def test_bf16_inputs():
    q, k, v = _qkv(1, 256, 256, 2, 64, dtype=jnp.bfloat16)
    ref = attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    got = flash_attention(q, k, v, causal=True, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(ref), atol=3e-2
    )


def test_scale_override():
    q, k, v = _qkv(1, 128, 128, 1, 64)
    ref = attention(q, k, v, scale=0.5)
    got = flash_attention(q, k, v, scale=0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)


def test_causal_requires_square():
    q, k, v = _qkv(1, 128, 256, 1, 64)
    with pytest.raises(ValueError, match="Lq == Lk"):
        flash_attention(q, k, v, causal=True, interpret=True)


@pytest.mark.parametrize(
    "Lq,Lk,causal",
    [(128, 128, False), (200, 200, True), (100, 300, False)],
)
def test_gradients_match_xla_reference(Lq, Lk, causal):
    """The custom VJP (blocked flash backward off the saved
    log-sum-exp) agrees with differentiating the dense reference."""
    q, k, v = _qkv(1, Lq, Lk, 2, 64, seed=3)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.tanh(fn(q, k, v)))

    g_ref = jax.grad(
        loss(lambda q, k, v: attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_flash = jax.grad(
        loss(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, interpret=True
            )
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)


@pytest.mark.parametrize(
    "causal,bwd_bq,bwd_bk",
    [
        (False, 128, 128),
        (True, 128, 128),
        (True, 128, 256),  # unequal blocks stress the live-bound asymmetry
        (True, 256, 128),
    ],
)
def test_gradients_multiblock(causal, bwd_bq, bwd_bk):
    """Cross-block gradient accumulation: shrink the backward blocks so
    the dkv kernel sweeps several q blocks into its VMEM accumulators and
    the dq kernel sweeps several k blocks — including dead causal block
    pairs, whose upper-triangle skip must leave the accumulators intact
    (a sign error or an off-by-one in the `live` bound would only ever
    surface at real sequence lengths otherwise)."""
    q, k, v = _qkv(1, 384, 384, 2, 64, seed=7)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.tanh(fn(q, k, v)))

    g_ref = jax.grad(
        loss(lambda q, k, v: attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_flash = jax.grad(
        loss(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, interpret=True,
                block_q=128, block_k=128,
                bwd_block_q=bwd_bq, bwd_block_k=bwd_bk,  # ≥2 blocks/axis
            )
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)


def test_transformer_trains_with_flash_attention():
    """A full training step (loss + grads + update) through the flash
    kernel — long-context training is the point of the O(L) backward."""
    from functools import partial

    from pygrid_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab=64, d_model=32, n_heads=2, d_ff=64, n_layers=1, max_len=64
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    step_ref = transformer.make_training_step(cfg)
    step_flash = transformer.make_training_step(
        cfg, attn_fn=partial(flash_attention, interpret=True)
    )
    X = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, 64)
    out_ref = step_ref(X, y, jnp.float32(0.1), *params)
    out_flash = step_flash(X, y, jnp.float32(0.1), *params)
    np.testing.assert_allclose(
        float(out_flash[0]), float(out_ref[0]), atol=1e-4
    )  # same loss
    for a, b in zip(out_ref[2:], out_flash[2:]):  # same updated params
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=2e-4
        )


def test_transformer_remat_flash_training_step():
    """remat + flash in both directions — the composition the
    long-context training bench runs (jax.checkpoint re-traces the
    block, so the Pallas VJP must survive a second trace)."""
    from functools import partial

    from pygrid_tpu.models import transformer
    from pygrid_tpu.parallel import make_scanned_rounds

    cfg = transformer.TransformerConfig(
        vocab=64, d_model=32, n_heads=2, d_ff=64, n_layers=2, max_len=64
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    flash = partial(flash_attention, interpret=True)
    step_plain = transformer.make_training_step(cfg, attn_fn=flash)
    step_remat = transformer.make_training_step(
        cfg, attn_fn=flash, remat=True
    )
    X = jax.random.randint(jax.random.PRNGKey(1), (2, 2, 64), 0, 64)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 2, 64), 0, 64)
    lr = jnp.float32(0.1)
    out_p = make_scanned_rounds(step_plain, n_rounds=2)(params, X, y, lr)
    out_r = make_scanned_rounds(step_remat, n_rounds=2)(params, X, y, lr)
    # remat changes memory, never math
    np.testing.assert_allclose(
        np.asarray(out_p[1]), np.asarray(out_r[1]), rtol=1e-5
    )
    for a, b in zip(out_p[0], out_r[0]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


def test_plugs_into_transformer_attn_fn():
    """The kernel satisfies the transformer's injectable attn_fn contract
    (same [B, L, H, D] signature as `attention`)."""
    from functools import partial

    from pygrid_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab=64, d_model=32, n_heads=2, d_ff=64, n_layers=1, max_len=64
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    X = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)
    ref_logits = transformer.apply(params, X, cfg)
    flash_logits = transformer.apply(
        params, X, cfg,
        attn_fn=partial(flash_attention, interpret=True),
    )
    np.testing.assert_allclose(
        np.asarray(flash_logits), np.asarray(ref_logits), atol=1e-4
    )


# ── what a block holds: heads that share a tile, sequences that share a step ──


def _loss_and_grads(fn, q, k, v):
    def loss(q, k, v):
        return jnp.sum(jnp.tanh(fn(q, k, v).astype(jnp.float32)))

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


#: (B, L, H, D), the flash kernel's block arguments, dtype, tolerance
PACKED_CASES = {
    # the benchmark's two training cells, one client's attention
    "fed-device-short": ((2, 128, 12, 64), {}, jnp.float32, 2e-5),
    "fed-silo-docs": (
        (1, 2048, 12, 64),
        dict(block_q=1024, block_k=1024, bwd_block_q=1024, bwd_block_k=1024),
        jnp.float32, 5e-5,
    ),
    # two sequences a step and an odd head count: the last tile is half pad
    "odd-heads": ((2, 128, 3, 64), {}, jnp.float32, 2e-5),
    # four heads a tile, and a ragged length beside them
    "four-a-tile": ((2, 200, 8, 32), {}, jnp.float32, 2e-5),
    # a head that fills its tile: nothing shared, nothing padded
    "full-tile": ((2, 128, 2, 128), {}, jnp.float32, 2e-5),
    # a head that neither divides a tile nor fills one: padded, one a tile
    "padded-head": ((1, 128, 2, 48), {}, jnp.float32, 2e-5),
    # the cells' own operand type
    "bf16": ((2, 128, 12, 64), {}, jnp.bfloat16, 4e-2),
    # several tiles a sequence with two heads a tile
    "multi-tile": (
        (1, 384, 4, 64),
        dict(block_q=128, block_k=128, bwd_block_q=128, bwd_block_k=256),
        jnp.float32, 2e-5,
    ),
}


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_forward_and_gradients_by_what_a_block_holds(case):
    (B, L, H, D), blocks, dtype, atol = PACKED_CASES[case]
    q, k, v = _qkv(B, L, L, H, D, dtype=dtype, seed=11)
    as_f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want, want_grads = _loss_and_grads(
        lambda q, k, v: attention(q, k, v, causal=True), *as_f32
    )
    got, got_grads = _loss_and_grads(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True, **blocks
        ),
        q, k, v,
    )
    np.testing.assert_allclose(float(got), float(want), rtol=max(atol, 1e-4))
    for a, b in zip(want_grads, got_grads):
        assert b.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(b, dtype=np.float32), np.asarray(a), atol=atol
        )


def test_under_the_trainers_client_vmap():
    """``make_fused_rounds`` maps the loss over a leading client axis:
    the batching rule puts the clients' grid axis in front of the
    kernels' own, whatever a step of theirs holds."""
    clients, shape = 3, (2, 128, 4, 64)
    q, k, v = (
        jnp.stack(parts)
        for parts in zip(*(_qkv(*shape[:2], shape[1], *shape[2:], seed=s)
                           for s in range(clients)))
    )

    def per_client(fn):
        return jax.vmap(
            lambda q, k, v: _loss_and_grads(fn, q, k, v)
        )(q, k, v)

    want, want_grads = per_client(
        lambda q, k, v: attention(q, k, v, causal=True)
    )
    got, got_grads = per_client(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True)
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    for a, b in zip(want_grads, got_grads):
        assert b.shape == (clients, *shape)
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, in order
    (a kernel's own body left out)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_one_tile_sequences_are_read_as_the_model_lays_them_out():
    """Untimed, on the program as traced: at the short cell's shape the
    three kernels take ``[B, L, H·D]`` operands (a reshape of what the
    model hands over: no pad, no transpose) and one grid step holds every
    head of at least one sequence."""
    B, L, H, D = 4, 128, 12, 64
    q, k, v = _qkv(B, L, L, H, D, dtype=jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    eqns = list(_equations(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert "pad" not in names and "transpose" not in [
        e.primitive.name for e in eqns
        if any(getattr(x.aval, "dtype", None) == jnp.bfloat16 for x in e.invars)
    ]
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert sorted(
        e.params["name"] for e in calls
    ) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    for call in calls:
        grid = call.params["grid_mapping"].grid
        assert int(np.prod(grid)) <= B, grid
        streams = [
            x.aval.shape for x in call.invars if x.aval.dtype == jnp.bfloat16
        ]
        assert streams and all(s == (B, L, H * D) for s in streams), streams
