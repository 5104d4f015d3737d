"""The paged decode attention kernel (serving/paged_attention.py) against
the gather path it replaces on a TPU (models/decode.py paged_decode_step).

The kernel runs here in interpret mode. What has to hold: the same
numbers as the gather for every width, length and cache dtype, down to
float32 rounding; a frozen row's cache state untouched; a free slot
(zeroed table row) unable to change a live slot's output; pages shared by
two slots read by both; tier-1's tiny configurations NOT eligible (their
bit-identity tests keep running the gather); and the fused program
holding the kernel when the pool is eligible.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pygrid_tpu.models import decode
from pygrid_tpu.models import transformer as T
from pygrid_tpu.serving import paged_attention
from pygrid_tpu.serving.programs import ProgramSet

H, DH, BLOCK, PAGES, BLOCKS, LAYERS = 16, 128, 8, 4, 24, 3
ROWS = PAGES * BLOCK


def _gather(q, k_pool, v_pool, layer, table, lengths):
    """The lines of ``paged_decode_step``'s XLA attention."""
    w = q.shape[0]
    k_rows = k_pool[layer][table].reshape(w, ROWS, H, DH)
    v_rows = v_pool[layer][table].reshape(w, ROWS, H, DH)
    mask = jnp.arange(ROWS)[None, :] < lengths[:, None]
    s = jnp.einsum(
        "whd,wlhd->whl", q, k_rows, preferred_element_type=jnp.float32
    ) * DH**-0.5
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "whl,wlhd->whd", p.astype(v_rows.dtype), v_rows,
        preferred_element_type=jnp.float32,
    )


#: t = 0, block - 1, block, rows - 1 as valid lengths t + 1
EDGES = (1, BLOCK, BLOCK + 1, ROWS)
CASES = {
    **{f"w1-len{n}": [n] for n in EDGES},
    "w4-edges": list(EDGES),
    "w16-mixed": [*EDGES, 2, 7, 9, 15, 16, 17, 23, 24, 25, 30, 31, 3],
}


@pytest.mark.parametrize("q_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cache_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_gather(case, cache_dtype, q_dtype):
    lengths = jnp.asarray(CASES[case], jnp.int32)
    w = lengths.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(w), 4)
    shape = (LAYERS, BLOCKS, BLOCK, H, DH)
    k_pool = jax.random.normal(keys[0], shape).astype(cache_dtype)
    v_pool = jax.random.normal(keys[1], shape).astype(cache_dtype)
    q = (3.0 * jax.random.normal(keys[2], (w, H, DH))).astype(q_dtype)
    table = jax.random.randint(keys[3], (w, PAGES), 1, BLOCKS)
    got = paged_attention.paged_decode_attention(
        q, k_pool, v_pool, jnp.int32(1), table, lengths, interpret=True
    )
    want = _gather(q, k_pool, v_pool, 1, table, lengths)
    assert got.shape == (w, H, DH) and got.dtype == jnp.float32
    # both accumulate in float32; only the order of the sums differs
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# ── through paged_decode_step ────────────────────────────────────────────

CFG = T.TransformerConfig(
    vocab=40, d_model=8 * DH, n_heads=8, n_layers=2, d_ff=32, max_len=ROWS
)


@pytest.fixture(scope="module")
def params():
    return T.init(jax.random.PRNGKey(7), CFG)


def _filled_cache(seed, pos):
    """A float32 pool of random rows (block 0, the trash block, too) with
    the slots at ``pos``."""
    cache = decode.init_paged_cache(CFG, len(pos), BLOCKS, BLOCK)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return decode.PagedKVCache(
        k=jax.random.normal(kk, cache.k.shape),
        v=jax.random.normal(kv, cache.v.shape),
        pos=jnp.asarray(pos, jnp.int32),
    )


def _step(monkeypatch, kernel, params, cache, table, token, active=None):
    monkeypatch.setattr(paged_attention, "eligible", lambda *a: kernel)
    return decode.paged_decode_step(
        params, cache, jnp.asarray(table, jnp.int32),
        jnp.asarray(token, jnp.int32), CFG, active=active,
    )


def _private_table(w):
    return 1 + np.arange(w * PAGES, dtype=np.int32).reshape(w, PAGES)


def _check_frozen_row(monkeypatch, params):
    """``active`` false: the row's pool pages and ``pos`` are as if the
    step never ran, on the kernel path as on the gather."""
    table = _private_table(4)
    cache = _filled_cache(1, [3, 8, 20, 31])
    active = jnp.asarray([True, False, True, True])
    token = [5, 6, 7, 8]
    logits, new = _step(monkeypatch, True, params, cache, table, token, active)
    want, ref = _step(monkeypatch, False, params, cache, table, token, active)
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(new.pos, [4, 8, 21, 32])
    np.testing.assert_array_equal(new.pos, ref.pos)
    frozen = table[1]
    np.testing.assert_array_equal(new.k[:, frozen], cache.k[:, frozen])
    np.testing.assert_array_equal(new.v[:, frozen], cache.v[:, frozen])
    # an active row did append: its page changed at its old position
    assert not np.array_equal(new.k[:, table[0, 0], 3], cache.k[:, table[0, 0], 3])


def _check_zeroed_row(monkeypatch, params):
    """A free slot inside the width reads trash block 0 and nothing else:
    live rows answer as the gather does, whatever the free slot's stale
    position and whatever lies in the trash block."""
    table = _private_table(4)
    table[2] = 0
    token = [5, 6, 7, 8]
    cache = _filled_cache(2, [3, 8, 30, 17])
    logits, new = _step(monkeypatch, True, params, cache, table, token)
    want, _ = _step(monkeypatch, False, params, cache, table, token)
    live = np.array([0, 1, 3])
    np.testing.assert_allclose(logits[live], want[live], rtol=1e-4, atol=1e-4)
    assert np.isfinite(np.asarray(logits)).all()
    other = decode.PagedKVCache(
        k=cache.k.at[:, 0].set(7.0), v=cache.v.at[:, 0].set(-7.0),
        pos=cache.pos.at[2].set(0),
    )
    again, _ = _step(monkeypatch, True, params, other, table, token)
    np.testing.assert_array_equal(again[live], logits[live])
    # its garbage write went to the trash block, not to a live page
    owned = table[live].ravel()
    changed = np.any(
        np.asarray(new.k[:, owned] != cache.k[:, owned]), axis=(0, 3, 4)
    )
    assert changed.sum() == len(live)  # one appended row per live slot


def _check_shared_prefix(monkeypatch, params):
    """Two slots whose tables name the same prefix pages both read them;
    each appends to a page of its own."""
    table = _private_table(3)
    table[1, :2] = table[0, :2]  # a 16-token shared prefix
    cache = _filled_cache(3, [16, 19, 5])
    token = [9, 10, 11]
    logits, new = _step(monkeypatch, True, params, cache, table, token)
    want, ref = _step(monkeypatch, False, params, cache, table, token)
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)
    shared = table[0, :2]
    np.testing.assert_array_equal(new.k[:, shared], cache.k[:, shared])
    np.testing.assert_allclose(new.k, ref.k, rtol=1e-4, atol=1e-4)


STEP_CHECKS = {
    "frozen-row": _check_frozen_row,
    "zeroed-row": _check_zeroed_row,
    "shared-prefix": _check_shared_prefix,
}


@pytest.mark.parametrize("check", sorted(STEP_CHECKS))
def test_step_on_the_kernel_path_keeps_the_cache_discipline(
    check, monkeypatch, params
):
    STEP_CHECKS[check](monkeypatch, params)


# ── when the kernel is taken ─────────────────────────────────────────────


def _pool(n_heads, head_dim, dtype, block=64, n_layers=2):
    return jax.ShapeDtypeStruct((n_layers, 8, block, n_heads, head_dim), dtype)


@pytest.mark.parametrize(
    "pool, max_pages, taken",
    [
        # the benchmark's Cerebras-GPT-1.3B pool, as the engine builds it
        (_pool(16, 128, jnp.bfloat16), 32, True),
        (_pool(8, 128, jnp.float32), 32, True),
        # tier-1's engines: heads of 8 to 32, float32, blocks of 4 to 16
        (_pool(2, 8, jnp.float32, block=8), 4, False),
        (_pool(4, 16, jnp.float32, block=16), 16, False),
        (_pool(4, 32, jnp.bfloat16, block=4), 8, False),
        # heads that do not fill the cache dtype's sublane tile
        (_pool(8, 128, jnp.bfloat16), 32, False),
        (_pool(12, 128, jnp.float32), 32, False),
        # scores of one table past the VMEM the kernel plans for
        (_pool(32, 128, jnp.bfloat16), 64, False),
    ],
)
def test_eligibility_is_decided_from_backend_and_shapes(
    pool, max_pages, taken, monkeypatch
):
    assert jax.default_backend() == "cpu"
    assert not paged_attention.eligible(pool, max_pages)  # never off a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_attention.eligible(pool, max_pages) is taken


def test_fused_program_holds_the_kernel_only_when_eligible(
    monkeypatch, params
):
    w, steps = 4, 2

    def args():  # the program donates the cache: a fresh one a call
        cache = _filled_cache(4, [3, 8, 20, 5])
        return (
            params, cache.k, cache.v, cache.pos,
            jnp.asarray([1, 2, 3, 4], jnp.int32),  # the slots' last tokens
            jnp.asarray(_private_table(w)),
            jnp.asarray([2, 1, 2, 0], jnp.int32), jnp.zeros(w, jnp.float32),
            jnp.zeros((steps, w, 2), jnp.uint32),
        )

    def fused():  # a fresh trace each time: the rule is read when traced
        return ProgramSet(CFG).paged_decode_fused(w, steps)

    assert "pallas_call" not in str(jax.make_jaxpr(fused())(*args()))
    monkeypatch.setattr(paged_attention, "eligible", lambda *a: True)
    text = str(jax.make_jaxpr(fused())(*args()))
    assert "pallas_call" in text and "paged_decode_attention" in text
    # and it runs (interpreted here): the same tokens as the gather's
    emitted, k, _v, pos, last = fused()(*args())
    monkeypatch.setattr(paged_attention, "eligible", lambda *a: False)
    want, ref_k, _rv, ref_pos, ref_last = fused()(*args())
    np.testing.assert_array_equal(last, ref_last)
    np.testing.assert_array_equal(emitted, want)
    np.testing.assert_array_equal(pos, ref_pos)
    np.testing.assert_allclose(k, ref_k, rtol=1e-4, atol=1e-4)
