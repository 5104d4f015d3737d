"""The paged decode attention kernel (serving/paged_attention.py) against
the gather path it replaces on a TPU (models/decode.py paged_decode_step).

The kernel runs here in interpret mode. What has to hold: the same
numbers as the gather for every width, length and cache dtype, down to
float32 rounding; a frozen row's cache state untouched; a free slot
(zeroed table row) unable to change a live slot's output; pages shared by
two slots read by both; tier-1's tiny configurations NOT eligible (their
bit-identity tests keep running the gather); and the fused program
holding the kernel when the pool is eligible.

The second half holds the WIDE kernel (a position's K/V heads side by
side on the lanes, several pages a DMA wave and a matmul) to the lines
it replaces in ``models/lfm2_moe.py`` (``_pages`` + ``_attend_wide``) in
the same way, and to one thing more: a length whose pages do not fill
its last wave reads no page past its last.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pygrid_tpu.models import decode, lfm2_moe
from pygrid_tpu.models import transformer as T
from pygrid_tpu.models.sdar_moe import _pages
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


# ── the wide kernel: heads side by side on the lanes, pages in waves ─────

W_BLOCK, W_PAGES, W_BLOCKS, WAVE, DH_WIDE = 16, 8, 41, 3, 64
W_ROWS = W_PAGES * W_BLOCK
#: (query heads, K/V heads): rows of 512 lanes as the served family's, and
#: rows of one lane tile
WIDE_SHAPES = {"r32-c512": (32, 8), "r8-c128": (8, 2)}
#: t = 0, block - 1, block, a wave's last row, the next wave's first, the
#: table's last (whose wave holds two pages of the three), as lengths t + 1
W_EDGES = (
    1, W_BLOCK, W_BLOCK + 1, WAVE * W_BLOCK, WAVE * W_BLOCK + 1, W_ROWS - 1,
    W_ROWS,
)
W_CASES = {
    "w1": [[n] for n in W_EDGES],
    "w4": [list(W_EDGES[:4]), list(W_EDGES[3:])],
    "w16": [[*W_EDGES, 2, 15, 31, 33, 47, 50, 95, 97, 100]],
}


def _wide_pools(seed, heads, cache_dtype, w, nan_page=False):
    """Random pools, queries and tables (ids 1 .. blocks - 2; the last
    block is all NaN where ``nan_page``, and no table names it yet)."""
    H, G = heads
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (LAYERS, W_BLOCKS, W_BLOCK, G * DH_WIDE)
    k_pool = jax.random.normal(keys[0], shape).astype(cache_dtype)
    v_pool = jax.random.normal(keys[1], shape).astype(cache_dtype)
    if nan_page:
        k_pool = k_pool.at[:, -1].set(jnp.nan)
        v_pool = v_pool.at[:, -1].set(jnp.nan)
    q = 3.0 * jax.random.normal(keys[2], (w, H, DH_WIDE))
    table = jax.random.randint(keys[3], (w, W_PAGES), 1, W_BLOCKS - 1)
    return q, k_pool, v_pool, np.array(table)


def _wide_kernel(q, k_pool, v_pool, layer, table, lengths, G, wave=WAVE):
    """As ``lfm2_moe.paged_decode_step`` calls it: the heads laid wide
    outside the kernel and picked out of its result."""
    a_wide = paged_attention.paged_decode_attention_wide(
        lfm2_moe._widen(q, G), k_pool, v_pool, jnp.int32(layer),
        jnp.asarray(table), jnp.asarray(lengths, jnp.int32),
        scale=DH_WIDE**-0.5, wave=wave, interpret=True,
    )
    assert a_wide.dtype == jnp.float32
    return lfm2_moe._narrow(a_wide, G)


def _wide_gather(q, k_pool, v_pool, layer, table, lengths, G):
    """The lines of ``lfm2_moe.paged_decode_step``'s XLA attention."""
    w, table = q.shape[0], jnp.asarray(table)
    rows = table.shape[1] * k_pool.shape[2]
    k_rows = _pages(k_pool, layer, table).reshape(w, rows, -1)
    v_rows = _pages(v_pool, layer, table).reshape(w, rows, -1)
    mask = jnp.arange(rows)[None, :] < jnp.asarray(lengths)[:, None]
    return lfm2_moe._attend_wide(q, k_rows, v_rows, mask, G)


@pytest.mark.parametrize("heads", sorted(WIDE_SHAPES))
@pytest.mark.parametrize("cache_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(W_CASES))
def test_wide_kernel_matches_the_gather(case, cache_dtype, heads):
    H, G = WIDE_SHAPES[heads]
    for i, lengths in enumerate(W_CASES[case]):
        w = len(lengths)
        q, k_pool, v_pool, table = _wide_pools(
            10 * w + i, (H, G), cache_dtype, w
        )
        got = _wide_kernel(q, k_pool, v_pool, 1, table, lengths, G)
        want = _wide_gather(q, k_pool, v_pool, 1, table, lengths, G)
        assert got.shape == (w, H * DH_WIDE)
        # both accumulate in float32; only the order of the sums differs
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("cache_dtype", ["float32", "bfloat16"])
def test_wide_kernel_at_the_wave_the_shapes_give(cache_dtype):
    """No ``wave`` given: :func:`wave_pages` fills half a megabyte, here
    the whole table in one wave."""
    H, G = WIDE_SHAPES["r32-c512"]
    lengths = [1, W_BLOCK + 1, W_ROWS - 1, W_ROWS]
    q, k_pool, v_pool, table = _wide_pools(5, (H, G), cache_dtype, 4)
    itemsize = jnp.dtype(cache_dtype).itemsize
    assert paged_attention.wave_pages(
        W_BLOCK, G * DH_WIDE, itemsize, W_PAGES
    ) == W_PAGES
    assert paged_attention.wave_pages(64, 512, 2, 72) == 8  # the cell's
    got = _wide_kernel(q, k_pool, v_pool, 0, table, lengths, G, wave=None)
    want = _wide_gather(q, k_pool, v_pool, 0, table, lengths, G)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def _check_wide_reads_no_page_past_its_last(cache_dtype):
    """Every place of a table past the slot's last live page names a page
    of NaNs: page counts 1, 2, 4, 5, 7 leave the last wave of three short,
    and one DMA too many, or a stale row met by a zero weight, shows."""
    H, G = WIDE_SHAPES["r8-c128"]
    lengths = [1, 2 * W_BLOCK, 3 * W_BLOCK + 1, 5 * W_BLOCK, W_ROWS - W_BLOCK]
    q, k_pool, v_pool, table = _wide_pools(
        6, (H, G), cache_dtype, len(lengths), nan_page=True
    )
    for row, n in zip(table, lengths):
        row[-(-n // W_BLOCK):] = W_BLOCKS - 1
    got = _wide_kernel(q, k_pool, v_pool, 1, table, lengths, G)
    assert np.isfinite(np.asarray(got)).all()
    want = _wide_gather(
        q, jnp.nan_to_num(k_pool), jnp.nan_to_num(v_pool), 1, table, lengths, G
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def _check_wide_free_slot(cache_dtype):
    """A free slot inside the width (zeroed table row, length 1) reads
    trash block 0 and nothing else: whatever lies there, the live slots
    answer the same bits."""
    H, G = WIDE_SHAPES["r8-c128"]
    lengths = [40, 1, W_ROWS, 17]
    q, k_pool, v_pool, table = _wide_pools(7, (H, G), cache_dtype, 4)
    table[1] = 0
    got = _wide_kernel(q, k_pool, v_pool, 1, table, lengths, G)
    want = _wide_gather(q, k_pool, v_pool, 1, table, lengths, G)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    again = _wide_kernel(
        q, k_pool.at[:, 0].set(7.0), v_pool.at[:, 0].set(jnp.nan), 1, table,
        lengths, G,
    )
    live = np.array([0, 2, 3])
    np.testing.assert_array_equal(again[live], got[live])


def _check_wide_shared_pages(cache_dtype):
    """Two slots whose tables name the same prefix pages both read them."""
    H, G = WIDE_SHAPES["r8-c128"]
    lengths = [4 * W_BLOCK + 3, 4 * W_BLOCK + 9, 30]
    q, k_pool, v_pool, table = _wide_pools(8, (H, G), cache_dtype, 3)
    table[1, :4] = table[0, :4]
    got = _wide_kernel(q, k_pool, v_pool, 0, table, lengths, G)
    want = _wide_gather(q, k_pool, v_pool, 0, table, lengths, G)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert not np.allclose(got[0], got[1])  # their own queries and tails


WIDE_CHECKS = {
    "no-page-past-the-last": _check_wide_reads_no_page_past_its_last,
    "free-slot": _check_wide_free_slot,
    "shared-pages": _check_wide_shared_pages,
}


@pytest.mark.parametrize("cache_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("check", sorted(WIDE_CHECKS))
def test_wide_kernel_keeps_the_cache_discipline(check, cache_dtype):
    WIDE_CHECKS[check](cache_dtype)


def test_wide_kernel_refuses_more_query_rows_than_its_scratch_plans():
    H, G = 2 * paged_attention.WIDE_QUERY_ROWS, 2
    q, k_pool, v_pool, table = _wide_pools(9, (H, G), "float32", 1)
    with pytest.raises(ValueError, match="query rows"):
        _wide_kernel(q, k_pool, v_pool, 0, table, [5], G)


def _wide_pool(width, dtype, block=64, blocks=4609):
    return jax.ShapeDtypeStruct((2, blocks, block, width), dtype)


@pytest.mark.parametrize(
    "pool, max_pages, taken",
    [
        # the benchmark's LFM2-24B-A2B pool, as the engine builds it
        (_wide_pool(512, jnp.bfloat16), 72, True),
        (_wide_pool(128, jnp.float32, block=8), 16, True),
        # tier-1's engines: 2 K/V heads of 16 side by side, float32
        (_wide_pool(32, jnp.float32, block=16, blocks=33), 8, False),
        (_wide_pool(64, jnp.bfloat16, block=16, blocks=9), 4, False),
        # a page that does not fill the cache dtype's sublane tiles
        (_wide_pool(512, jnp.bfloat16, block=8), 72, False),
        (_wide_pool(512, jnp.float32, block=4), 72, False),
        # the heads on an axis of their own: the other kernel's pool
        (_pool(8, 64, jnp.bfloat16), 72, False),
        # scores of one table past the VMEM the kernel plans for
        (_wide_pool(512, jnp.bfloat16), 512, False),
    ],
)
def test_wide_eligibility_is_decided_from_backend_and_shapes(
    pool, max_pages, taken, monkeypatch
):
    assert jax.default_backend() == "cpu"
    assert not paged_attention.eligible_wide(pool, max_pages)  # never off a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_attention.eligible_wide(pool, max_pages) is taken
