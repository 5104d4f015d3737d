"""Pallas TPU paged decode attention: one query row per slot against the
slot's LIVE pages, read in place from the block pool. Two kernels: the
multi-head one below, a page a matmul over a ``[.., H, dh]`` pool, and a
head-agnostic one over a WIDE pool (a position's K/V heads side by side
on the lanes), several pages a DMA wave and a matmul
(:func:`paged_decode_attention_wide`, at the end of the module).

The XLA path in :func:`pygrid_tpu.models.decode.paged_decode_step`
slices one layer's whole pool out as the gather's operand, gathers every
slot's full table (live pages or not), converts the gathered rows to
float32 for a one-row multiply-reduce and reads them again for the
attention: ~64 GB of traffic a step at Cerebras-GPT-1.3B widths where
the live k/v is ~0.7 GB (PERF.md §6, PR 25). Here the pools stay in HBM
as they are; each grid program is one slot, walks pages
``0 .. ceil(length / block) - 1`` of its table row and DMAs each
``[block, H, dh]`` page (contiguous) into a double-buffered VMEM tile.
Pages past the live length cost neither a DMA nor compute.

Same mathematics as the gather path at the same precision, in two
passes so that it rounds the same way: scores ``q·kᵀ`` accumulate in
float32 and are scaled and masked to ``l < length``; one float32 softmax
over all of the slot's scores (max, exp, sum, true division); ``p`` is
cast to the cache dtype; ``p·v`` accumulates in float32.

Layout: a page viewed as ``[block·H, dh]`` is a free reshape (H fills
whole sublane tiles), so both dots are plain 2-D MXU matmuls of the
slot's ``[H, dh]`` query against every (token, head) row of the page.
That computes each head against every head's keys; the kernel keeps the
matching-head entries (column ``l·H + h`` of row ``h``) and masks the
rest to ``-1e30`` like rows past the length, so they leave the softmax
as exact zeros and the second dot sums only the slot's own head. The
MXU has the room, though not for nothing: a K/V page pair takes ~1.2 µs
on a v5e against 0.64 µs for its two DMAs (PERF.md §6, PR 25).

:func:`eligible` is the one rule for when the kernel is taken; everything
else (tier-1's tiny heads, the CPU) keeps the gather path, which is also
this kernel's reference in ``tests/unit/test_paged_attention.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

#: VMEM the kernel may plan for its page buffers and score scratch: half
#: of the v5e's 16 MiB default scoped limit, the rest left to Mosaic
VMEM_BUDGET = 8 * 1024 * 1024


def vmem_bytes(
    block: int, n_heads: int, head_dim: int, max_pages: int, itemsize: int
) -> int:
    """Scratch the kernel allocates: k and v page double buffers plus the
    float32 scores of one slot's whole table."""
    page = block * n_heads * head_dim * itemsize
    scores = max_pages * n_heads * block * n_heads * 4
    return 4 * page + scores


def eligible(cache_k: jax.Array, max_pages: int) -> bool:
    """True when decode attention over this pool takes the kernel: on a
    TPU, with shapes it tiles. ``head_dim`` fills whole 128-lane rows,
    the heads of one token fill whole sublane tiles of the cache dtype
    (8 rows of 32 bits: 8 float32, 16 bfloat16), so a page is a
    ``[block·H, dh]`` matrix with no relayout, and the scratch fits
    :data:`VMEM_BUDGET`. Decided from what the program can observe: no
    switch, no model name."""
    if jax.default_backend() != "tpu":
        return False
    _, _, block, n_heads, head_dim = cache_k.shape
    itemsize = jnp.dtype(cache_k.dtype).itemsize
    if itemsize not in (2, 4) or head_dim % 128:
        return False
    if n_heads % (32 // itemsize):
        return False
    return (
        vmem_bytes(block, n_heads, head_dim, max_pages, itemsize)
        <= VMEM_BUDGET
    )


def _precision(dtype):
    """float32 operands multiply as float32 (the gather path's one-row
    products are exact on the VPU); narrower ones are exact as they are."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _query_operand(q, cache_dtype):
    """The query as the first dot's left operand, in the cache dtype's
    arithmetic without losing any of its own bits. A float32 query
    against a bfloat16 cache (parameters served in bfloat16 with no
    compute cast: the layer norm hands on float32) is split into three
    bfloat16 parts stacked on the row axis, ``q == hi + mid + lo``
    exactly: one bfloat16 matmul with exact products, summed in float32,
    in place of converting every page to float32."""
    if q.dtype == jnp.float32 and cache_dtype == jnp.bfloat16:
        hi = q.astype(jnp.bfloat16)
        rest = q - hi.astype(jnp.float32)
        mid = rest.astype(jnp.bfloat16)
        lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        return jnp.concatenate([hi, mid, lo], axis=0)
    return q.astype(jnp.promote_types(q.dtype, cache_dtype))


def _kernel(
    layer_ref, table_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
    kbuf, vbuf, s_scr, sem, *, scale, block, n_heads, max_pages,
):
    s = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[s]
    n_pages = jnp.clip(pl.cdiv(length, block), 1, max_pages)
    cols = block * n_heads

    def page_copy(pool, buf, which, p, slot):
        return pltpu.make_async_copy(
            pool.at[layer, table_ref[s, p]], buf.at[slot], sem.at[which, slot]
        )

    def page(pool, buf, which, p):
        """Page ``p`` of the slot as ``[block·H, dh]`` in VMEM, with page
        ``p + 1`` on its way into the other buffer."""
        slot = p % 2

        @pl.when(p + 1 < n_pages)
        def _():
            page_copy(pool, buf, which, p + 1, 1 - slot).start()

        page_copy(pool, buf, which, p, slot).wait()
        return buf[slot].reshape(cols, buf.shape[-1])

    page_copy(k_hbm, kbuf, 0, 0, 0).start()
    page_copy(v_hbm, vbuf, 1, 0, 0).start()

    q = _query_operand(q_ref[0], kbuf.dtype)  # [H or 3H, dh]
    # column l·H + h of row h' holds head h' against token l of head h:
    # keep h == h', and only tokens below the slot's length
    row = lax.broadcasted_iota(jnp.int32, (n_heads, cols), 0)
    col = lax.broadcasted_iota(jnp.int32, (n_heads, cols), 1)
    own_head = (col % n_heads) == row
    tok = col // n_heads

    def scores(p, m):
        sc = lax.dot_general(
            q, page(k_hbm, kbuf, 0, p).astype(q.dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_precision(q.dtype),
        )
        if sc.shape[0] != n_heads:  # the query's three bfloat16 parts
            sc = (sc[2 * n_heads:] + sc[n_heads:2 * n_heads]) + sc[:n_heads]
        keep = own_head & (tok + p * block < length)
        sc = jnp.where(keep, sc * scale, _NEG)
        s_scr[p] = sc
        return jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))

    m = lax.fori_loop(
        0, n_pages, scores, jnp.full((n_heads, 1), _NEG, jnp.float32)
    )

    def exps(p, total):
        e = jnp.exp(s_scr[p] - m)
        s_scr[p] = e
        return total + jnp.sum(e, axis=-1, keepdims=True)

    total = lax.fori_loop(
        0, n_pages, exps, jnp.zeros((n_heads, 1), jnp.float32)
    )

    def values(p, acc):
        v2d = page(v_hbm, vbuf, 1, p)
        prob = (s_scr[p] / total).astype(v2d.dtype)
        return acc + jnp.dot(
            prob, v2d, preferred_element_type=jnp.float32,
            precision=_precision(v2d.dtype),
        )

    o_ref[0] = lax.fori_loop(
        0, n_pages, values, jnp.zeros(o_ref.shape[1:], jnp.float32)
    )


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    layer: jax.Array,
    table: jax.Array,
    lengths: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """Attention of one query row per slot over the slot's first
    ``lengths[s]`` cached rows, read through ``table`` from the pools.

    ``q``: [w, H, dh]; ``k_pool``/``v_pool``: [n_layers, num_blocks,
    block, H, dh], left where they are; ``layer``: int32 scalar, the
    pools' layer to read; ``table``: [w, max_pages] int32 block ids;
    ``lengths``: [w] int32 valid rows per slot (the query's own k/v
    already written), at least 1. Returns [w, H, dh] float32.
    ``interpret=True`` runs the same kernel on the CPU for tests."""
    w, n_heads, head_dim = q.shape
    block = k_pool.shape[2]
    max_pages = table.shape[1]
    row_spec = pl.BlockSpec(
        (1, n_heads, head_dim), lambda s, *_: (s, 0, 0),
        memory_space=pltpu.VMEM,
    )
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    page = (2, block, n_heads, head_dim)
    return pl.pallas_call(
        partial(
            _kernel, scale=head_dim**-0.5, block=block, n_heads=n_heads,
            max_pages=max_pages,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(w,),
            in_specs=[row_spec, pool_spec, pool_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM(page, k_pool.dtype),
                pltpu.VMEM(page, v_pool.dtype),
                pltpu.VMEM(
                    (max_pages, n_heads, block * n_heads), jnp.float32
                ),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((w, n_heads, head_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        table.astype(jnp.int32),
        lengths.astype(jnp.int32),
        q, k_pool, v_pool,
    )


# ── a wide pool: a position's heads side by side on the lanes ────────────

#: bytes of K (or V) one DMA wave brings in and one matmul takes. A page
#: alone is too little to hide a DMA's latency behind (a 64 KB page is
#: 0.08 us of a v5e's HBM bandwidth); half a megabyte is eight such pages
WAVE_BYTES = 512 * 1024

#: query rows a slot the wide kernel's score scratch is planned for (the
#: pool does not say how many query heads attend over it)
WIDE_QUERY_ROWS = 64


def wave_pages(block: int, width: int, itemsize: int, max_pages: int) -> int:
    """Pages a wave: as many as fill :data:`WAVE_BYTES`, fixed from the
    pool's shape when the program is traced."""
    return max(1, min(max_pages, WAVE_BYTES // (block * width * itemsize)))


def wide_vmem_bytes(
    block: int, width: int, max_pages: int, itemsize: int
) -> int:
    """Scratch the wide kernel allocates: k and v wave double buffers plus
    the float32 scores of one slot's whole table, :data:`WIDE_QUERY_ROWS`
    query rows of it."""
    wave = wave_pages(block, width, itemsize, max_pages)
    buffers = 4 * wave * block * width * itemsize
    scores = -(-max_pages // wave) * WIDE_QUERY_ROWS * wave * block * 4
    return buffers + scores


def eligible_wide(cache_k: jax.Array, max_pages: int) -> bool:
    """True when decode attention over this WIDE pool ``[layers, blocks,
    block, C]`` takes :func:`paged_decode_attention_wide`: on a TPU, rows
    of whole 128-lane tiles, a page of whole sublane tiles of the cache
    dtype (so a page lands in a wave's buffer with no relayout), and the
    scratch within :data:`VMEM_BUDGET`. Decided from what the program can
    observe: no switch, no model name."""
    if jax.default_backend() != "tpu" or cache_k.ndim != 4:
        return False
    _, _, block, width = cache_k.shape
    itemsize = jnp.dtype(cache_k.dtype).itemsize
    if itemsize not in (2, 4) or width % 128:
        return False
    if block % (32 // itemsize):
        return False
    return wide_vmem_bytes(block, width, max_pages, itemsize) <= VMEM_BUDGET


def _wide_kernel(
    layer_ref, table_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
    kbuf, vbuf, s_scr, sem, *, scale, block, wave, max_pages,
):
    s = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[s]
    n_pages = jnp.clip(pl.cdiv(length, block), 1, max_pages)
    n_waves = pl.cdiv(n_pages, wave)
    rows = wave * block

    def page_copy(pool, buf, which, wv, j):
        """Page ``j`` of wave ``wv`` on its way to its place in the
        wave's buffer."""
        return pltpu.make_async_copy(
            pool.at[layer, table_ref[s, wv * wave + j]],
            buf.at[wv % 2, pl.ds(pl.multiple_of(j * block, block), block)],
            sem.at[which, wv % 2],
        )

    def start(pool, buf, which, wv):
        """All of wave ``wv``'s page DMAs at once. The places of a last
        wave past the slot's last page are not read but zeroed: their
        scores are masked, but a weight of ``0`` times whatever a value
        buffer happened to hold need not be ``0``. A loop over the pages
        and not ``wave`` unrolled branches: the kernel is lowered anew
        in every process, whatever the compile cache holds, and unrolled
        that was half a second a decode program (PERF.md §6, PR 48)."""
        def page(j, _):
            live = wv * wave + j < n_pages

            @pl.when(live)
            def _():
                page_copy(pool, buf, which, wv, j).start()

            @pl.when(jnp.logical_not(live))
            def _():
                at = pl.multiple_of(j * block, block)
                buf[wv % 2, pl.ds(at, block), :] = jnp.zeros(
                    (block, buf.shape[-1]), buf.dtype
                )

        lax.fori_loop(0, wave, page, None)

    def fetch(pool, buf, which, wv):
        """Wave ``wv`` of the slot as ``[wave * block, C]`` in VMEM, with
        wave ``wv + 1`` on its way into the other buffer."""
        @pl.when(wv + 1 < n_waves)
        def _():
            start(pool, buf, which, wv + 1)

        def page(j, _):
            @pl.when(wv * wave + j < n_pages)
            def _():
                page_copy(pool, buf, which, wv, j).wait()

        lax.fori_loop(0, wave, page, None)
        return buf[wv % 2]

    start(k_hbm, kbuf, 0, 0)
    start(v_hbm, vbuf, 1, 0)

    q = q_ref[0]  # [R, C], in the cache dtype
    tok = lax.broadcasted_iota(jnp.int32, (q.shape[0], rows), 1)

    def scores(wv, m):
        sc = lax.dot_general(
            q, fetch(k_hbm, kbuf, 0, wv), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_precision(q.dtype),
        )
        sc = jnp.where(tok + wv * rows < length, sc * scale, _NEG)
        s_scr[wv] = sc
        return jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))

    m = lax.fori_loop(
        0, n_waves, scores, jnp.full((q.shape[0], 1), _NEG, jnp.float32)
    )

    def exps(wv, total):
        e = jnp.exp(s_scr[wv] - m)
        s_scr[wv] = e
        return total + jnp.sum(e, axis=-1, keepdims=True)

    total = lax.fori_loop(
        0, n_waves, exps, jnp.zeros((q.shape[0], 1), jnp.float32)
    )

    def values(wv, acc):
        v2d = fetch(v_hbm, vbuf, 1, wv)
        prob = (s_scr[wv] / total).astype(v2d.dtype)
        return acc + jnp.dot(
            prob, v2d, preferred_element_type=jnp.float32,
            precision=_precision(v2d.dtype),
        )

    o_ref[0] = lax.fori_loop(
        0, n_waves, values, jnp.zeros(o_ref.shape[1:], jnp.float32)
    )


@partial(jax.jit, static_argnames=("scale", "wave", "interpret"))
def paged_decode_attention_wide(
    q_wide: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    layer: jax.Array,
    table: jax.Array,
    lengths: jax.Array,
    scale: float,
    wave: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``softmax(q_wide . K^T * scale, over rows l < lengths[s]) . V`` for
    each slot, K and V the slot's cached rows read through ``table`` from
    wide pools. The kernel knows nothing of heads: the caller lays each
    query head on its K/V head's lanes of a ``C``-wide row (zeros on the
    others) and picks a head's own lanes out of the result.

    ``q_wide``: [w, R, C], multiplied in the pools' dtype; ``k_pool``/
    ``v_pool``: [n_layers, num_blocks, block, C], left where they are;
    ``layer``: int32 scalar; ``table``: [w, max_pages] int32 block ids;
    ``lengths``: [w] int32 valid rows per slot, at least 1; ``wave``:
    pages a wave, :func:`wave_pages` of the shapes unless a test or a
    measurement gives another. Returns [w, R, C] float32.
    ``interpret=True`` runs the same kernel on the CPU for tests."""
    w, q_rows, width = q_wide.shape
    if q_rows > WIDE_QUERY_ROWS:
        raise ValueError(
            f"{q_rows} query rows a slot: the score scratch that "
            f"eligible_wide plans holds {WIDE_QUERY_ROWS}"
        )
    block = k_pool.shape[2]
    max_pages = table.shape[1]
    if wave is None:
        wave = wave_pages(block, width, k_pool.dtype.itemsize, max_pages)
    row_spec = pl.BlockSpec(
        (1, q_rows, width), lambda s, *_: (s, 0, 0), memory_space=pltpu.VMEM,
    )
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    buffers = (2, wave * block, width)
    return pl.pallas_call(
        partial(
            _wide_kernel, scale=scale, block=block, wave=wave,
            max_pages=max_pages,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(w,),
            in_specs=[row_spec, pool_spec, pool_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM(buffers, k_pool.dtype),
                pltpu.VMEM(buffers, v_pool.dtype),
                pltpu.VMEM(
                    (-(-max_pages // wave), q_rows, wave * block),
                    jnp.float32,
                ),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((w, q_rows, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_decode_attention_wide",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        table.astype(jnp.int32),
        lengths.astype(jnp.int32),
        q_wide.astype(k_pool.dtype), k_pool, v_pool,
    )
