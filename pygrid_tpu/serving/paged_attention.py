"""Pallas TPU paged decode attention: one query row per slot against the
slot's LIVE pages, read in place from the block pool.

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
