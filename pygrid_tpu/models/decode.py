"""Autoregressive decoding for the transformer family — KV-cache serving.

The training side of the flagship model lives in
:mod:`pygrid_tpu.models.transformer`; this module is its inference twin:
a static-shape KV cache, a dense single-pass ``prefill``, and a
``lax.scan``-driven decode loop so a whole ``generate`` call is ONE
compiled XLA program (no per-token Python dispatch, no dynamic shapes —
the cache is allocated at ``max_len`` and masked by position, the idiom
XLA/TPU wants).

Two cache layouts live here and no third. ``KVCache`` with ``prefill``,
``decode_step`` and ``generate`` is the **reference**: one request (or
one lock-step batch) over a contiguous ``[max_len]`` cache, what the
tests, the chip smoke and the examples compare the engine against.
``PagedKVCache`` with ``paged_prefill_chunk`` and ``paged_decode_step``
is the **served** layout: a block pool shared by independent requests,
driven by :mod:`pygrid_tpu.serving` and by nothing else. The node
generates only through the second.

No reference analog: the reference's inference surface is data-centric
``run_inference`` over MLP/CNN plans (SURVEY §2.1); autoregressive
generation exists here because the transformer family does. The decode
attention is a masked dense pass over the cache — at single-token decode
the op is bandwidth-bound on the cache read and XLA's fused
softmax(qkᵀ)v is already the right program, so no Pallas kernel is
needed (the flash kernel earns its keep on the L×L training path). The
PAGED step is the exception: gathering whole block tables made it read
~90× the live k/v, so on a TPU its attention is
:mod:`pygrid_tpu.serving.paged_attention`'s kernel (PERF.md §6, PR 25).

Correctness contract: greedy decode from a prompt must equal repeated
full-forward ``transformer.apply`` argmax (teacher-forced equivalence,
``tests/unit/test_decode.py``).
"""

from __future__ import annotations

import sys
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from pygrid_tpu.models.transformer import (
    PARAMS_PER_LAYER,
    TransformerConfig,
    _cast,
    _ln,
)


def bundle(
    cfg: TransformerConfig, params: Sequence[jax.Array]
) -> dict:
    """Servable transformer bundle for ``host-model`` /
    ``run-generation``: a plain serde-serializable dict carrying the
    config and parameters, so a node can rebuild the model and run
    :func:`generate` against it (``node/events.py run_generation``)."""
    import numpy as np

    return {
        "family": "transformer",
        "cfg": list(cfg),
        "params": [np.asarray(p) for p in params],
    }


#: the served families, one registry: the bundle's ``family`` tag and the
#: config's type name, each to the module that serves it (imported on
#: first use: this module is the transformer's)
FAMILIES = {
    "transformer": ("TransformerConfig", "pygrid_tpu.models.decode"),
    "jamba": ("JambaConfig", "pygrid_tpu.models.jamba"),
    "sdar_moe": ("SdarConfig", "pygrid_tpu.models.sdar_moe"),
    "solar_open2": ("SolarConfig", "pygrid_tpu.models.solar_open2"),
    "brumby": ("BrumbyConfig", "pygrid_tpu.models.brumby"),
    "lfm2_moe": ("Lfm2Config", "pygrid_tpu.models.lfm2_moe"),
}


def _family_module(tag: str) -> Any:
    import importlib

    return importlib.import_module(FAMILIES[tag][1])


def family_of(cfg) -> Any:
    """The module that serves configurations of ``cfg``'s type."""
    name = type(cfg).__name__
    for tag, (config_type, _) in FAMILIES.items():
        if config_type == name:
            return _family_module(tag)
    raise ValueError(f"no served family has configurations of type {name}")


def from_bundle(spec: dict) -> tuple[Any, Any]:
    """Inverse of a family's ``bundle`` (by its ``family`` tag):
    ``(config, parameters)`` as the serving engine takes them."""
    tag = spec.get("family") if isinstance(spec, dict) else None
    if not isinstance(tag, str) or tag not in FAMILIES:
        raise ValueError("not a generative transformer bundle")
    module = _family_module(tag)
    if module is not sys.modules[__name__]:
        return module.from_bundle(spec)
    cfg = TransformerConfig(*[int(v) for v in spec["cfg"]])
    params = [jnp.asarray(p) for p in spec["params"]]
    expect = 2 + PARAMS_PER_LAYER * cfg.n_layers + 2
    if len(params) != expect:
        raise ValueError(
            f"bundle has {len(params)} params, config needs {expect}"
        )
    return cfg, params


# ── what the serving engine asks of a model family ───────────────────────
#
# The engine and its programs reach a hosted model only through its
# family's module (:func:`family_of`): ``init_paged_cache``,
# ``paged_prefill_chunk`` and ``paged_decode_step`` over a cache whose
# first three fields are ``k, v, pos``, and the facts below. A family
# with no K/V layer (``kv_layers`` 0) still names the three: its ``k``
# and ``v`` are empty arrays, ``pos`` counts the positions a slot has
# taken in, the table its programs are handed has one column and is not
# read, and everything the slot holds comes after, as state. This module
# is the transformer's; :mod:`pygrid_tpu.models.jamba` the state-space
# hybrid's, :mod:`pygrid_tpu.models.sdar_moe` the block-diffusion
# decoder's, :mod:`pygrid_tpu.models.solar_open2` the delta-rule
# hybrid's with a chip's share of its experts,
# :mod:`pygrid_tpu.models.brumby` the power-retention decoder's, whose
# cache is state alone, and :mod:`pygrid_tpu.models.lfm2_moe` the
# short-convolution hybrid's with a dense layer ahead of its expert
# layers. A family with experts answers, after its cache,
# what its forward counted of them (one number, or three); a prefill whose
# recurrence runs in chunks may answer a pair: the chunks that held the
# prompt's own positions and the chunks it ran.

#: no recurrent state, so prefix pages can be shared: that sharing is the
#: one thing a family switches off by saying True here
RECURRENT = False


#: positions a row's forward carries: one, and every forward yields one
#: token a row. A family that says more (a diffusion block) has a step
#: that takes the block and its mask flags, and a forward of it may
#: reveal none: the engine then holds the block's state between forwards
BLOCK_LEN = 1


def kv_layers(cfg: TransformerConfig) -> int:
    """Layers that hold keys and values in the block pool: all of them."""
    return cfg.n_layers


def kv_heads(cfg: TransformerConfig) -> int:
    return cfg.n_heads


def kv_kernel(cache_k: jax.Array, max_pages: int) -> bool:
    """Whether decode attention over this pool reads live pages in place."""
    from pygrid_tpu.serving import paged_attention

    return paged_attention.eligible(cache_k, max_pages)


def state_bytes_per_slot(cfg: TransformerConfig, dtype: Any) -> int:
    """Device bytes a slot holds beside its K/V pages: none."""
    return 0


def cache_elements(cfg: TransformerConfig, batch: int) -> int:
    """Cache elements ``batch`` rows can hold at ``max_len``."""
    return 2 * cfg.n_layers * batch * cfg.max_len * cfg.d_model


class KVCache(NamedTuple):
    """Static-shape per-layer key/value cache.

    ``k``/``v``: [n_layers, B, max_len, n_heads, head_dim]; ``pos``: the
    number of valid positions already written (scalar int32, traced).
    """

    k: jax.Array
    v: jax.Array
    pos: jax.Array


def init_cache(
    cfg: TransformerConfig,
    batch: int,
    dtype: Any = jnp.float32,
) -> KVCache:
    dh = cfg.d_model // cfg.n_heads
    shape = (cfg.n_layers, batch, cfg.max_len, cfg.n_heads, dh)
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        pos=jnp.int32(0),
    )


def _block(h, layer_params, c, attn):
    """One transformer block with an injected attention stage — the ONE
    copy of the per-layer numerics every decode variant shares (the
    bit-identical-greedy contract between ``generate()`` and the slot
    engine rides on these staying in lockstep). ``attn(x, wq, wk, wv)``
    receives the ln1 output and the cast projection weights and returns
    the attention result [..., d_model], handling the q/k/v layout,
    cache writes, and masking for its variant."""
    (ln1_s, ln1_b, wq, wk, wv, wo, ln2_s, ln2_b, w1, b1, w2, b2) = (
        layer_params
    )
    x = c(_ln(h, ln1_s, ln1_b))
    a = attn(x, c(wq), c(wk), c(wv))
    h = h + c(a) @ c(wo)
    with jax.named_scope("mlp"):
        x = c(_ln(h, ln2_s, ln2_b))
        return h + c(jax.nn.gelu(x @ c(w1) + c(b1))) @ c(w2) + c(b2)


def _decode_attention(q, k_cache, v_cache, n_valid):
    """Masked dense attention of ONE query position against the cache.

    q: [B, H, dh]; k_cache/v_cache: [B, max_len, H, dh]; n_valid: scalar
    count of live cache rows (the query's own k/v already written).
    f32 softmax per the repo-wide contract."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bhd,blhd->bhl", q, k_cache,
        preferred_element_type=jnp.float32,
    ) * scale
    mask = jnp.arange(k_cache.shape[1]) < n_valid  # [max_len]
    s = jnp.where(mask[None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhl,blhd->bhd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )


def decode_step(
    params: Sequence[jax.Array],
    cache: KVCache,
    token: jax.Array,
    cfg: TransformerConfig = TransformerConfig(),
    compute_dtype: Any | None = None,
) -> tuple[jax.Array, KVCache]:
    """One decode step: ``token`` [B] int32 at position ``cache.pos`` →
    (logits [B, vocab] f32, cache with k/v appended)."""
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else None

    def c(x):
        return _cast(x, cd)

    embed, pos_emb = params[0], params[1]
    B = token.shape[0]
    dh = cfg.d_model // cfg.n_heads
    t = cache.pos
    h = c(embed[token] + pos_emb[t])  # [B, d]

    new_k, new_v = cache.k, cache.v
    idx = 2
    for layer in range(cfg.n_layers):

        def attn(x, wq, wk, wv, layer=layer):
            nonlocal new_k, new_v
            q = (x @ wq).reshape(B, cfg.n_heads, dh)
            k = (x @ wk).reshape(B, cfg.n_heads, dh)
            v = (x @ wv).reshape(B, cfg.n_heads, dh)
            new_k = new_k.at[layer, :, t].set(k.astype(new_k.dtype))
            new_v = new_v.at[layer, :, t].set(v.astype(new_v.dtype))
            return _decode_attention(
                q, new_k[layer], new_v[layer], t + 1
            ).reshape(B, cfg.d_model)

        h = _block(h, params[idx : idx + PARAMS_PER_LAYER], c, attn)
        idx += PARAMS_PER_LAYER
    h = _ln(h, params[idx], params[idx + 1])
    logits = jnp.dot(
        c(h), c(embed).T, preferred_element_type=jnp.float32
    )
    return logits, KVCache(k=new_k, v=new_v, pos=t + 1)


def prefill(
    params: Sequence[jax.Array],
    cache: KVCache,
    prompt: jax.Array,
    cfg: TransformerConfig = TransformerConfig(),
    compute_dtype: Any | None = None,
) -> tuple[jax.Array, KVCache]:
    """Ingest a [B, P] prompt in ONE dense causal pass; returns the last
    position's logits and the filled cache.

    All P positions flow through each layer together (causal-masked
    attention over the whole prompt, k/v written to the cache in bulk via
    ``dynamic_update_slice``) — the sequential ``lax.scan`` this replaces
    dispatched P dependent single-token steps, serializing what is a
    parallel matmul workload. Same numerics contract as the full forward
    (``tests/unit/test_decode.py`` asserts the last-position logits
    against ``transformer.apply``)."""
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else None

    def c(x):
        return _cast(x, cd)

    embed, pos_emb = params[0], params[1]
    B, P = prompt.shape
    dh = cfg.d_model // cfg.n_heads
    t0 = cache.pos
    positions = t0 + jnp.arange(P)  # global positions of the prompt
    h = c(embed[prompt] + pos_emb[positions])  # [B, P, d]
    scale = dh**-0.5
    #: rows of the cache a query at global position p may read: everything
    #: written before this prefill plus the causal prefix of the prompt
    mask = (
        jnp.arange(cfg.max_len)[None, :] <= positions[:, None]
    )  # [P, max_len]

    new_k, new_v = cache.k, cache.v
    idx = 2
    for layer in range(cfg.n_layers):

        def attn(x, wq, wk, wv, layer=layer):
            nonlocal new_k, new_v
            q = (x @ wq).reshape(B, P, cfg.n_heads, dh)
            k = (x @ wk).reshape(B, P, cfg.n_heads, dh)
            v = (x @ wv).reshape(B, P, cfg.n_heads, dh)
            new_k = lax.dynamic_update_slice(
                new_k, k.astype(new_k.dtype)[None], (layer, 0, t0, 0, 0)
            )
            new_v = lax.dynamic_update_slice(
                new_v, v.astype(new_v.dtype)[None], (layer, 0, t0, 0, 0)
            )
            s = jnp.einsum(
                "bphd,blhd->bhpl", q, new_k[layer],
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(mask[None, None, :, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum(
                "bhpl,blhd->bphd", p.astype(new_v.dtype), new_v[layer],
                preferred_element_type=jnp.float32,
            ).reshape(B, P, cfg.d_model)

        h = _block(h, params[idx : idx + PARAMS_PER_LAYER], c, attn)
        idx += PARAMS_PER_LAYER
    h = _ln(h[:, -1], params[idx], params[idx + 1])  # last position only
    logits = jnp.dot(
        c(h), c(embed).T, preferred_element_type=jnp.float32
    )
    return logits, KVCache(k=new_k, v=new_v, pos=t0 + P)


# ── paged (block-table) shared cache ─────────────────────────────────────────
#
# The cache the serving engine (pygrid_tpu.serving) keeps, ONE per hosted
# model, for S request slots that join (per-slot prefill), decode together at
# their own positions and leave between steps (PagedAttention, Kwon et al.
# SOSP '23; prefix sharing after RadixAttention, Zheng et al.): instead of one
# contiguous [max_len] region per slot, k/v live in ONE pool of fixed-size
# blocks and each slot carries a block table mapping logical pages to pool
# blocks. Short requests hold only the pages they use, and identical prompt
# prefixes can share read-only pages copy-on-write (appends always land in a
# request's own private pages — the engine allocates tables so a shared page
# is never a scatter target). Block 0 is the TRASH block: never allocated,
# the scatter target for pad positions and freed slots, never read unmasked.


class PagedKVCache(NamedTuple):
    """Block-pool key/value cache shared by independent requests.

    ``k``/``v``: [n_layers, num_blocks, block, n_heads, head_dim]; ``pos``:
    [S] int32 per-slot valid-row counts. Logical row ``j`` of slot ``s``
    lives at pool block ``table[s, j // block]``, offset ``j % block`` —
    the block table is a separate (engine-owned, host-updated) argument,
    not part of this carry, because it only changes at admission/free.
    """

    k: jax.Array
    v: jax.Array
    pos: jax.Array


#: the cache the paged programs carry (``family_of``'s contract)
PagedCache = PagedKVCache


def init_paged_cache(
    cfg: TransformerConfig,
    slots: int,
    num_blocks: int,
    block: int,
    dtype: Any = jnp.float32,
) -> PagedKVCache:
    dh = cfg.d_model // cfg.n_heads
    shape = (cfg.n_layers, num_blocks, block, cfg.n_heads, dh)
    return PagedKVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        pos=jnp.zeros((slots,), jnp.int32),
    )


def paged_prefill_chunk(
    params: Sequence[jax.Array],
    cache: PagedKVCache,
    table: jax.Array,
    slot: jax.Array,
    chunk: jax.Array,
    start: jax.Array,
    length: jax.Array,
    cfg: TransformerConfig = TransformerConfig(),
    compute_dtype: Any | None = None,
) -> tuple[jax.Array, PagedKVCache]:
    """Dense prefill of one slot's prompt SUFFIX through its block table.

    ``chunk``: [Pb] int32, the prompt's tokens from ``start`` on, padded
    to a bucket width; ``start``: the global position of ``chunk[0]`` —
    0 for a fresh prompt, or the (block-aligned) length of a shared
    prefix whose pages the engine already mapped into ``table[slot]``;
    ``length``: the TOTAL prompt length (start + true chunk length).
    All three are traced, so one compiled program serves every prefix
    split within a chunk bucket. Returns the logits at prompt position
    ``length - 1`` ([vocab]) and the cache with the chunk's rows written
    through the table and ``pos[slot] = length``.

    Attention gathers the slot's logical rows [0, max_pages*block) from
    the pool and masks to ``l <= start + p`` — a continuation chunk reads
    the shared prefix it did not compute, which is the prefill work a
    prefix hit saves. Pad positions (and any position past the table)
    scatter into trash block 0, never into an allocated page, so a
    SHARED page is never written by construction — that is the whole
    copy-on-write discipline, enforced here rather than by the engine.
    """
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else None

    def c(x):
        return _cast(x, cd)

    embed, pos_emb = params[0], params[1]
    Pb = chunk.shape[0]
    block = cache.k.shape[2]
    max_pages = table.shape[1]
    rows = max_pages * block
    dh = cfg.d_model // cfg.n_heads
    positions = start + jnp.arange(Pb)  # global positions, unclipped
    h = c(embed[chunk] + pos_emb[jnp.minimum(positions, cfg.max_len - 1)])
    row = table[slot]  # [max_pages]
    real = jnp.arange(Pb) < (length - start)
    page = jnp.minimum(positions // block, max_pages - 1)
    #: pad scatter targets route to trash block 0 — a pad row must never
    #: land in a real page (it could be SHARED with another request)
    blk = jnp.where(real, row[page], 0)
    off = jnp.where(real, positions % block, 0)
    #: query at global position p sees rows [0, p]: the shared prefix
    #: plus the chunk's own causal history (scattered just above)
    mask = jnp.arange(rows)[None, :] <= positions[:, None]  # [Pb, rows]
    scale = dh**-0.5

    new_k, new_v = cache.k, cache.v
    idx = 2
    for layer in range(cfg.n_layers):

        def attn(x, wq, wk, wv, layer=layer):
            nonlocal new_k, new_v
            q = (x @ wq).reshape(Pb, cfg.n_heads, dh)
            # round k/v through the CACHE dtype before attending —
            # decode reads these rows post-rounding and bit-identical
            # greedy requires prefill to see the same
            k = (x @ wk).reshape(Pb, cfg.n_heads, dh).astype(new_k.dtype)
            v = (x @ wv).reshape(Pb, cfg.n_heads, dh).astype(new_v.dtype)
            with jax.named_scope("kv_write"):
                new_k = new_k.at[layer, blk, off].set(k)
                new_v = new_v.at[layer, blk, off].set(v)
            with jax.named_scope("kv_gather"):
                k_rows = new_k[layer, row].reshape(rows, cfg.n_heads, dh)
                v_rows = new_v[layer, row].reshape(rows, cfg.n_heads, dh)
            with jax.named_scope("paged_attention"):
                s = jnp.einsum(
                    "phd,lhd->hpl", q, k_rows,
                    preferred_element_type=jnp.float32,
                ) * scale
                s = jnp.where(mask[None, :, :], s, -1e30)
                p = jax.nn.softmax(s, axis=-1)
                return jnp.einsum(
                    "hpl,lhd->phd", p.astype(v_rows.dtype), v_rows,
                    preferred_element_type=jnp.float32,
                ).reshape(Pb, cfg.d_model)

        h = _block(h, params[idx : idx + PARAMS_PER_LAYER], c, attn)
        idx += PARAMS_PER_LAYER
    h_last = lax.dynamic_index_in_dim(
        h, length - 1 - start, axis=0, keepdims=False
    )
    with jax.named_scope("lm_head"):
        h_last = _ln(h_last, params[idx], params[idx + 1])
        logits = jnp.dot(
            c(h_last), c(embed).T, preferred_element_type=jnp.float32
        )
    return logits, PagedKVCache(
        k=new_k, v=new_v, pos=cache.pos.at[slot].set(length)
    )


def paged_decode_step(
    params: Sequence[jax.Array],
    cache: PagedKVCache,
    table: jax.Array,
    token: jax.Array,
    cfg: TransformerConfig = TransformerConfig(),
    compute_dtype: Any | None = None,
    active: jax.Array | None = None,
) -> tuple[jax.Array, PagedKVCache]:
    """One decode step for the first ``w`` slots through their block
    tables: each slot at its own ``pos``, logits [w, vocab] f32, one row
    appended per advanced slot. A free slot inside the width has a
    zeroed table row, so its garbage write lands in trash block 0 — it
    can never corrupt a block that was freed and reallocated to a live
    request.

    ``active`` ([w] bool, optional) freezes rows mid-batch: a frozen
    row's k/v write routes to trash block 0 and its ``pos`` does not
    advance, so the row's cache state is EXACTLY as if the step never
    ran for it. This is what lets the fused multi-step scan keep
    stepping a batch after some rows finish (wasted compute, no state
    damage) — an active row's numerics are untouched by the mask, so
    the bit-identical-greedy contract survives fusion.

    Attention takes one of two paths, by one rule read when the program
    is traced (:func:`pygrid_tpu.serving.paged_attention.eligible`): on
    a TPU, at shapes it tiles, a Pallas kernel reads each slot's live
    pages in place; everywhere else the slot's whole table is gathered
    and masked, as below.
    """
    from pygrid_tpu.serving import paged_attention

    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else None

    def c(x):
        return _cast(x, cd)

    embed, pos_emb = params[0], params[1]
    w = token.shape[0]
    block = cache.k.shape[2]
    max_pages = table.shape[1]
    rows = max_pages * block
    dh = cfg.d_model // cfg.n_heads
    t = cache.pos[:w]  # [w] per-slot positions
    tw = table[:w]  # [w, max_pages]
    page = jnp.minimum(t // block, max_pages - 1)
    blk = jnp.take_along_axis(tw, page[:, None], axis=1)[:, 0]  # [w]
    off = t % block
    if active is not None:
        blk = jnp.where(active, blk, 0)  # frozen rows scatter to trash
    h = c(embed[token] + pos_emb[jnp.minimum(t, cfg.max_len - 1)])
    mask = jnp.arange(rows)[None, :] <= t[:, None]  # [w, rows]
    scale = dh**-0.5
    use_kernel = paged_attention.eligible(cache.k, max_pages)
    if use_kernel:
        #: rows the kernel attends over: ``l <= t``. A free slot inside
        #: the width (zeroed table row: block 0 is never a live row's
        #: page) has a stale, growing ``pos``; it reads one page of
        #: trash, not 32
        lengths = jnp.where(tw[:, 0] != 0, jnp.minimum(t + 1, rows), 1)

    new_k, new_v = cache.k, cache.v
    idx = 2
    for layer in range(cfg.n_layers):

        def attn(x, wq, wk, wv, layer=layer):
            nonlocal new_k, new_v
            q = (x @ wq).reshape(w, cfg.n_heads, dh)
            k = (x @ wk).reshape(w, cfg.n_heads, dh)
            v = (x @ wv).reshape(w, cfg.n_heads, dh)
            with jax.named_scope("kv_write"):
                new_k = new_k.at[layer, blk, off].set(k.astype(new_k.dtype))
                new_v = new_v.at[layer, blk, off].set(v.astype(new_v.dtype))
            if use_kernel:
                with jax.named_scope("paged_attention"):
                    # off the TPU only a test that patched ``eligible``
                    # gets here: the same kernel, interpreted
                    return paged_attention.paged_decode_attention(
                        q, new_k, new_v, jnp.int32(layer), tw, lengths,
                        interpret=jax.default_backend() != "tpu",
                    ).reshape(w, cfg.d_model)
            with jax.named_scope("kv_gather"):
                k_rows = new_k[layer][tw].reshape(w, rows, cfg.n_heads, dh)
                v_rows = new_v[layer][tw].reshape(w, rows, cfg.n_heads, dh)
            with jax.named_scope("paged_attention"):
                s = jnp.einsum(
                    "whd,wlhd->whl", q, k_rows,
                    preferred_element_type=jnp.float32,
                ) * scale
                s = jnp.where(mask[:, None, :], s, -1e30)
                p = jax.nn.softmax(s, axis=-1)
                return jnp.einsum(
                    "whl,wlhd->whd", p.astype(v_rows.dtype), v_rows,
                    preferred_element_type=jnp.float32,
                ).reshape(w, cfg.d_model)

        h = _block(h, params[idx : idx + PARAMS_PER_LAYER], c, attn)
        idx += PARAMS_PER_LAYER
    with jax.named_scope("lm_head"):
        h = _ln(h, params[idx], params[idx + 1])
        logits = jnp.dot(
            c(h), c(embed).T, preferred_element_type=jnp.float32
        )
    advance = (
        active.astype(jnp.int32) if active is not None
        else jnp.ones((w,), jnp.int32)
    )
    new_pos = cache.pos.at[:w].add(advance)
    return logits, PagedKVCache(k=new_k, v=new_v, pos=new_pos)


def generate(
    params: Sequence[jax.Array],
    prompt: jax.Array,
    n_new: int,
    cfg: TransformerConfig = TransformerConfig(),
    temperature: float | jax.Array = 0.0,
    key: jax.Array | None = None,
    compute_dtype: Any | None = None,
    cache_dtype: Any | None = None,
) -> jax.Array:
    """Generate ``n_new`` tokens after a [B, P] prompt; returns [B, n_new].

    ``temperature == 0``: greedy argmax. Otherwise softmax sampling at
    the given temperature (``key`` required); ``temperature`` may be a
    traced scalar when sampling, so one jitted program serves every
    temperature. The prefill is one dense causal pass and the decode
    loop is one ``lax.scan`` — the whole call jits to a single XLA
    program with a static-shape cache. ``cache_dtype`` narrows the KV cache itself
    (decode is bandwidth-bound on the cache read, so bf16 halves the
    per-step sweep); defaults to ``compute_dtype`` when that is set,
    else f32. Exactly ``n_new - 1`` decode steps run after prefill —
    the first token comes from the prefill logits.
    """
    if prompt.shape[1] + n_new > cfg.max_len:
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + n_new ({n_new}) exceeds "
            f"max_len ({cfg.max_len})"
        )
    temp_is_static = isinstance(temperature, (int, float))
    if temp_is_static and temperature < 0.0:
        # the traced path clamps negatives to greedy; the static path
        # would sample the LEAST likely tokens — reject instead
        raise ValueError("temperature must be >= 0")
    if temp_is_static and temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) requires a PRNG key")
    if not temp_is_static and key is None:
        raise ValueError("a traced temperature requires a PRNG key")
    # sample iff a key was provided and temperature isn't a static zero
    greedy = key is None or (temp_is_static and temperature == 0.0)

    kv_dtype = (
        cache_dtype
        if cache_dtype is not None
        else (compute_dtype if compute_dtype is not None else jnp.float32)
    )
    cache = init_cache(cfg, prompt.shape[0], dtype=kv_dtype)
    logits, cache = prefill(params, cache, prompt, cfg, compute_dtype)

    def pick(logits, k):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)
        if temp_is_static:
            # static temperature is validated >= 0 at entry (== 0 is the
            # greedy branch), so the divide is safe here
            return jax.random.categorical(
                k, logits / temperature, axis=-1
            ).astype(prompt.dtype)
        # traced temperature: a runtime zero must fall back to greedy —
        # logits / 0 is NaN logits and categorical over NaN returns
        # arbitrary tokens; the guard keeps one compiled program serving
        # every temperature INCLUDING zero
        t = jnp.asarray(temperature, jnp.float32)
        safe_t = jnp.where(t > 0.0, t, jnp.float32(1.0))
        sampled = jax.random.categorical(k, logits / safe_t, axis=-1)
        return jnp.where(
            t > 0.0, sampled, jnp.argmax(logits, axis=-1)
        ).astype(prompt.dtype)

    keys = (
        jax.random.split(key, n_new)
        if key is not None
        else jnp.zeros((n_new, 2), jnp.uint32)
    )

    first = pick(logits, keys[0])

    def step(carry, k):
        cache, tok = carry
        new_logits, cache = decode_step(
            params, cache, tok, cfg, compute_dtype
        )
        nxt = pick(new_logits, k)
        return (cache, nxt), nxt

    _, rest = lax.scan(step, (cache, first), keys[1:])
    return jnp.concatenate([first[:, None], rest.T], axis=1)
