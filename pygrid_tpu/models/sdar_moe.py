"""Block-diffusion decoder with routed experts (the SDAR-MoE block) — the
third model family the serving engine hosts.

A stack of pre-RMSNorm residual layers, each grouped-query attention
followed by a routed expert layer::

    a = rms(h; norm_in);  q = a Wq [n_heads, head_dim],  k = a Wk,  v = a Wv
        [n_kv_heads, head_dim], no biases;  q, k <- rms over head_dim with a
        learned weight (q_norm, k_norm);  rotary positions on all of
        head_dim, rotate-half, at the absolute position;  scores
        q·k / sqrt(head_dim), each K/V head under its n_heads / n_kv_heads
        query heads;  h += softmax_f32(scores + M) v Wo
    m = rms(h; norm_ff);  h += sum_{e in top-k} p_e · experts_e(m)
        (:func:`pygrid_tpu.models.moe.routed_experts`: softmax router in
        float32, the k largest renormalised, no drops, no shared expert)
    logits = rms(h; norm_f) W_head                      (an untied head)

**The mask is block-causal**, ``M[i, j] = 1`` iff ``j // BLOCK_LEN <= i //
BLOCK_LEN``: a position sees all of its own block of ``BLOCK_LEN`` and all
of every earlier block. Generation is by diffusion over blocks: a block
starts masked (the mask token's embedding row stands at a masked
position), a *denoising* forward runs the block's positions over the
committed K/V and themselves and reveals some of the masked positions,
each with the argmax of its OWN position's logits, the most confident
first; when none is masked the block is *committed*: it runs once more
with all of its tokens known, its K/V are what later blocks read, and
the row's position moves on by ``BLOCK_LEN``. That run needs no forward
of its own: the whole block rides in the NEXT block's first denoising
forward, ``2 x BLOCK_LEN`` positions under the block-causal mask among
them (the layout the family is trained in: a noised block attending the
clean blocks before it). So a row's forward carries the current block's
``BLOCK_LEN`` positions, with the block before them where it is to be
committed, and yields nought to ``BLOCK_LEN`` tokens. Which positions
are masked is the caller's knowledge, handed in as flags:
``cfg.mask_id`` can occur as a real token.

Parameters are a nested dict BY NAME (``embed``, ``head``, ``norm_f``,
``layers``: a list of per-layer dicts), matrices ``[in, out]``, a layer's
experts stacked ``[n_experts, ...]``.

Serving keeps K/V in the engine's paged block pool, ``[n_layers, blocks,
block, n_kv_heads, head_dim]``, keys after their norm and rotation.
:func:`paged_decode_step` is the ONE device program's body: every forward
writes its block's K/V at ``pos .. pos + BLOCK_LEN - 1`` (a later forward
of the same block overwrites them) and attends over ``pos + BLOCK_LEN``
rows; where the caller says the block before is committed, that block's
known tokens run at ``pos`` first, their K/V stand, and ``pos`` moves on
by ``BLOCK_LEN`` before the current block is placed. A
page of ``block`` tokens ends on a block boundary (``block % BLOCK_LEN ==
0``), so a prompt page's K/V depend on nothing after the page: prefix
pages stay shareable.

Precision: weights as stored (bf16 when served), matmuls accumulate in
float32; the residual stream, norms, rotation, router and softmax are
float32.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from pygrid_tpu.models import moe
from pygrid_tpu.models.decode import PagedKVCache
from pygrid_tpu.models.jamba import _caster, _mm, _rms

#: positions a row's forward carries: the diffusion block (the family's
#: published generation script; the configuration has no key for it)
BLOCK_LEN = 4


class SdarConfig(NamedTuple):
    vocab: int = 128
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    n_layers: int = 2
    n_experts: int = 8
    top_k: int = 2
    d_expert: int = 32
    #: the served context (rows of a slot's block table)
    max_len: int = 256
    #: the token whose embedding row stands at a masked position
    mask_id: int = 127
    rope_theta: int = 1_000_000


# ── what the engine asks of a family ─────────────────────────────────────

RECURRENT = False


def kv_layers(cfg: SdarConfig) -> int:
    return cfg.n_layers


def kv_heads(cfg: SdarConfig) -> int:
    return cfg.n_kv_heads


def kv_kernel(cache_k: jax.Array, max_pages: int) -> bool:
    """Block attention gathers: the Pallas kernel reads one query row a
    cache head, and a block has ``BLOCK_LEN x n_heads / n_kv_heads``."""
    return False


def state_bytes_per_slot(cfg: SdarConfig, dtype: Any) -> int:
    return 0


def cache_elements(cfg: SdarConfig, batch: int) -> int:
    per_token = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
    return int(batch * cfg.max_len * per_token)


PagedCache = PagedKVCache


def init_paged_cache(
    cfg: SdarConfig,
    slots: int,
    num_blocks: int,
    block: int,
    dtype: Any = jnp.float32,
) -> PagedKVCache:
    if block % BLOCK_LEN or cfg.max_len % BLOCK_LEN:
        raise ValueError(
            f"a page of {block} tokens and a context of {cfg.max_len} must "
            f"both end on a block boundary (multiples of {BLOCK_LEN})"
        )
    shape = (cfg.n_layers, num_blocks, block, cfg.n_kv_heads, cfg.head_dim)
    return PagedKVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        pos=jnp.zeros((slots,), jnp.int32),
    )


# ── parameters ───────────────────────────────────────────────────────────


def layer_shapes(cfg: SdarConfig) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape of one layer's parameters."""
    d, dh, f, E = cfg.d_model, cfg.head_dim, cfg.d_expert, cfg.n_experts
    return {
        "norm_in": (d,), "wq": (d, cfg.n_heads * dh),
        "wk": (d, cfg.n_kv_heads * dh), "wv": (d, cfg.n_kv_heads * dh),
        "q_norm": (dh,), "k_norm": (dh,), "wo": (cfg.n_heads * dh, d),
        "norm_ff": (d,), "router": (d, E), "w_gate": (E, d, f),
        "w_up": (E, d, f), "w_down": (E, f, d),
    }


def init(key: jax.Array, cfg: SdarConfig = SdarConfig()) -> dict:
    """Seeded parameters: matrices normal at fan-in scale, norms one."""
    d = cfg.d_model
    params: dict = {
        "embed": jax.random.normal(
            jax.random.fold_in(key, 0), (cfg.vocab, d)
        ) * d**-0.5,
        "head": jax.random.normal(
            jax.random.fold_in(key, 1), (d, cfg.vocab)
        ) * d**-0.5,
        "norm_f": jnp.ones((d,)),
        "layers": [],
    }
    for layer in range(cfg.n_layers):
        lkey = jax.random.fold_in(key, layer + 2)
        lp = {}
        for j, (name, shape) in enumerate(layer_shapes(cfg).items()):
            if "norm" in name:
                lp[name] = jnp.ones(shape)
            else:
                lp[name] = jax.random.normal(
                    jax.random.fold_in(lkey, j), shape
                ) * shape[-2] ** -0.5
        params["layers"].append(lp)
    return params


def bundle(cfg: SdarConfig, params: dict) -> dict:
    """Servable bundle for ``host-model`` / ``run-generation``."""
    import numpy as np

    return {
        "family": "sdar_moe",
        "cfg": list(cfg),
        "params": jax.tree.map(np.asarray, params),
    }


def from_bundle(spec: dict) -> tuple[SdarConfig, dict]:
    """Inverse of :func:`bundle`; every leaf is checked by name and shape
    against the config."""
    if not isinstance(spec, dict) or spec.get("family") != "sdar_moe":
        raise ValueError("not a generative sdar_moe bundle")
    cfg = SdarConfig(*[int(v) for v in spec["cfg"]])
    params = jax.tree.map(jnp.asarray, spec["params"])
    layers = params.get("layers") if isinstance(params, dict) else None
    if layers is None or len(layers) != cfg.n_layers:
        raise ValueError(f"bundle's layers do not number {cfg.n_layers}")
    want = layer_shapes(cfg)
    for i, lp in enumerate(layers):
        got = {k: tuple(v.shape) for k, v in lp.items()}
        if got != want:
            raise ValueError(f"layer {i}: leaves {got}, config needs {want}")
    for name, shape in (
        ("embed", (cfg.vocab, cfg.d_model)), ("head", (cfg.d_model, cfg.vocab)),
        ("norm_f", (cfg.d_model,)),
    ):
        if tuple(params[name].shape) != shape:
            raise ValueError(f"{name}: shape {params[name].shape}, not {shape}")
    return cfg, params


# ── the mathematics prefill and the block step share ─────────────────────


def _rope(x, positions, theta):
    """Rotary positions on all of the last axis, rotate-half. ``x``:
    [..., heads, dh] float32; ``positions``: x's leading axes."""
    half = x.shape[-1] // 2
    inv_freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    cos = jnp.cos(angle)[..., None, :]
    sin = jnp.sin(angle)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkv(x, lp, c, cfg, positions, kv_dtype):
    """q [..., n_heads, dh] float32 and k, v [..., n_kv_heads, dh] as the
    cache holds them, head norms and rotation applied."""
    lead = x.shape[:-1]
    dh = cfg.head_dim
    q = _mm(x, c(lp["wq"])).reshape(*lead, cfg.n_heads, dh)
    k = _mm(x, c(lp["wk"])).reshape(*lead, cfg.n_kv_heads, dh)
    v = _mm(x, c(lp["wv"])).reshape(*lead, cfg.n_kv_heads, dh)
    q = _rope(_rms(q, lp["q_norm"]), positions, cfg.rope_theta)
    k = _rope(_rms(k, lp["k_norm"]), positions, cfg.rope_theta)
    return q, k.astype(kv_dtype), v.astype(kv_dtype)


def _pages(pool, layer: int, table):
    """The pages ``table`` names of one layer of ``pool`` [L, blocks,
    block, G, dh], in ONE gather over the pool where it lies:
    ``pool[layer][table]`` made XLA copy the layer out first, 67 MB at
    the served size before each of a forward's twelve gathers (12% of the
    device's busy time: PERF.md §6, PR 34)."""
    return pool[jnp.full_like(table, layer), table]


def _experts(h, lp, c, cfg, live=None):
    """The expert layer's residual branch over ``h`` [..., d] and the
    number of this layer's experts that received a row. ``live`` [...]
    bool: positions that are somebody's (``None``: all); the others take
    no row of any expert."""
    lead = h.shape[:-1]
    y, touched, _ = moe.routed_experts(
        _rms(h, lp["norm_ff"]).reshape(-1, cfg.d_model), lp["router"],
        c(lp["w_gate"]), c(lp["w_up"]), c(lp["w_down"]), cfg.top_k,
        interpret=jax.default_backend() != "tpu",
        live=None if live is None else live.reshape(-1),
    )
    return y.reshape(*lead, cfg.d_model), touched


def expert_bytes(params: dict) -> int:
    """Bytes of one expert's three matrices as stored."""
    lp = params["layers"][0]
    return sum(
        lp[name][0].size * lp[name].dtype.itemsize
        for name in ("w_gate", "w_up", "w_down")
    )


# ── serving: prefill and the block step through the paged cache ──────────


def paged_prefill_chunk(
    params: dict,
    cache: PagedKVCache,
    table: jax.Array,
    slot: jax.Array,
    chunk: jax.Array,
    start: jax.Array,
    length: jax.Array,
    cfg: SdarConfig = SdarConfig(),
    compute_dtype: Any | None = None,
) -> tuple[jax.Array, PagedKVCache, jax.Array]:
    """Prefill of one slot's prompt blocks through its block table, under
    the block-causal mask. ``chunk`` [Pb]: the tokens from ``start`` (the
    page-aligned length of a shared prefix) on, padded to a bucket;
    ``length``: where the prompt's WHOLE blocks end, a multiple of
    ``BLOCK_LEN`` (its tail, if any, opens the first generated block).
    Writes the K/V of positions ``start .. length - 1`` (pads to trash
    block 0) and sets ``pos[slot] = length``. No token comes of a prefill:
    the logits returned are zeros, and the last layer's experts, which
    feed nothing but logits, are not run. Third, as the block step gives
    it: the bytes of expert weights the prefill had to read."""
    c = _caster(compute_dtype)
    Pb = chunk.shape[0]
    block = cache.k.shape[2]
    max_pages = table.shape[1]
    rows = max_pages * block
    G, R = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    positions = start + jnp.arange(Pb)
    real = jnp.arange(Pb) < (length - start)
    row = table[slot]
    page = jnp.minimum(positions // block, max_pages - 1)
    blk = jnp.where(real, row[page], 0)
    off = jnp.where(real, positions % block, 0)
    mask = (
        jnp.arange(rows)[None, :] // BLOCK_LEN
        <= positions[:, None] // BLOCK_LEN
    )  # [Pb, rows]
    h = c(params["embed"][chunk]).astype(jnp.float32)

    new_k, new_v = cache.k, cache.v
    touched = jnp.int32(0)
    for li, lp in enumerate(params["layers"]):
        with jax.named_scope("attn.block"):
            q, k, v = _qkv(
                _rms(h, lp["norm_in"]), lp, c, cfg, positions, new_k.dtype
            )
            new_k = new_k.at[li, blk, off].set(k)
            new_v = new_v.at[li, blk, off].set(v)
            k_rows = _pages(new_k, li, row).reshape(rows, G, cfg.head_dim)
            v_rows = _pages(new_v, li, row).reshape(rows, G, cfg.head_dim)
            s = jnp.einsum(
                "pgrd,lgd->grpl",
                q.reshape(Pb, G, R, cfg.head_dim).astype(k_rows.dtype),
                k_rows, preferred_element_type=jnp.float32,
            ) * cfg.head_dim**-0.5
            p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), -1)
            a = jnp.einsum(
                "grpl,lgd->pgrd", p.astype(v_rows.dtype), v_rows,
                preferred_element_type=jnp.float32,
            ).reshape(Pb, cfg.n_heads * cfg.head_dim)
            h = h + _mm(a, c(lp["wo"]))
        if li + 1 < cfg.n_layers:
            y, n = _experts(h, lp, c, cfg)
            h = h + y
            touched = touched + n
    return (
        jnp.zeros((cfg.vocab,), jnp.float32),
        PagedKVCache(k=new_k, v=new_v, pos=cache.pos.at[slot].set(length)),
        touched.astype(jnp.float32) * float(expert_bytes(params)),
    )


def paged_decode_step(
    params: dict,
    cache: PagedKVCache,
    table: jax.Array,
    token: jax.Array,
    cfg: SdarConfig = SdarConfig(),
    compute_dtype: Any | None = None,
    *,
    before: jax.Array,
    commit: jax.Array,
    masked: jax.Array,
) -> tuple[jax.Array, PagedKVCache, jax.Array]:
    """One forward of the current block of the first ``w`` slots, each at
    its own ``pos``, with the block before riding in it where it is to be
    committed. ``token`` [w, BLOCK_LEN]: the block as it stands;
    ``masked`` [w, BLOCK_LEN] bool: positions that hold no token yet (the
    mask token's embedding stands there, whatever ``token`` says);
    ``before`` [w, BLOCK_LEN]: the tokens of the block before, whole;
    ``commit`` [w] bool: rows whose block before this forward commits.
    Such a row runs ``2 x BLOCK_LEN`` positions: the block before at
    ``pos .. pos + BLOCK_LEN - 1`` (over the cache and itself: its K/V
    there are what later blocks read), ``pos`` moves on by ``BLOCK_LEN``,
    and the current block stands behind it and sees it. Every other row's
    first ``BLOCK_LEN`` positions are nobody's: their K/V go to trash
    block 0, nothing reads what they compute, and the current block
    stands at ``pos``. Every row writes its current block's K/V at its
    (moved) ``pos`` and attends over ``pos + BLOCK_LEN`` rows; a free slot
    inside the width has a zeroed table row, so all its writes land in
    trash block 0. The last layer runs its queries and its experts over
    the current block alone (the block before is there for its K/V).
    Returns the CURRENT block's logits ``[w, BLOCK_LEN, vocab]`` float32,
    the cache, and the bytes of expert weights the forward had to read
    (touched (layer, expert) pairs x one expert's three matrices,
    float32: exact up to 2**24 pairs)."""
    c = _caster(compute_dtype)
    w, L = token.shape
    block = cache.k.shape[2]
    max_pages = table.shape[1]
    rows = max_pages * block
    G, R = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    tw = table[:w]
    t = cache.pos[:w]
    moved = t + L * commit.astype(jnp.int32)
    #: where each of a row's two blocks starts, and whose positions are
    #: somebody's: the block before only where it is committed
    starts = jnp.repeat(jnp.stack([t, moved], 1), L, axis=1)
    live = jnp.repeat(jnp.stack([commit, jnp.ones_like(commit)], 1), L, axis=1)
    positions = starts + jnp.tile(jnp.arange(L), 2)  # [w, 2L]
    page = jnp.minimum(positions // block, max_pages - 1)
    blk = jnp.where(live, jnp.take_along_axis(tw, page, axis=1), 0)
    off = positions % block
    token = jnp.concatenate(
        [before, jnp.where(masked, cfg.mask_id, token)], 1
    )
    #: all of a position's own block and all before it
    mask = jnp.arange(rows)[None, None, :] < (starts + L)[:, :, None]
    h = c(params["embed"][token]).astype(jnp.float32)  # [w, 2L, d]

    new_k, new_v = cache.k, cache.v
    touched = jnp.int32(0)
    for li, lp in enumerate(params["layers"]):
        with jax.named_scope("attn.block"):
            q, k, v = _qkv(
                _rms(h, lp["norm_in"]), lp, c, cfg, positions, new_k.dtype
            )
            new_k = new_k.at[li, blk, off].set(k)
            new_v = new_v.at[li, blk, off].set(v)
            if li + 1 == cfg.n_layers:
                # nothing reads the block before past its K/V here, and
                # every position that is left is somebody's
                q, h, mask, live = q[:, L:], h[:, L:], mask[:, L:], None
            P = q.shape[1]
            k_rows = _pages(new_k, li, tw).reshape(w, rows, G, cfg.head_dim)
            v_rows = _pages(new_v, li, tw).reshape(w, rows, G, cfg.head_dim)
            s = jnp.einsum(
                "wpgrd,wlgd->wgrpl",
                q.reshape(w, P, G, R, cfg.head_dim).astype(k_rows.dtype),
                k_rows, preferred_element_type=jnp.float32,
            ) * cfg.head_dim**-0.5
            p = jax.nn.softmax(
                jnp.where(mask[:, None, None, :, :], s, -1e30), -1
            )
            a = jnp.einsum(
                "wgrpl,wlgd->wpgrd", p.astype(v_rows.dtype), v_rows,
                preferred_element_type=jnp.float32,
            ).reshape(w, P, cfg.n_heads * cfg.head_dim)
            h = h + _mm(a, c(lp["wo"]))
        y, n = _experts(h, lp, c, cfg, live)
        h = h + y
        touched = touched + n
    with jax.named_scope("lm_head"):
        logits = _mm(_rms(h, params["norm_f"]), c(params["head"]))
    return (
        logits,
        PagedKVCache(k=new_k, v=new_v, pos=cache.pos.at[:w].set(moved)),
        touched.astype(jnp.float32) * float(expert_bytes(params)),
    )
