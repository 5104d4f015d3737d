"""Hybrid of gated short convolutions and grouped-query attention with a
dense layer ahead of its expert layers (the LFM2-MoE block) — the sixth
model family the serving engine hosts.

A stack of pre-RMSNorm residual layers, ``h += mixer(rms(h)); h +=
ffn(rms(h))``, the head tied to the embedding::

    rms(x; w) = x / sqrt(mean(x^2) + 1e-5) * w

**The mixer** of layer ``i`` is causal softmax attention where bit ``i``
of ``cfg.attn_layers`` is set and a double-gated short convolution
everywhere else (published: one attention layer in four).

- *Short convolution*: ``[B, C, X] = x W_in`` (``W_in`` [d, 3d], split in
  that order, no bias); ``u = B * X``; ``c_t = sum_j w_j u_{t - (K-1) +
  j}`` a channel, a causal depthwise convolution of ``d_conv`` = K taps,
  zeros before the prompt, no bias and NO activation; ``out = (C * c)
  W_out``. No attention and no recurrence beyond the ``K - 1`` taps: the
  state a slot keeps is ``u_{t-K+1} .. u_{t-1}``.
- *Attention*: ``n_heads`` query heads on ``n_kv_heads`` key/value heads
  of ``head_dim = d_model / n_heads``, no biases; ``q`` and ``k`` through
  an RMS norm over a head with a learned weight; rotary positions on all
  of a head, rotate-half, at theta ``rope_theta``; scale
  ``head_dim^-1/2``.

**The FFN** of the first ``n_dense`` layers is a gated SiLU MLP of
``d_ff``; every other layer's is ``n_experts`` routed experts of
``d_expert``, ``top_k`` a token, no shared expert: ``s = sigmoid(x W_r)``
over all of them in float32, the chosen are the ``top_k`` largest of ``s
+ expert_bias``, their weights ``s`` WITHOUT the bias over ``(their sum +
1e-6)`` (:func:`pygrid_tpu.models.moe.routed_experts`, ``sigmoid``; the
family's ``routed_scaling_factor`` is 1 and nothing multiplies by it).

Parameters are a nested dict BY NAME (``embed``, ``norm_f``, ``layers``:
a list of per-layer dicts), matrices ``[in, out]``, a layer's experts
stacked ``[n_experts, ...]``, the convolution ``[d_conv, d_model]`` (a
tap's channels along the lanes).

Serving keeps TWO kinds of state side by side (:class:`ConvCache`):

- the attention layers' keys and values in the engine's paged block pool,
  keys after their norm and rotation, a position's heads SIDE BY SIDE on
  the lanes: ``[n_attn, blocks, block, n_kv_heads * head_dim]``. With the
  heads on an axis of their own a head of 64 fills half a 128-lane row,
  and at the published widths XLA then copied the whole pool into another
  layout before every write and gather (eight copies of 1.2 GB a decode
  step, 6.1 GB of temporaries: the compiled step said so before any chip
  did); 8 x 64 = 512 lanes are whole rows, and the pool is written and
  gathered where it lies;
- the convolutions' taps, FIXED per slot and not pageable: ONE tensor
  ``conv [n_conv, d_conv - 1, slots, d_model]`` in the cache dtype: 8 KB a
  layer and slot at the published width, the smallest state the cache
  manager holds beside pages.

The state invariant is :mod:`pygrid_tpu.models.jamba`'s, and
``tests/unit/test_lfm2_serving.py`` pins it: :func:`paged_prefill_chunk`
computes a slot's taps from the prompt alone and WRITES ALL OF THEM, as of
the last ``d_conv - 1`` TRUE positions of a padded bucket (zeros where the
prompt is shorter), so nothing has to be true of a slot's state before its
prefill, and :func:`paged_decode_step` may leave garbage in every row that
no live request owns. A prompt is always prefilled whole (``start == 0``):
no prefix pages are shared.

A prompt's convolutions are ``d_conv`` shifted multiplies that XLA fuses
(three taps are no kernel's worth); its attention layers never build
``[heads, P, P]`` scores on a TPU (the flash kernel of
:mod:`pygrid_tpu.parallel.pallas_attention`, two 64-wide heads a lane
tile, K/V heads repeated to their groups). A decode step gathers whole
block tables.

Every forward answers, beside its logits and cache, three counts as the
program made them (float32 ``[3]``): the bytes of experts' weights it had
to read (touched (layer, expert) pairs x one expert's three matrices), the
assignments its routers made, and those that fell on a held expert (every
one: a chip holds all of a layer's experts). The dense layers count in
none of them.

Precision: weights as stored (bf16 when served), matmuls accumulate in
float32; the residual stream, norms, rotation, router and softmax are
float32. The convolution's input ``u`` is rounded through the cache dtype
wherever it is produced, so prefill and decode see the same taps.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from pygrid_tpu.models import moe
from pygrid_tpu.models.jamba import _caster, _mm
from pygrid_tpu.models.sdar_moe import _pages, _rope
from pygrid_tpu.parallel.pallas_attention import flash_attention

#: the RMS norms' epsilon (the family's published ``norm_eps``)
RMS_EPS = 1e-5


class Lfm2Config(NamedTuple):
    vocab: int = 128
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    n_layers: int = 5
    #: bit ``i`` set: layer ``i``'s mixer is attention (a convolution
    #: where it is not). Default: conv, attention, conv, conv, conv
    attn_layers: int = 0b00010
    #: the leading layers whose FFN is a dense MLP of ``d_ff``
    n_dense: int = 1
    d_ff: int = 96
    n_experts: int = 8
    top_k: int = 2
    d_expert: int = 32
    #: taps of the short convolution (the family's ``conv_L_cache``)
    d_conv: int = 3
    #: the served context (rows of a slot's block table)
    max_len: int = 256
    rope_theta: int = 1_000_000

    def is_attn(self, layer: int) -> bool:
        return bool(self.attn_layers >> layer & 1)

    def is_dense(self, layer: int) -> bool:
        return layer < self.n_dense

    @property
    def attn_ids(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layers) if self.is_attn(i))

    @property
    def conv_ids(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layers) if not self.is_attn(i))

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


# ── what the engine asks of a family ─────────────────────────────────────

#: a recurrent state rides beside the K/V pool: no shared prefix pages
RECURRENT = True

#: one position a row a forward, one token out of each
BLOCK_LEN = 1


def kv_layers(cfg: Lfm2Config) -> int:
    """Layers that hold keys and values in the block pool."""
    return len(cfg.attn_ids)


def kv_heads(cfg: Lfm2Config) -> int:
    return cfg.n_kv_heads


def kv_kernel(cache_k: jax.Array, max_pages: int) -> bool:
    """Whether decode attention over this pool reads live pages in place:
    the wide kernel's rule (a TPU, a pool it tiles)."""
    from pygrid_tpu.serving import paged_attention

    return paged_attention.eligible_wide(cache_k, max_pages)


def state_bytes_per_slot(cfg: Lfm2Config, dtype: Any) -> int:
    """Device bytes of one slot's recurrent state: the convolution's last
    ``d_conv - 1`` inputs in the cache dtype, every conv layer."""
    taps = (cfg.d_conv - 1) * cfg.d_model * jnp.dtype(dtype).itemsize
    return int(len(cfg.conv_ids) * taps)


def cache_elements(cfg: Lfm2Config, batch: int) -> int:
    """Cache elements ``batch`` rows can hold at the served context."""
    kv = 2 * kv_layers(cfg) * cfg.max_len * cfg.n_kv_heads * cfg.head_dim
    state = len(cfg.conv_ids) * (cfg.d_conv - 1) * cfg.d_model
    return int(batch * (kv + state))


class ConvCache(NamedTuple):
    """``k``/``v``: the attention layers' block pool; ``pos``: [S] valid
    rows a slot; ``conv``: the conv layers' per-slot taps (module
    docstring)."""

    k: jax.Array
    v: jax.Array
    pos: jax.Array
    conv: jax.Array


PagedCache = ConvCache


def init_paged_cache(
    cfg: Lfm2Config,
    slots: int,
    num_blocks: int,
    block: int,
    dtype: Any = jnp.float32,
) -> ConvCache:
    kv = (len(cfg.attn_ids), num_blocks, block, cfg.n_kv_heads * cfg.head_dim)
    return ConvCache(
        k=jnp.zeros(kv, dtype),
        v=jnp.zeros(kv, dtype),
        pos=jnp.zeros((slots,), jnp.int32),
        conv=jnp.zeros(
            (len(cfg.conv_ids), cfg.d_conv - 1, slots, cfg.d_model), dtype
        ),
    )


# ── parameters ───────────────────────────────────────────────────────────


def layer_shapes(cfg: Lfm2Config, layer: int) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape of one layer's parameters."""
    d, dh = cfg.d_model, cfg.head_dim
    if cfg.is_attn(layer):
        kv = cfg.n_kv_heads * dh
        mixer = {
            "norm_in": (d,), "wq": (d, d), "wk": (d, kv), "wv": (d, kv),
            "q_norm": (dh,), "k_norm": (dh,), "wo": (d, d),
        }
    else:
        mixer = {
            "norm_in": (d,), "w_in": (d, 3 * d), "conv_w": (cfg.d_conv, d),
            "w_out": (d, d),
        }
    if cfg.is_dense(layer):
        f = cfg.d_ff
        ffn = {"norm_ff": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    else:
        E, f = cfg.n_experts, cfg.d_expert
        ffn = {
            "norm_ff": (d,), "router": (d, E), "expert_bias": (E,),
            "w_gate": (E, d, f), "w_up": (E, d, f), "w_down": (E, f, d),
        }
    return {**mixer, **ffn}


def init(key: jax.Array, cfg: Lfm2Config = Lfm2Config()) -> dict:
    """Seeded parameters: matrices normal at fan-in scale, norms one, the
    taps at ``d_conv^-1/2`` and ``expert_bias`` at a scale that changes
    some tokens' choice (a trained model's balances its load)."""
    d = cfg.d_model
    params: dict = {
        "embed": jax.random.normal(
            jax.random.fold_in(key, 0), (cfg.vocab, d)
        ) * d**-0.5,
        "norm_f": jnp.ones((d,)),
        "layers": [],
    }
    for layer in range(cfg.n_layers):
        lkey = jax.random.fold_in(key, layer + 2)
        lp = {}
        for j, (name, shape) in enumerate(layer_shapes(cfg, layer).items()):
            k = jax.random.fold_in(lkey, j)
            if "norm" in name:
                leaf = jnp.ones(shape)
            elif name == "expert_bias":
                leaf = 0.05 * jax.random.normal(k, shape)
            else:
                leaf = jax.random.normal(k, shape) * shape[-2] ** -0.5
            lp[name] = leaf
        params["layers"].append(lp)
    return params


def bundle(cfg: Lfm2Config, params: dict) -> dict:
    """Servable bundle for ``host-model`` / ``run-generation``."""
    import numpy as np

    return {
        "family": "lfm2_moe",
        "cfg": list(cfg),
        "params": jax.tree.map(np.asarray, params),
    }


def from_bundle(spec: dict) -> tuple[Lfm2Config, dict]:
    """Inverse of :func:`bundle`; every leaf is checked by name and shape
    against the config."""
    if not isinstance(spec, dict) or spec.get("family") != "lfm2_moe":
        raise ValueError("not a generative lfm2_moe bundle")
    cfg = Lfm2Config(*[int(v) for v in spec["cfg"]])
    params = jax.tree.map(jnp.asarray, spec["params"])
    layers = params.get("layers") if isinstance(params, dict) else None
    if layers is None or len(layers) != cfg.n_layers:
        raise ValueError(f"bundle's layers do not number {cfg.n_layers}")
    for i, lp in enumerate(layers):
        want = layer_shapes(cfg, i)
        got = {k: tuple(v.shape) for k, v in lp.items()}
        if got != want:
            raise ValueError(f"layer {i}: leaves {got}, config needs {want}")
    for name, shape in (
        ("embed", (cfg.vocab, cfg.d_model)), ("norm_f", (cfg.d_model,)),
    ):
        if tuple(params[name].shape) != shape:
            raise ValueError(f"{name}: shape {params[name].shape}, not {shape}")
    return cfg, params


# ── the mathematics prefill and decode share ─────────────────────────────


def _rms(x, w):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) * w


def expert_bytes(params: dict, cfg: Lfm2Config) -> int:
    """Bytes of one routed expert's three matrices as stored (nought for a
    stack that is dense layers alone)."""
    if cfg.n_dense >= cfg.n_layers:
        return 0
    lp = params["layers"][cfg.n_dense]
    return sum(
        lp[name][0].size * lp[name].dtype.itemsize
        for name in ("w_gate", "w_up", "w_down")
    )


def _ffn(h, lp, c, cfg, layer):
    """A layer's second residual branch over ``h`` [T, d]: the dense MLP
    or the routed experts; and the layer's three counts (touched experts,
    assignments made, assignments held: nought all three for a dense
    layer)."""
    x = _rms(h, lp["norm_ff"])
    if cfg.is_dense(layer):
        with jax.named_scope("mlp"):
            gated = jax.nn.silu(_mm(x, c(lp["w_gate"]))) * _mm(x, c(lp["w_up"]))
            return _mm(gated, c(lp["w_down"])), jnp.zeros((3,), jnp.float32)
    y, touched, landed = moe.routed_experts(
        x, lp["router"], c(lp["w_gate"]), c(lp["w_up"]), c(lp["w_down"]),
        cfg.top_k, interpret=jax.default_backend() != "tpu", sigmoid=True,
        bias=lp["expert_bias"],
    )
    made = x.shape[0] * cfg.top_k
    return y, jnp.stack([touched, jnp.int32(made), landed]).astype(jnp.float32)


def _counted(counts, params, cfg):
    """A forward's three counts as it answers them: touched pairs become
    the bytes of their matrices."""
    scale = jnp.array([float(expert_bytes(params, cfg)), 1.0, 1.0], jnp.float32)
    return counts * scale


def _qkv(x, lp, c, cfg, positions, kv_dtype):
    """q [..., n_heads, dh] float32 and k, v [..., n_kv_heads, dh] as the
    cache holds them, head norms and rotation applied."""
    lead, dh = x.shape[:-1], cfg.head_dim
    q = _mm(x, c(lp["wq"])).reshape(*lead, cfg.n_heads, dh)
    k = _mm(x, c(lp["wk"])).reshape(*lead, cfg.n_kv_heads, dh)
    v = _mm(x, c(lp["wv"])).reshape(*lead, cfg.n_kv_heads, dh)
    q = _rope(_rms(q, lp["q_norm"]), positions, cfg.rope_theta)
    k = _rope(_rms(k, lp["k_norm"]), positions, cfg.rope_theta)
    return q, k.astype(kv_dtype), v.astype(kv_dtype)


def flash_eligible(cfg: Lfm2Config) -> bool:
    """True where a prompt's attention takes the flash kernel: on a TPU,
    with heads that fill a 128-lane row alone or in pairs."""
    return jax.default_backend() == "tpu" and cfg.head_dim % 64 == 0


def _attn_seq(x, lp, c, cfg, kv_dtype, flash: bool | None = None):
    """Causal grouped-query attention within one sequence from position
    0. Returns (out [P, d_model], k, v [P, n_kv, dh] as the cache holds
    them). ``flash`` None: the kernel where :func:`flash_eligible`; the
    scores are built whole everywhere else (the CPU, tier-1's tiny
    widths)."""
    P, H, G, dh = x.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # k/v rounded through the CACHE dtype before attending: decode reads
    # these rows post-rounding
    q, k, v = _qkv(x, lp, c, cfg, jnp.arange(P), kv_dtype)
    if flash_eligible(cfg) if flash is None else flash:
        with jax.named_scope("attn.flash"):
            groups = lambda t: jnp.broadcast_to(  # noqa: E731
                t[:, :, None], (P, G, H // G, dh)
            ).reshape(1, P, H, dh)
            a = flash_attention(
                q.reshape(1, P, H, dh).astype(k.dtype), groups(k), groups(v),
                causal=True, scale=dh**-0.5,
                interpret=jax.default_backend() != "tpu",
                precision=(
                    lax.Precision.HIGHEST if k.dtype == jnp.float32 else None
                ),
            ).reshape(P, H * dh).astype(jnp.float32)
    else:
        causal = jnp.arange(P)[None, :] <= jnp.arange(P)[:, None]
        s = jnp.einsum(
            "pgrd,lgd->grpl", q.reshape(P, G, H // G, dh).astype(k.dtype), k,
            preferred_element_type=jnp.float32,
        ) * dh**-0.5
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -1e30), axis=-1)
        a = jnp.einsum(
            "grpl,lgd->pgrd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        ).reshape(P, H * dh)
    return _mm(a, c(lp["wo"])), k, v


def _widen(q, G):
    """``q`` [w, H, dh] with each query head laid on its K/V head's ``dh``
    lanes of a ``G * dh`` row, zeros on the others: [w, H, G * dh]."""
    w, H, dh = q.shape
    own = jnp.eye(G, dtype=q.dtype)  # [G, G]: a head's own lanes
    return jnp.einsum(
        "wgrd,gh->wgrhd", q.reshape(w, G, H // G, dh), own
    ).reshape(w, H, G * dh)


def _narrow(a_wide, G):
    """A head's own ``dh`` lanes out of each ``G * dh`` row of ``a_wide``
    [w, H, G * dh]: [w, H * dh]."""
    w, H, C = a_wide.shape
    own = jnp.eye(G, dtype=a_wide.dtype)
    return jnp.einsum(
        "wgrhd,gh->wgrd", a_wide.reshape(w, G, H // G, G, C // G), own
    ).reshape(w, H * C // G)


def _attend_wide(q, k_rows, v_rows, mask, G):
    """Decode attention over gathered rows that hold a position's ``G``
    K/V heads side by side on the lanes. ``q`` [w, H, dh] float32;
    ``k_rows``, ``v_rows`` [w, rows, G * dh]; ``mask`` [w, rows]. Returns
    [w, H * dh] float32.

    A query head is laid on its K/V head's ``dh`` lanes of a ``G * dh``
    row, zeros on the others, so that the scores and the read-out are
    products over whole rows as they were gathered: ``G`` times the
    multiplications (zeros times another head's keys), which a decode
    step does not feel, and no relayout of the rows, which it does:
    splitting the gathered ``[w, rows, G * dh]`` into heads of 64 was a
    copy of every row a layer, a sixth of the device's time at the
    published widths (PERF.md §6, PR 47)."""
    dh = q.shape[-1]
    s = jnp.einsum(
        "whc,wlc->whl", _widen(q, G).astype(k_rows.dtype), k_rows,
        preferred_element_type=jnp.float32,
    ) * dh**-0.5
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, -1e30), axis=-1)
    a_wide = jnp.einsum(
        "whl,wlc->whc", p.astype(v_rows.dtype), v_rows,
        preferred_element_type=jnp.float32,
    )
    return _narrow(a_wide, G)


def _conv_gates(x, lp, c, taps_dtype):
    """What the convolution takes of the normed input ``x`` [..., d]: its
    input ``u = B * X`` as the cache holds it, and the gate ``C``."""
    B, C, X = jnp.split(_mm(x, c(lp["w_in"])), 3, -1)
    return (B * X).astype(taps_dtype), C


def _conv_seq(x, lp, c, cfg, length, taps_dtype):
    """The short-convolution mixer over one sequence from position 0.
    ``x``: [P, d_model] (normed); positions at and past ``length`` are
    padding. Returns (out [P, d_model], the taps [d_conv - 1, d_model]
    after position ``length - 1``)."""
    P, K = x.shape[0], cfg.d_conv
    with jax.named_scope("shortconv"):
        u, C = _conv_gates(x, lp, c, taps_dtype)
        u_pad = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
        conv_w = lp["conv_w"].astype(jnp.float32)
        conv = sum(
            u_pad[j : j + P].astype(jnp.float32) * conv_w[j] for j in range(K)
        )
        # inputs length-K+1 .. length-1 sit at u_pad[length .. length+K-2]
        # (zeros where the prompt is shorter than the taps)
        taps = lax.dynamic_slice_in_dim(u_pad, length, K - 1, axis=0)
        return _mm(C * conv, c(lp["w_out"])), taps


def _head(h, params, c):
    with jax.named_scope("lm_head"):
        return _mm(_rms(h, params["norm_f"]), c(params["embed"]).T)


def apply(
    params: dict,
    tokens: jax.Array,
    cfg: Lfm2Config = Lfm2Config(),
    compute_dtype: Any | None = None,
) -> jax.Array:
    """Logits [B, T, vocab] for int tokens [B, T]: the full forward, no
    cache (every sequence from position 0 and zero taps)."""
    c = _caster(compute_dtype)
    T = tokens.shape[1]

    def one(seq):
        h = c(params["embed"][seq]).astype(jnp.float32)
        for i, lp in enumerate(params["layers"]):
            x = _rms(h, lp["norm_in"])
            if cfg.is_attn(i):
                h = h + _attn_seq(x, lp, c, cfg, jnp.float32)[0]
            else:
                h = h + _conv_seq(x, lp, c, cfg, T, jnp.float32)[0]
            h = h + _ffn(h, lp, c, cfg, i)[0]
        return _head(h, params, c)

    # a row at a time: the expert layer's sort is per sequence
    return jnp.stack([one(seq) for seq in tokens])


# ── serving: prefill and decode through the cache ────────────────────────


def paged_prefill_chunk(
    params: dict,
    cache: ConvCache,
    table: jax.Array,
    slot: jax.Array,
    chunk: jax.Array,
    start: jax.Array,
    length: jax.Array,
    cfg: Lfm2Config = Lfm2Config(),
    compute_dtype: Any | None = None,
) -> tuple[jax.Array, ConvCache, jax.Array]:
    """Prefill of one slot's WHOLE prompt: ``chunk`` [Pb] padded to a
    bucket, ``length`` its true length (traced: one program a bucket).
    ``start`` is the transformer signature's shared-prefix length and is 0
    here. Returns the logits at position ``length - 1``; the cache with
    the prompt's K/V written through the slot's table (pad positions to
    trash block 0), the slot's taps written as of position ``length - 1``
    and ``pos[slot] = length``; and the forward's three counts (module
    docstring)."""
    del start  # always 0: this family shares no prefix pages
    c = _caster(compute_dtype)
    Pb = chunk.shape[0]
    block = cache.k.shape[2]
    max_pages = table.shape[1]
    positions = jnp.arange(Pb)
    real = positions < length
    page = jnp.minimum(positions // block, max_pages - 1)
    blk = jnp.where(real, table[slot][page], 0)
    off = jnp.where(real, positions % block, 0)
    h = c(params["embed"][chunk]).astype(jnp.float32)

    new_k, new_v, conv = cache.k, cache.v, cache.conv
    counts = jnp.zeros((3,), jnp.float32)
    ai = mi = 0
    for i, lp in enumerate(params["layers"]):
        x = _rms(h, lp["norm_in"])
        if cfg.is_attn(i):
            out, k, v = _attn_seq(x, lp, c, cfg, new_k.dtype)
            with jax.named_scope("kv_write"):
                new_k = new_k.at[ai, blk, off].set(k.reshape(Pb, -1))
                new_v = new_v.at[ai, blk, off].set(v.reshape(Pb, -1))
            ai += 1
        else:
            out, taps = _conv_seq(x, lp, c, cfg, length, conv.dtype)
            with jax.named_scope("state_write"):
                conv = lax.dynamic_update_slice(
                    conv, taps[None, :, None], (mi, 0, slot, 0)
                )
            mi += 1
        h = h + out
        y, n = _ffn(h, lp, c, cfg, i)
        h, counts = h + y, counts + n
    h_last = lax.dynamic_index_in_dim(h, length - 1, axis=0, keepdims=False)
    return (
        _head(h_last, params, c),
        ConvCache(
            k=new_k, v=new_v, pos=cache.pos.at[slot].set(length), conv=conv,
        ),
        _counted(counts, params, cfg),
    )


def paged_decode_step(
    params: dict,
    cache: ConvCache,
    table: jax.Array,
    token: jax.Array,
    cfg: Lfm2Config = Lfm2Config(),
    compute_dtype: Any | None = None,
    active: jax.Array | None = None,
) -> tuple[jax.Array, ConvCache, jax.Array]:
    """One decode step for the first ``w`` slots: each attention layer
    appends a K/V row through the slot's table and attends over the
    slot's rows; each conv layer convolves the slot's taps with the
    step's input and shifts them by one. Logits [w, vocab] float32, the
    cache, and the forward's three counts.

    Attention takes one of two paths, by one rule read when the program
    is traced (:func:`kv_kernel`): on a TPU, over a pool it tiles, a
    Pallas kernel reads each slot's live pages in place, several pages a
    matmul; everywhere else the slot's whole table is gathered and
    masked. The same mathematics either way (:func:`_attend_wide`).

    ``active`` ([w] bool) freezes rows as in the transformer step: a
    frozen row's K/V write goes to trash block 0 and its ``pos`` stays.
    Its taps are NOT held: they are garbage afterwards, which the module's
    invariant allows, because the engine freezes only rows that have all
    their tokens."""
    c = _caster(compute_dtype)
    w = token.shape[0]
    block = cache.k.shape[2]
    max_pages = table.shape[1]
    rows = max_pages * block
    G, dh = cfg.n_kv_heads, cfg.head_dim
    t = cache.pos[:w]
    tw = table[:w]
    page = jnp.minimum(t // block, max_pages - 1)
    blk = jnp.take_along_axis(tw, page[:, None], axis=1)[:, 0]
    off = t % block
    if active is not None:
        blk = jnp.where(active, blk, 0)
    use_kernel = kv_kernel(cache.k, max_pages)
    if use_kernel:
        from pygrid_tpu.serving import paged_attention

        #: rows the kernel attends over: ``l <= t``. A free slot inside
        #: the width (zeroed table row: block 0 is never a live row's
        #: page) has a stale, growing ``pos``; it reads one page of
        #: trash, not the table
        lengths = jnp.where(tw[:, 0] != 0, jnp.minimum(t + 1, rows), 1)
    else:
        mask = jnp.arange(rows)[None, :] <= t[:, None]  # [w, rows]
    h = c(params["embed"][token]).astype(jnp.float32)

    new_k, new_v, conv = cache.k, cache.v, cache.conv
    counts = jnp.zeros((3,), jnp.float32)
    ai = mi = 0
    for i, lp in enumerate(params["layers"]):
        x = _rms(h, lp["norm_in"])
        if cfg.is_attn(i):
            q, k, v = _qkv(x, lp, c, cfg, t, new_k.dtype)
            with jax.named_scope("kv_write"):
                new_k = new_k.at[ai, blk, off].set(k.reshape(w, -1))
                new_v = new_v.at[ai, blk, off].set(v.reshape(w, -1))
            if use_kernel:
                with jax.named_scope("paged_attention"):
                    # off the TPU only a test that patched ``kv_kernel``
                    # gets here: the same kernel, interpreted
                    a = _narrow(
                        paged_attention.paged_decode_attention_wide(
                            _widen(q, G), new_k, new_v, jnp.int32(ai), tw,
                            lengths, scale=dh**-0.5,
                            interpret=jax.default_backend() != "tpu",
                        ),
                        G,
                    )
            else:
                with jax.named_scope("attn_gather"):
                    k_rows = _pages(new_k, ai, tw).reshape(w, rows, G * dh)
                    v_rows = _pages(new_v, ai, tw).reshape(w, rows, G * dh)
                with jax.named_scope("paged_attention"):
                    a = _attend_wide(q, k_rows, v_rows, mask, G)
            out = _mm(a, c(lp["wo"]))
            ai += 1
        else:
            with jax.named_scope("shortconv_step"):
                u, C = _conv_gates(x, lp, c, conv.dtype)
                window = jnp.concatenate([conv[mi, :, :w], u[None]])
                mixed = jnp.einsum(
                    "kwd,kd->wd", window.astype(jnp.float32),
                    lp["conv_w"].astype(jnp.float32),
                )
                out = _mm(C * mixed, c(lp["w_out"]))
            with jax.named_scope("state_write"):
                conv = conv.at[mi, :, :w].set(window[1:])
            mi += 1
        h = h + out
        y, n = _ffn(h, lp, c, cfg, i)
        h, counts = h + y, counts + n
    advance = (
        active.astype(jnp.int32) if active is not None
        else jnp.ones((w,), jnp.int32)
    )
    return (
        _head(h, params, c),
        ConvCache(
            k=new_k, v=new_v, pos=cache.pos.at[:w].add(advance), conv=conv,
        ),
        _counted(counts, params, cfg),
    )
