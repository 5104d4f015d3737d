"""Hybrid delta-rule / softmax decoder with routed and shared experts (the
Solar-Open2 block) — the fourth model family the serving engine hosts.

A stack of pre-RMSNorm residual layers, ``h += mixer(rms(h)); h +=
experts(rms(h))``, every layer an expert layer, an untied head::

    rms(x; w) = x / sqrt(mean(x^2) + 1e-5) * w

**The mixer** of layer ``i`` is causal softmax attention when ``i %
gqa_period == gqa_offset`` and the gated delta rule otherwise (the
published 1:3).

- *Softmax*: ``n_heads`` query heads on ``n_kv_heads`` key/value heads of
  ``head_dim``, no biases, NO positional signal, scale ``head_dim^-1/2``,
  and an output gate: ``out = [attn * sigmoid(x W_gate)] W_o``.
- *Delta rule* (Kimi Delta Attention; :mod:`pygrid_tpu.models.kda` has the
  recurrence and its two forms), ``n_heads`` heads with ``d_k = d_v =
  head_dim``: ``q, k, v = x W_q, x W_k, x W_v``, each through a causal
  depthwise convolution of ``d_conv`` taps over time (no bias) and SiLU;
  a head's ``q <- q / |q|_2 * d_k^-1/2``, ``k <- k / |k|_2``; the
  log-decay a channel ``g = -exp(A_log_h) * softplus(x W_f_down W_f_up +
  dt_bias)`` (rank ``kda_rank``); ``beta = 2 sigmoid(x W_beta)`` a head
  (the 2 lets a head's transition have a negative eigenvalue); the state
  ``S`` [d_k, d_v] a head, zero at a prompt's start; ``out = [rms_head(o;
  o_norm) * sigmoid(x W_g_down W_g_up)] W_o``, the norm over ``d_v``.

**The experts**: ``p = softmax(x W_r)`` over ALL ``n_experts`` in
float32, the ``top_k`` largest renormalised; ``y = shared(x) + sum_{e in
top-k, held here} p_e expert_e(x)``, each a gated SiLU FFN of
``d_expert``. This chip HOLDS experts ``held_first .. held_first +
held_count - 1`` of the router's ``n_experts`` (its share under expert
parallelism; ``held_count == n_experts``: all of them). What the absent
experts would add is left out and that partial sum goes on to the next
layer: no exchange, and nothing stands in for the other chips
(:func:`pygrid_tpu.models.moe.routed_experts`, ``held``).

Parameters are a nested dict BY NAME (``embed``, ``head``, ``norm_f``,
``layers``: a list of per-layer dicts), matrices ``[in, out]``, a layer's
held experts stacked ``[held_count, ...]``, the convolutions ``[d_conv,
3 * n_heads * head_dim]`` (q's, k's and v's channels side by side on the
lanes).

Serving keeps TWO kinds of state side by side (:class:`DeltaCache`):

- the softmax layers' keys and values in the engine's paged block pool,
  ``[n_gqa, blocks, block, n_kv_heads, head_dim]``;
- the delta-rule layers' state, FIXED per slot and not pageable: ONE
  tensor ``delta [n_kda, slots, n_heads, d_v, d_k]`` float32 (a head's
  matrix held transposed: the decays run along the lanes) and the three
  convolutions' last ``d_conv - 1`` inputs ``conv [n_kda, d_conv - 1,
  slots, 3 * n_heads * head_dim]`` in the cache dtype.

The state invariant is :mod:`pygrid_tpu.models.jamba`'s, and
``tests/unit/test_solar_serving.py`` pins it: :func:`paged_prefill_chunk`
computes a slot's whole state from zero and WRITES ALL OF IT, at the true
prompt length (a padded position has ``g = 0`` and ``beta = 0``: it
neither decays the state nor feeds it), so nothing has to be true of a
slot's state before its prefill, and :func:`paged_decode_step` may leave
garbage in every row that no live request owns. A prompt is always
prefilled whole (``start == 0``): no prefix pages are shared.

A prompt's delta-rule layers run the recurrence in CHUNKS (the Pallas
kernels ``kda_chunk_solver`` and ``kda_chunk_state`` on a TPU), never a
token at a time; its softmax
layers never build ``[heads, P, P]`` scores (the flash kernel of
:mod:`pygrid_tpu.parallel.pallas_attention` on a TPU, K/V heads repeated
to their groups). A decode step gathers whole block tables.

Every forward answers, beside its logits and cache, three counts as the
program made them (float32 ``[3]``): the bytes of held experts' weights
it had to read (touched (layer, expert) pairs x one expert's three
matrices), the assignments its routers made, and those that fell on a
held expert.

Precision: weights as stored (bf16 when served), matmuls accumulate in
float32; the residual stream, norms, decays, the recurrence's state, the
router and the softmax are float32. The convolution's input is rounded
through the cache dtype wherever it is produced, so prefill and decode
see the same taps.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from pygrid_tpu.models import kda, moe
from pygrid_tpu.models.jamba import _caster, _mm
from pygrid_tpu.models.sdar_moe import _pages
from pygrid_tpu.parallel.pallas_attention import flash_attention

#: the RMS norms' epsilon (the family's published ``rms_norm_eps``)
RMS_EPS = 1e-5
#: under the square root of a head's L2 norm of q and of k
L2_EPS = 1e-6


class SolarConfig(NamedTuple):
    vocab: int = 128
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    n_layers: int = 4
    #: the router's outputs: every expert of the layer, wherever it lies
    n_experts: int = 16
    top_k: int = 2
    d_expert: int = 32
    #: the experts this chip holds of each layer
    held_first: int = 0
    held_count: int = 16
    #: the served context (rows of a slot's block table)
    max_len: int = 256
    gqa_period: int = 4
    gqa_offset: int = 0
    #: rank of the decay's and the output gate's projections
    kda_rank: int = 16
    d_conv: int = 4

    def is_gqa(self, layer: int) -> bool:
        return layer % self.gqa_period == self.gqa_offset

    @property
    def gqa_layers(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layers) if self.is_gqa(i))

    @property
    def kda_layers(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layers) if not self.is_gqa(i))

    @property
    def d_heads(self) -> int:
        return self.n_heads * self.head_dim


# ── what the engine asks of a family ─────────────────────────────────────

#: a recurrent state rides beside the K/V pool: no shared prefix pages
RECURRENT = True

#: one position a row a forward, one token out of each
BLOCK_LEN = 1


def kv_layers(cfg: SolarConfig) -> int:
    """Layers that hold keys and values in the block pool."""
    return len(cfg.gqa_layers)


def kv_heads(cfg: SolarConfig) -> int:
    return cfg.n_kv_heads


def kv_kernel(cache_k: jax.Array, max_pages: int) -> bool:
    """Decode attention gathers: the Pallas kernel wants as many cache
    heads as query heads."""
    return False


def state_bytes_per_slot(cfg: SolarConfig, dtype: Any) -> int:
    """Device bytes of one slot's recurrent state: a float32 matrix a
    head and the three convolutions' taps in the cache dtype, every
    delta-rule layer."""
    per_layer = cfg.n_heads * cfg.head_dim * cfg.head_dim * 4 + (
        (cfg.d_conv - 1) * 3 * cfg.d_heads * jnp.dtype(dtype).itemsize
    )
    return int(len(cfg.kda_layers) * per_layer)


def cache_elements(cfg: SolarConfig, batch: int) -> int:
    """Cache elements ``batch`` rows can hold at the served context."""
    kv = 2 * kv_layers(cfg) * cfg.max_len * cfg.n_kv_heads * cfg.head_dim
    state = len(cfg.kda_layers) * cfg.d_heads * (
        cfg.head_dim + 3 * (cfg.d_conv - 1)
    )
    return int(batch * (kv + state))


class DeltaCache(NamedTuple):
    """``k``/``v``: the softmax layers' block pool; ``pos``: [S] valid
    rows a slot; ``delta``/``conv``: the delta-rule layers' per-slot
    state (module docstring)."""

    k: jax.Array
    v: jax.Array
    pos: jax.Array
    delta: jax.Array
    conv: jax.Array


PagedCache = DeltaCache


def init_paged_cache(
    cfg: SolarConfig,
    slots: int,
    num_blocks: int,
    block: int,
    dtype: Any = jnp.float32,
) -> DeltaCache:
    kv = (len(cfg.gqa_layers), num_blocks, block, cfg.n_kv_heads, cfg.head_dim)
    n_kda, dh = len(cfg.kda_layers), cfg.head_dim
    return DeltaCache(
        k=jnp.zeros(kv, dtype),
        v=jnp.zeros(kv, dtype),
        pos=jnp.zeros((slots,), jnp.int32),
        delta=jnp.zeros((n_kda, slots, cfg.n_heads, dh, dh), jnp.float32),
        conv=jnp.zeros((n_kda, cfg.d_conv - 1, slots, 3 * cfg.d_heads), dtype),
    )


# ── parameters ───────────────────────────────────────────────────────────


def layer_shapes(cfg: SolarConfig, layer: int) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape of one layer's parameters."""
    d, hd, f, r = cfg.d_model, cfg.d_heads, cfg.d_expert, cfg.kda_rank
    experts = {
        "norm_ff": (d,), "router": (d, cfg.n_experts),
        "w_gate": (cfg.held_count, d, f), "w_up": (cfg.held_count, d, f),
        "w_down": (cfg.held_count, f, d),
        "s_gate": (d, f), "s_up": (d, f), "s_down": (f, d),
    }
    if cfg.is_gqa(layer):
        kv = cfg.n_kv_heads * cfg.head_dim
        return {
            "norm_in": (d,), "wq": (d, hd), "wk": (d, kv), "wv": (d, kv),
            "wg": (d, hd), "wo": (hd, d), **experts,
        }
    return {
        "norm_in": (d,), "wq": (d, hd), "wk": (d, hd), "wv": (d, hd),
        "conv_w": (cfg.d_conv, 3 * hd), "f_down": (d, r), "f_up": (r, hd),
        "dt_bias": (hd,), "A_log": (cfg.n_heads,), "g_down": (d, r),
        "g_up": (r, hd), "w_beta": (d, cfg.n_heads),
        "o_norm": (cfg.head_dim,), "wo": (hd, d), **experts,
    }


def init(key: jax.Array, cfg: SolarConfig = SolarConfig()) -> dict:
    """Seeded parameters: matrices normal at fan-in scale, norms one, a
    head's ``A = exp(A_log)`` uniform in [1, 16] and ``softplus(dt_bias)``
    log-uniform in [0.001, 0.1] (the delta rule's published
    initialisation: a channel's state lives for tens to thousands of
    positions)."""
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
        for j, (name, shape) in enumerate(layer_shapes(cfg, layer).items()):
            k = jax.random.fold_in(lkey, j)
            if name == "A_log":
                leaf = jnp.log(jax.random.uniform(k, shape, minval=1.0, maxval=16.0))
            elif name == "dt_bias":
                dt = jnp.exp(
                    jax.random.uniform(k, shape)
                    * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001)
                )
                leaf = dt + jnp.log(-jnp.expm1(-dt))
            elif "norm" in name:
                leaf = jnp.ones(shape)
            else:
                leaf = jax.random.normal(k, shape) * shape[-2] ** -0.5
            lp[name] = leaf
        params["layers"].append(lp)
    return params


def bundle(cfg: SolarConfig, params: dict) -> dict:
    """Servable bundle for ``host-model`` / ``run-generation``."""
    import numpy as np

    return {
        "family": "solar_open2",
        "cfg": list(cfg),
        "params": jax.tree.map(np.asarray, params),
    }


def from_bundle(spec: dict) -> tuple[SolarConfig, dict]:
    """Inverse of :func:`bundle`; every leaf is checked by name and shape
    against the config."""
    if not isinstance(spec, dict) or spec.get("family") != "solar_open2":
        raise ValueError("not a generative solar_open2 bundle")
    cfg = SolarConfig(*[int(v) for v in spec["cfg"]])
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
        ("embed", (cfg.vocab, cfg.d_model)), ("head", (cfg.d_model, cfg.vocab)),
        ("norm_f", (cfg.d_model,)),
    ):
        if tuple(params[name].shape) != shape:
            raise ValueError(f"{name}: shape {params[name].shape}, not {shape}")
    return cfg, params


# ── the mathematics prefill and decode share ─────────────────────────────


def _rms(x, w):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) * w


def _unit(x):
    """A head's vector at unit L2 length."""
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def expert_bytes(params: dict) -> int:
    """Bytes of one routed expert's three matrices as stored."""
    lp = params["layers"][0]
    return sum(
        lp[name][0].size * lp[name].dtype.itemsize
        for name in ("w_gate", "w_up", "w_down")
    )


def _experts(h, lp, c, cfg):
    """The expert layer's residual branch over ``h`` [T, d]: the shared
    expert and the held routed ones; and this layer's three counts
    (touched held experts, assignments made, assignments held)."""
    x = _rms(h, lp["norm_ff"])
    held = (
        None if cfg.held_count == cfg.n_experts
        else (cfg.held_first, cfg.held_count)
    )
    y, touched, landed = moe.routed_experts(
        x, lp["router"], c(lp["w_gate"]), c(lp["w_up"]), c(lp["w_down"]),
        cfg.top_k, interpret=jax.default_backend() != "tpu", held=held,
    )
    y = y + moe.shared_expert(x, c(lp["s_gate"]), c(lp["s_up"]), c(lp["s_down"]))
    made = x.shape[0] * cfg.top_k
    return y, jnp.stack([touched, jnp.int32(made), landed]).astype(jnp.float32)


def _counted(counts, params):
    """A forward's three counts as it answers them: touched pairs become
    the bytes of their matrices."""
    scale = jnp.array([float(expert_bytes(params)), 1.0, 1.0], jnp.float32)
    return counts * scale


def _kda_inputs(x, qkv, lp, c, cfg):
    """What the recurrence takes of the convolved ``qkv`` [..., 3 * H *
    dh] and the normed input ``x``: ``q, k, v, g`` [..., H, dh] and
    ``beta`` [..., H], float32."""
    lead, H, dh = x.shape[:-1], cfg.n_heads, cfg.head_dim
    q, k, v = (
        t.reshape(*lead, H, dh) for t in jnp.split(jax.nn.silu(qkv), 3, -1)
    )
    q, k = _unit(q) * dh**-0.5, _unit(k)
    f = _mm(_mm(x, c(lp["f_down"])), c(lp["f_up"]))
    g = -jnp.exp(lp["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        f + lp["dt_bias"].astype(jnp.float32)
    ).reshape(*lead, H, dh)
    beta = 2.0 * jax.nn.sigmoid(_mm(x, c(lp["w_beta"])))
    return q, k, v, g, beta


def _kda_out(o, x, lp, c, cfg):
    """``[rms_head(o) * sigmoid(x W_g_down W_g_up)] W_o``."""
    gate = jax.nn.sigmoid(_mm(_mm(x, c(lp["g_down"])), c(lp["g_up"])))
    o = _rms(o, lp["o_norm"]).reshape(*x.shape[:-1], cfg.d_heads)
    return _mm(o * gate, c(lp["wo"]))


def _kda_seq(x, lp, c, cfg, length, taps_dtype):
    """The delta-rule mixer over one sequence from a ZERO state. ``x``:
    [P, d_model] (normed); positions at and past ``length`` are padding.
    Returns (out [P, d_model], the state [H, d_v, d_k] and the conv taps
    after position ``length - 1``)."""
    P, K = x.shape[0], cfg.d_conv
    u = jnp.concatenate(
        [_mm(x, c(lp[name])) for name in ("wq", "wk", "wv")], -1
    ).astype(taps_dtype)
    u_pad = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    conv_w = lp["conv_w"].astype(jnp.float32)
    qkv = sum(u_pad[j : j + P].astype(jnp.float32) * conv_w[j] for j in range(K))
    # inputs length-K+1 .. length-1 sit at u_pad[length .. length+K-2]
    taps = lax.dynamic_slice_in_dim(u_pad, length, K - 1, axis=0)
    q, k, v, g, beta = _kda_inputs(x, qkv, lp, c, cfg)
    real = jnp.arange(P) < length
    g = jnp.where(real[:, None, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)
    o, state = kda.chunked(
        q, k, v, g, beta, length, mm_dtype=c(lp["wq"]).dtype,
        interpret=jax.default_backend() != "tpu",
    )
    return _kda_out(o, x, lp, c, cfg), state, taps


def flash_eligible(cfg: SolarConfig) -> bool:
    """True where a prompt's softmax attention takes the flash kernel: on
    a TPU, with heads that fill whole 128-lane rows."""
    return jax.default_backend() == "tpu" and cfg.head_dim % 128 == 0


def _gqa_seq(x, lp, c, cfg, kv_dtype, flash: bool | None = None):
    """Causal grouped-query attention within one sequence, gated. Returns
    (out [P, d_model], k, v [P, n_kv, dh] as the cache holds them).
    ``flash`` None: the kernel where :func:`flash_eligible`; the scores
    are built whole everywhere else (the CPU, tier-1's tiny widths)."""
    P, H, G, dh = x.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _mm(x, c(lp["wq"])).reshape(P, G, H // G, dh)
    # k/v rounded through the CACHE dtype before attending: decode reads
    # these rows post-rounding
    k = _mm(x, c(lp["wk"])).reshape(P, G, dh).astype(kv_dtype)
    v = _mm(x, c(lp["wv"])).reshape(P, G, dh).astype(kv_dtype)
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
            "pgrd,lgd->grpl", q.astype(k.dtype), k,
            preferred_element_type=jnp.float32,
        ) * dh**-0.5
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -1e30), axis=-1)
        a = jnp.einsum(
            "grpl,lgd->pgrd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        ).reshape(P, H * dh)
    gate = jax.nn.sigmoid(_mm(x, c(lp["wg"])))
    return _mm(a * gate, c(lp["wo"])), k, v


def _head(h, params, c):
    with jax.named_scope("lm_head"):
        return _mm(_rms(h, params["norm_f"]), c(params["head"]))


def apply(
    params: dict,
    tokens: jax.Array,
    cfg: SolarConfig = SolarConfig(),
    compute_dtype: Any | None = None,
) -> jax.Array:
    """Logits [B, T, vocab] for int tokens [B, T]: the full forward, no
    cache (every sequence from a zero state)."""
    c = _caster(compute_dtype)
    T = tokens.shape[1]

    def one(seq):
        h = c(params["embed"][seq]).astype(jnp.float32)
        for i, lp in enumerate(params["layers"]):
            x = _rms(h, lp["norm_in"])
            if cfg.is_gqa(i):
                h = h + _gqa_seq(x, lp, c, cfg, jnp.float32)[0]
            else:
                h = h + _kda_seq(x, lp, c, cfg, T, jnp.float32)[0]
            h = h + _experts(h, lp, c, cfg)[0]
        return _head(h, params, c)

    # a row at a time: the expert layer's sort is per sequence
    return jnp.stack([one(seq) for seq in tokens])


# ── serving: prefill and decode through the cache ────────────────────────


def paged_prefill_chunk(
    params: dict,
    cache: DeltaCache,
    table: jax.Array,
    slot: jax.Array,
    chunk: jax.Array,
    start: jax.Array,
    length: jax.Array,
    cfg: SolarConfig = SolarConfig(),
    compute_dtype: Any | None = None,
) -> tuple[jax.Array, DeltaCache, jax.Array]:
    """Prefill of one slot's WHOLE prompt: ``chunk`` [Pb] padded to a
    bucket, ``length`` its true length (traced: one program a bucket).
    ``start`` is the transformer signature's shared-prefix length and is 0
    here. Returns the logits at position ``length - 1``; the cache with
    the prompt's K/V written through the slot's table (pad positions to
    trash block 0), the slot's whole recurrent state written as of
    position ``length - 1`` and ``pos[slot] = length``; and the forward's
    three counts (module docstring)."""
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

    new_k, new_v, delta, conv = cache.k, cache.v, cache.delta, cache.conv
    counts = jnp.zeros((3,), jnp.float32)
    ai = mi = 0
    for i, lp in enumerate(params["layers"]):
        x = _rms(h, lp["norm_in"])
        if cfg.is_gqa(i):
            out, k, v = _gqa_seq(x, lp, c, cfg, new_k.dtype)
            with jax.named_scope("kv_write"):
                new_k = new_k.at[ai, blk, off].set(k)
                new_v = new_v.at[ai, blk, off].set(v)
            ai += 1
        else:
            out, state, taps = _kda_seq(x, lp, c, cfg, length, conv.dtype)
            with jax.named_scope("state_write"):
                delta = lax.dynamic_update_slice(
                    delta, state[None, None], (mi, slot, 0, 0, 0)
                )
                conv = lax.dynamic_update_slice(
                    conv, taps[None, :, None], (mi, 0, slot, 0)
                )
            mi += 1
        h = h + out
        y, n = _experts(h, lp, c, cfg)
        h, counts = h + y, counts + n
    h_last = lax.dynamic_index_in_dim(h, length - 1, axis=0, keepdims=False)
    return (
        _head(h_last, params, c),
        DeltaCache(
            k=new_k, v=new_v, pos=cache.pos.at[slot].set(length),
            delta=delta, conv=conv,
        ),
        _counted(counts, params),
    )


def paged_decode_step(
    params: dict,
    cache: DeltaCache,
    table: jax.Array,
    token: jax.Array,
    cfg: SolarConfig = SolarConfig(),
    compute_dtype: Any | None = None,
    active: jax.Array | None = None,
) -> tuple[jax.Array, DeltaCache, jax.Array]:
    """One decode step for the first ``w`` slots: each softmax layer
    appends a K/V row through the slot's table and attends over the
    gathered table; each delta-rule layer advances the slot's state by
    one rank-one update. Logits [w, vocab] float32, the cache, and the
    forward's three counts.

    ``active`` ([w] bool) freezes rows as in the transformer step: a
    frozen row's K/V write goes to trash block 0 and its ``pos`` stays.
    Its recurrent state is NOT held: it is garbage afterwards, which the
    module's invariant allows, because the engine freezes only rows that
    have all their tokens."""
    c = _caster(compute_dtype)
    w = token.shape[0]
    block = cache.k.shape[2]
    max_pages = table.shape[1]
    rows = max_pages * block
    H, G, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t = cache.pos[:w]
    tw = table[:w]
    page = jnp.minimum(t // block, max_pages - 1)
    blk = jnp.take_along_axis(tw, page[:, None], axis=1)[:, 0]
    off = t % block
    if active is not None:
        blk = jnp.where(active, blk, 0)
    mask = jnp.arange(rows)[None, :] <= t[:, None]  # [w, rows]
    h = c(params["embed"][token]).astype(jnp.float32)

    new_k, new_v, delta, conv = cache.k, cache.v, cache.delta, cache.conv
    counts = jnp.zeros((3,), jnp.float32)
    ai = mi = 0
    for i, lp in enumerate(params["layers"]):
        x = _rms(h, lp["norm_in"])
        if cfg.is_gqa(i):
            q = _mm(x, c(lp["wq"])).reshape(w, G, H // G, dh)
            k = _mm(x, c(lp["wk"])).reshape(w, G, dh)
            v = _mm(x, c(lp["wv"])).reshape(w, G, dh)
            with jax.named_scope("kv_write"):
                new_k = new_k.at[ai, blk, off].set(k.astype(new_k.dtype))
                new_v = new_v.at[ai, blk, off].set(v.astype(new_v.dtype))
            with jax.named_scope("attn_gather"):
                k_rows = _pages(new_k, ai, tw).reshape(w, rows, G, dh)
                v_rows = _pages(new_v, ai, tw).reshape(w, rows, G, dh)
            with jax.named_scope("paged_attention"):
                s = jnp.einsum(
                    "wgrd,wlgd->wgrl", q.astype(k_rows.dtype), k_rows,
                    preferred_element_type=jnp.float32,
                ) * dh**-0.5
                p = jax.nn.softmax(
                    jnp.where(mask[:, None, None, :], s, -1e30), axis=-1
                )
                a = jnp.einsum(
                    "wgrl,wlgd->wgrd", p.astype(v_rows.dtype), v_rows,
                    preferred_element_type=jnp.float32,
                ).reshape(w, H * dh)
            out = _mm(a * jax.nn.sigmoid(_mm(x, c(lp["wg"]))), c(lp["wo"]))
            ai += 1
        else:
            u = jnp.concatenate(
                [_mm(x, c(lp[name])) for name in ("wq", "wk", "wv")], -1
            ).astype(conv.dtype)
            window = jnp.concatenate([conv[mi, :, :w], u[None]])
            qkv = jnp.einsum(
                "kwd,kd->wd", window.astype(jnp.float32),
                lp["conv_w"].astype(jnp.float32),
            )
            o, state = kda.step(
                delta[mi, :w], *_kda_inputs(x, qkv, lp, c, cfg)
            )
            delta = delta.at[mi, :w].set(state)
            conv = conv.at[mi, :, :w].set(window[1:])
            out = _kda_out(o, x, lp, c, cfg)
            mi += 1
        h = h + out
        y, n = _experts(h, lp, c, cfg)
        h, counts = h + y, counts + n
    advance = (
        active.astype(jnp.int32) if active is not None
        else jnp.ones((w,), jnp.int32)
    )
    return (
        _head(h, params, c),
        DeltaCache(
            k=new_k, v=new_v, pos=cache.pos.at[:w].add(advance),
            delta=delta, conv=conv,
        ),
        _counted(counts, params),
    )
