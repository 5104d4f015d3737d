"""Hybrid state-space / attention decoder (the Jamba block) — the second
model family the serving engine hosts.

A stack of pre-RMSNorm residual layers, each a *mixer* followed by a gated
SiLU MLP. The mixer of layer ``i`` is causal attention when
``i % attn_period == attn_offset`` (grouped-query: ``n_heads`` query heads
on ``n_kv_heads`` key/value heads, no positional signal of any kind) and a
Mamba-1 selective state-space mixer otherwise (Gu & Dao 2023, with Jamba's
three inner RMS norms on Δ, B and C). The output head is tied to the
embedding.

Parameters are a nested dict BY NAME (``embed``, ``norm_f``, ``layers``: a
list of per-layer dicts), not positions in a flat list; matrices are
``[in, out]``. Two leaves are laid out for the device rather than as
published: ``A_log`` is ``[d_state, d_inner]`` and ``conv_w`` is
``[d_conv, d_inner]`` (``d_inner`` on the 128-lane axis; the published
``[d_inner, d_state]`` would pad 16 to 128 lanes).

Serving keeps TWO kinds of state side by side (:class:`HybridCache`):

- the attention layers' keys and values in the engine's paged block pool,
  ``[n_attn, blocks, block, n_kv_heads, head_dim]`` — same ``BlockPool``,
  block tables and trash block 0 as :mod:`pygrid_tpu.models.decode`;
- the Mamba layers' recurrent state, FIXED per slot and not pageable:
  ``ssm [n_mamba, slots, d_state, d_inner]`` float32 and the convolution's
  last ``d_conv - 1`` inputs ``conv [n_mamba, d_conv - 1, slots, d_inner]``
  in the cache dtype (slots on the sublane axis, so three taps are not
  padded to a tile).

The state invariant, which the engine relies on and
``tests/unit/test_jamba_serving.py`` pins: :func:`paged_prefill_chunk` computes a
slot's whole recurrent state from zero and WRITES ALL OF IT, at the true
prompt length (a padded position has Δ = 0: it neither decays the state
nor feeds it). So nothing has to be true of a slot's state before its
prefill, and :func:`paged_decode_step` may leave garbage in every row it
computes that no live request owns: free slots inside the width, and rows
the fused scan froze because they had their tokens (their K/V writes are
trash-routed and their position parked as in the transformer step; the
state is simply left to run on). A prompt is always prefilled whole
(``start == 0``): the engine shares no prefix pages for this family,
because a shared page would need the recurrent state at its boundary.

Precision: weights as stored (bf16 when served), matmuls accumulate in
float32, the residual stream, norms, Δ, the recurrence and the softmax are
float32. The convolution's input is rounded through the cache dtype
wherever it is produced, so prefill and decode see the same taps.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from pygrid_tpu.models.transformer import _cast

#: the RMS norms' epsilon (the family's published ``rms_norm_eps``); fixed,
#: like the transformer's ``_ln``
RMS_EPS = 1e-6

#: selective-scan steps per loop iteration of a prompt's scan: a prompt of
#: P tokens is P / SCAN_CHUNK dependent device loops a layer, not P
SCAN_CHUNK = 16


class JambaConfig(NamedTuple):
    vocab: int = 128
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 1
    n_layers: int = 4
    d_ff: int = 128
    #: the served context (rows of a slot's block table); the model itself
    #: has no positional table to bound it
    max_len: int = 256
    attn_period: int = 2
    attn_offset: int = 1
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 8

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def is_attn(self, layer: int) -> bool:
        return layer % self.attn_period == self.attn_offset

    @property
    def attn_layers(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layers) if self.is_attn(i))

    @property
    def mamba_layers(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.n_layers) if not self.is_attn(i))


# ── what the engine asks of a family ─────────────────────────────────────

#: a recurrent state rides beside the K/V pool: no shared prefix pages
#: (a second request would need a snapshot of the state at the page edge)
RECURRENT = True

#: one position a row a forward, one token out of each
BLOCK_LEN = 1


def kv_layers(cfg: JambaConfig) -> int:
    """Layers that hold keys and values in the block pool."""
    return len(cfg.attn_layers)


def kv_heads(cfg: JambaConfig) -> int:
    return cfg.n_kv_heads


def kv_kernel(cache_k: jax.Array, max_pages: int) -> bool:
    """Decode attention always gathers: the Pallas kernel wants as many
    cache heads as query heads."""
    return False


def state_bytes_per_slot(cfg: JambaConfig, dtype: Any) -> int:
    """Device bytes of one slot's recurrent state: float32 SSM state and
    the convolution taps in the cache dtype, every Mamba layer."""
    per_layer = cfg.d_inner * (
        cfg.d_state * 4 + (cfg.d_conv - 1) * jnp.dtype(dtype).itemsize
    )
    return int(len(cfg.mamba_layers) * per_layer)


def cache_elements(cfg: JambaConfig, batch: int) -> int:
    """Cache elements ``batch`` rows can hold at the served context."""
    kv = 2 * kv_layers(cfg) * cfg.max_len * cfg.n_kv_heads * cfg.head_dim
    state = len(cfg.mamba_layers) * cfg.d_inner * (
        cfg.d_state + cfg.d_conv - 1
    )
    return int(batch * (kv + state))


class HybridCache(NamedTuple):
    """``k``/``v``: the attention layers' block pool; ``pos``: [S] valid
    rows a slot; ``ssm``/``conv``: the Mamba layers' per-slot state (module
    docstring)."""

    k: jax.Array
    v: jax.Array
    pos: jax.Array
    ssm: jax.Array
    conv: jax.Array


PagedCache = HybridCache


def init_paged_cache(
    cfg: JambaConfig,
    slots: int,
    num_blocks: int,
    block: int,
    dtype: Any = jnp.float32,
) -> HybridCache:
    kv = (len(cfg.attn_layers), num_blocks, block, cfg.n_kv_heads, cfg.head_dim)
    n_mamba = len(cfg.mamba_layers)
    return HybridCache(
        k=jnp.zeros(kv, dtype),
        v=jnp.zeros(kv, dtype),
        pos=jnp.zeros((slots,), jnp.int32),
        ssm=jnp.zeros((n_mamba, slots, cfg.d_state, cfg.d_inner), jnp.float32),
        conv=jnp.zeros((n_mamba, cfg.d_conv - 1, slots, cfg.d_inner), dtype),
    )


# ── parameters ───────────────────────────────────────────────────────────

def layer_shapes(cfg: JambaConfig, layer: int) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape of one layer's parameters."""
    d, f, di = cfg.d_model, cfg.d_ff, cfg.d_inner
    mlp = {"norm_ff": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    if cfg.is_attn(layer):
        kv = cfg.n_kv_heads * cfg.head_dim
        return {
            "norm_in": (d,), "wq": (d, d), "wk": (d, kv), "wv": (d, kv),
            "wo": (d, d), **mlp,
        }
    return {
        "norm_in": (d,), "in_proj": (d, 2 * di), "conv_w": (cfg.d_conv, di),
        "conv_b": (di,), "x_proj": (di, cfg.dt_rank + 2 * cfg.d_state),
        "dt_norm": (cfg.dt_rank,), "b_norm": (cfg.d_state,),
        "c_norm": (cfg.d_state,), "dt_proj": (cfg.dt_rank, di),
        "dt_bias": (di,), "A_log": (cfg.d_state, di), "D": (di,),
        "out_proj": (di, d), **mlp,
    }


def init(key: jax.Array, cfg: JambaConfig = JambaConfig()) -> dict:
    """Seeded parameters: matrices normal at fan-in scale, norms and ``D``
    one, and the state-space leaves as Mamba's published initialisation
    has them (``A = -(1..d_state)``, Δ's bias the inverse softplus of a
    step log-uniform in [0.001, 0.1])."""
    params: dict = {
        "embed": jax.random.normal(
            jax.random.fold_in(key, 0), (cfg.vocab, cfg.d_model)
        ) * cfg.d_model**-0.5,
        "norm_f": jnp.ones((cfg.d_model,)),
        "layers": [],
    }
    for layer in range(cfg.n_layers):
        lkey = jax.random.fold_in(key, layer + 1)
        lp = {}
        for j, (name, shape) in enumerate(layer_shapes(cfg, layer).items()):
            k = jax.random.fold_in(lkey, j)
            if name == "A_log":
                leaf = jnp.broadcast_to(
                    jnp.log(jnp.arange(1.0, cfg.d_state + 1))[:, None], shape
                )
            elif name == "dt_bias":
                dt = jnp.exp(
                    jax.random.uniform(k, shape)
                    * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001)
                )
                leaf = dt + jnp.log(-jnp.expm1(-dt))
            elif name == "D" or "norm" in name:
                leaf = jnp.ones(shape)
            elif name == "conv_b":
                leaf = jnp.zeros(shape)
            else:
                leaf = jax.random.normal(k, shape) * shape[0] ** -0.5
            lp[name] = leaf
        params["layers"].append(lp)
    return params


def bundle(cfg: JambaConfig, params: dict) -> dict:
    """Servable bundle for ``host-model`` / ``run-generation``: the serde
    form of (config, parameters by name)."""
    import numpy as np

    return {
        "family": "jamba",
        "cfg": list(cfg),
        "params": jax.tree.map(np.asarray, params),
    }


def from_bundle(spec: dict) -> tuple[JambaConfig, dict]:
    """Inverse of :func:`bundle`; every layer's leaves are checked by name
    and shape against the config."""
    if not isinstance(spec, dict) or spec.get("family") != "jamba":
        raise ValueError("not a generative jamba bundle")
    cfg = JambaConfig(*[int(v) for v in spec["cfg"]])
    params = jax.tree.map(jnp.asarray, spec["params"])
    layers = params.get("layers") if isinstance(params, dict) else None
    if layers is None or len(layers) != cfg.n_layers:
        raise ValueError(f"bundle's layers do not number {cfg.n_layers}")
    for i, lp in enumerate(layers):
        want = layer_shapes(cfg, i)
        got = {k: tuple(v.shape) for k, v in lp.items()}
        if got != want:
            raise ValueError(f"layer {i}: leaves {got}, config needs {want}")
    for name, shape in (("embed", (cfg.vocab, cfg.d_model)), ("norm_f", (cfg.d_model,))):
        if tuple(params[name].shape) != shape:
            raise ValueError(f"{name}: shape {params[name].shape}, not {shape}")
    return cfg, params


# ── the mathematics both paths share ─────────────────────────────────────


def _rms(x, w):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) * w


def _mm(x, w):
    """``x @ w`` in the weights' dtype, accumulated in float32."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _layer(h, lp, c, mixer):
    """One residual layer with an injected mixer: the ONE copy of the
    per-layer numerics that the full forward, prefill and decode share."""
    h = h + mixer(_rms(h, lp["norm_in"]))
    with jax.named_scope("mlp"):
        x = _rms(h, lp["norm_ff"])
        gated = jax.nn.silu(_mm(x, c(lp["w_gate"]))) * _mm(x, c(lp["w_up"]))
        return h + _mm(gated, c(lp["w_down"]))


def _ssm_inputs(xc, lp, c, cfg):
    """Δ, B, C (float32) from the convolved input ``xc`` [..., d_inner]."""
    dbc = _mm(xc, c(lp["x_proj"]))
    r, n = cfg.dt_rank, cfg.d_state
    delta = _rms(dbc[..., :r], lp["dt_norm"])
    B = _rms(dbc[..., r : r + n], lp["b_norm"])
    C = _rms(dbc[..., r + n :], lp["c_norm"])
    delta = jax.nn.softplus(
        _mm(delta, c(lp["dt_proj"])) + lp["dt_bias"].astype(jnp.float32)
    )
    return delta, B, C


def selective_scan(delta, u, B, C, A, s0):
    """``s_t = exp(Δ_t ⊗ A) ⊙ s_{t-1} + (Δ_t ⊙ u_t) ⊗ B_t``, ``y_t = s_t
    C_t`` over ``P`` positions, float32. ``delta``, ``u``: [P, d_inner];
    ``B``, ``C``: [P, d_state]; ``A``, ``s0``: [d_state, d_inner]. Returns
    (y [P, d_inner], s_P). A position with Δ = 0 leaves the state as it
    was. The loop runs ``SCAN_CHUNK`` positions an iteration."""

    def step(s, xs):
        d, du, b, cc = xs
        s = jnp.exp(d[None, :] * A) * s + b[:, None] * du[None, :]
        return s, jnp.sum(s * cc[:, None], axis=0)

    with jax.named_scope("mamba_scan"):
        s, y = lax.scan(
            step, s0, (delta, delta * u, B, C),
            unroll=min(SCAN_CHUNK, delta.shape[0]),
        )
    return y, s


def _mamba_seq(x, lp, c, cfg, length, taps_dtype):
    """The Mamba mixer over one sequence from a ZERO state. ``x``: [P,
    d_model] (normed); positions at and past ``length`` are padding.
    Returns (out [P, d_model], ssm state and conv taps after position
    ``length - 1``)."""
    P, di, K = x.shape[0], cfg.d_inner, cfg.d_conv
    uz = _mm(x, c(lp["in_proj"]))
    # the taps the decode steps will read are rounded to the cache dtype:
    # round here too, so that prefill and decode convolve the same values
    u = uz[:, :di].astype(taps_dtype)
    z = uz[:, di:]
    u_pad = jnp.concatenate([jnp.zeros((K - 1, di), u.dtype), u])
    conv_w = lp["conv_w"].astype(jnp.float32)
    xc = lp["conv_b"].astype(jnp.float32) + sum(
        u_pad[j : j + P].astype(jnp.float32) * conv_w[j] for j in range(K)
    )
    xc = jax.nn.silu(xc)
    # inputs length-K+1 .. length-1 sit at u_pad[length .. length+K-2]
    taps = lax.dynamic_slice_in_dim(u_pad, length, K - 1, axis=0)
    delta, B, C = _ssm_inputs(xc, lp, c, cfg)
    real = jnp.arange(P) < length
    delta = jnp.where(real[:, None], delta, 0.0)
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    y, s = selective_scan(
        delta, xc, B, C, A, jnp.zeros((cfg.d_state, di), jnp.float32)
    )
    y = y + lp["D"].astype(jnp.float32) * xc
    return _mm(y * jax.nn.silu(z), c(lp["out_proj"])), s, taps


def _attn_seq(x, lp, c, cfg, kv_dtype):
    """Causal grouped-query attention within one sequence. Returns (out
    [P, d_model], k, v [P, n_kv, dh] as the cache holds them)."""
    P, H, G, dh = x.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _mm(x, c(lp["wq"])).reshape(P, G, H // G, dh)
    # round k/v through the CACHE dtype before attending, as the
    # transformer's prefill does: decode reads these rows post-rounding
    k = _mm(x, c(lp["wk"])).reshape(P, G, dh).astype(kv_dtype)
    v = _mm(x, c(lp["wv"])).reshape(P, G, dh).astype(kv_dtype)
    causal = jnp.arange(P)[None, :] <= jnp.arange(P)[:, None]
    s = jnp.einsum(
        "pgrd,lgd->grpl", q.astype(k.dtype), k,
        preferred_element_type=jnp.float32,
    ) * dh**-0.5
    s = jnp.where(causal[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum(
        "grpl,lgd->pgrd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).reshape(P, cfg.d_model)
    return _mm(a, c(lp["wo"])), k, v


def _caster(compute_dtype):
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else None
    return lambda x: _cast(x, cd)


def _head(h, params, c):
    with jax.named_scope("lm_head"):
        return _mm(_rms(h, params["norm_f"]), c(params["embed"]).T)


def apply(
    params: dict,
    tokens: jax.Array,
    cfg: JambaConfig = JambaConfig(),
    compute_dtype: Any | None = None,
) -> jax.Array:
    """Logits [B, T, vocab] for int tokens [B, T]: the full forward, no
    cache (every sequence from a zero state)."""
    c = _caster(compute_dtype)
    T = tokens.shape[1]

    def one(seq):
        h = c(params["embed"][seq]).astype(jnp.float32)
        for i, lp in enumerate(params["layers"]):
            if cfg.is_attn(i):
                mixer = lambda x, lp=lp: _attn_seq(x, lp, c, cfg, jnp.float32)[0]  # noqa: E731
            else:
                mixer = lambda x, lp=lp: _mamba_seq(x, lp, c, cfg, T, jnp.float32)[0]  # noqa: E731
            h = _layer(h, lp, c, mixer)
        return _head(h, params, c)

    return jax.vmap(one)(tokens)


# ── serving: prefill and decode through the hybrid cache ─────────────────


def paged_prefill_chunk(
    params: dict,
    cache: HybridCache,
    table: jax.Array,
    slot: jax.Array,
    chunk: jax.Array,
    start: jax.Array,
    length: jax.Array,
    cfg: JambaConfig = JambaConfig(),
    compute_dtype: Any | None = None,
) -> tuple[jax.Array, HybridCache]:
    """Prefill of one slot's WHOLE prompt: ``chunk`` [Pb] padded to a
    bucket, ``length`` its true length (traced: one program a bucket).
    ``start`` is the transformer signature's shared-prefix length and is 0
    here (module docstring). Returns the logits at position ``length - 1``
    and the cache with the prompt's K/V written through the slot's table
    (pad positions to trash block 0), the slot's whole recurrent state
    written as of position ``length - 1``, and ``pos[slot] = length``."""
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

    new_k, new_v, ssm, conv = cache.k, cache.v, cache.ssm, cache.conv
    ai = mi = 0
    for i, lp in enumerate(params["layers"]):
        if cfg.is_attn(i):

            def mixer(x, lp=lp, ai=ai):
                nonlocal new_k, new_v
                out, k, v = _attn_seq(x, lp, c, cfg, new_k.dtype)
                with jax.named_scope("kv_write"):
                    new_k = new_k.at[ai, blk, off].set(k)
                    new_v = new_v.at[ai, blk, off].set(v)
                return out

            ai += 1
        else:

            def mixer(x, lp=lp, mi=mi):
                nonlocal ssm, conv
                out, s, taps = _mamba_seq(x, lp, c, cfg, length, conv.dtype)
                with jax.named_scope("state_write"):
                    ssm = lax.dynamic_update_slice(
                        ssm, s[None, None], (mi, slot, 0, 0)
                    )
                    conv = lax.dynamic_update_slice(
                        conv, taps[None, :, None], (mi, 0, slot, 0)
                    )
                return out

            mi += 1
        h = _layer(h, lp, c, mixer)
    h_last = lax.dynamic_index_in_dim(h, length - 1, axis=0, keepdims=False)
    return _head(h_last, params, c), HybridCache(
        k=new_k, v=new_v, pos=cache.pos.at[slot].set(length),
        ssm=ssm, conv=conv,
    )


def paged_decode_step(
    params: dict,
    cache: HybridCache,
    table: jax.Array,
    token: jax.Array,
    cfg: JambaConfig = JambaConfig(),
    compute_dtype: Any | None = None,
    active: jax.Array | None = None,
) -> tuple[jax.Array, HybridCache]:
    """One decode step for the first ``w`` slots: each attention layer
    appends a K/V row through the slot's table and attends over the
    gathered table (keys and values broadcast over the query heads of
    their group); each Mamba layer advances the slot's state by one
    recurrence step. Logits [w, vocab] float32.

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

    new_k, new_v, ssm, conv = cache.k, cache.v, cache.ssm, cache.conv
    ai = mi = 0
    for i, lp in enumerate(params["layers"]):
        if cfg.is_attn(i):

            def mixer(x, lp=lp, ai=ai):
                nonlocal new_k, new_v
                q = _mm(x, c(lp["wq"])).reshape(w, G, H // G, dh)
                k = _mm(x, c(lp["wk"])).reshape(w, G, dh)
                v = _mm(x, c(lp["wv"])).reshape(w, G, dh)
                with jax.named_scope("kv_write"):
                    new_k = new_k.at[ai, blk, off].set(k.astype(new_k.dtype))
                    new_v = new_v.at[ai, blk, off].set(v.astype(new_v.dtype))
                with jax.named_scope("attn_gather"):
                    k_rows = new_k[ai][tw].reshape(w, rows, G, dh)
                    v_rows = new_v[ai][tw].reshape(w, rows, G, dh)
                with jax.named_scope("paged_attention"):
                    s = jnp.einsum(
                        "wgrd,wlgd->wgrl", q.astype(k_rows.dtype), k_rows,
                        preferred_element_type=jnp.float32,
                    ) * dh**-0.5
                    s = jnp.where(mask[:, None, None, :], s, -1e30)
                    p = jax.nn.softmax(s, axis=-1)
                    a = jnp.einsum(
                        "wgrl,wlgd->wgrd", p.astype(v_rows.dtype), v_rows,
                        preferred_element_type=jnp.float32,
                    ).reshape(w, cfg.d_model)
                return _mm(a, c(lp["wo"]))

            ai += 1
        else:

            def mixer(x, lp=lp, mi=mi):
                nonlocal ssm, conv
                with jax.named_scope("mamba_step"):
                    di = cfg.d_inner
                    uz = _mm(x, c(lp["in_proj"]))
                    u = uz[:, :di].astype(conv.dtype)
                    z = uz[:, di:]
                    window = jnp.concatenate([conv[mi, :, :w], u[None]])
                    conv_w = lp["conv_w"].astype(jnp.float32)
                    xc = jax.nn.silu(
                        lp["conv_b"].astype(jnp.float32)
                        + jnp.einsum(
                            "kwd,kd->wd", window.astype(jnp.float32), conv_w
                        )
                    )
                    delta, B, C = _ssm_inputs(xc, lp, c, cfg)
                    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
                    s = (
                        jnp.exp(delta[:, None, :] * A[None]) * ssm[mi, :w]
                        + B[:, :, None] * (delta * xc)[:, None, :]
                    )
                    y = jnp.einsum("wnd,wn->wd", s, C)
                    y = y + lp["D"].astype(jnp.float32) * xc
                    ssm = ssm.at[mi, :w].set(s)
                    conv = conv.at[mi, :, :w].set(window[1:])
                    return _mm(y * jax.nn.silu(z), c(lp["out_proj"]))

            mi += 1
        h = _layer(h, lp, c, mixer)
    advance = (
        active.astype(jnp.int32) if active is not None
        else jnp.ones((w,), jnp.int32)
    )
    return _head(h, params, c), HybridCache(
        k=new_k, v=new_v, pos=cache.pos.at[:w].add(advance),
        ssm=ssm, conv=conv,
    )
