"""Dense decoder with power retention in every layer (the Brumby block) —
the fifth model family the serving engine hosts, and the first that holds
no keys and values at all.

The Qwen3 dense block with its softmax replaced: a stack of pre-RMSNorm
residual layers, ``h += mixer(rms(h)); h += mlp(rms(h))``, an untied head::

    n = rms(h; norm_in);  q = n Wq [n_heads, head_dim],  k = n Wk,  v = n Wv
        [n_kv_heads, head_dim], no biases;  q, k <- rms over head_dim with a
        learned weight (q_norm, k_norm), then rotary positions on all of
        head_dim, rotate-half, at the absolute position
    log g = log sigmoid(n Wg + bg)             one number a K/V head, float32
    y = power retention of degree 2, scale head_dim^-1/2, each K/V head
        under its n_heads / n_kv_heads query heads
        (:mod:`pygrid_tpu.models.retention` has the recurrence, its feature
        map and its two forms)
    h += y Wo;  m = rms(h; norm_ff);  h += (silu(m W_gate) * (m W_up)) W_down
    logits = rms(h; norm_f) W_head

Parameters are a nested dict BY NAME (``embed``, ``head``, ``norm_f``,
``layers``: a list of per-layer dicts), matrices ``[in, out]``.

**The cache is state alone** (:class:`RetentionCache`): ``k`` and ``v`` are
empty, there is no block pool, a slot's table row is never read, and what
a slot holds is FIXED whatever its context: a float32 matrix ``S [d_v, F
d_k]`` and a normaliser ``z [F, d_k]`` a K/V head and layer (``F =
head_dim / 2 + 1``: 34 MB a layer and slot at heads of 128). ``kv_layers``
is 0: the engine then admits by free slot alone and keeps no page account
(:mod:`pygrid_tpu.serving.engine`).

The state invariant is :mod:`pygrid_tpu.models.jamba`'s:
:func:`paged_prefill_chunk` computes a slot's whole state from zero and
writes all of it at the true prompt length (a padded position has ``k = 0``
and ``log g = 0``: it neither decays the state nor feeds it), so nothing has
to be true of a slot's state before its prefill, and
:func:`paged_decode_step` may leave garbage in every row that no live
request owns. A prompt is always prefilled whole (``start == 0``), and its
prefill answers, after the cache, how many chunks of the recurrence held
the prompt's own positions and how many a layer's scan ran. Both update
the state where it lies: on a TPU the kernels ``retention_chunk``
and ``retention_step`` alias it, so a program never holds a second copy of
a tensor that is a third of the chip.

Precision: weights as stored (bf16 when served), matmuls accumulate in
float32; the residual stream, norms, rotation, gates and the recurrence's
state are float32.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from pygrid_tpu.models import retention
from pygrid_tpu.models.jamba import _caster, _layer, _mm, _rms
from pygrid_tpu.models.sdar_moe import _rope


class BrumbyConfig(NamedTuple):
    vocab: int = 128
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    n_layers: int = 2
    d_ff: int = 128
    #: the served context: positions, not pages (a slot holds no more at
    #: the last than at the first)
    max_len: int = 256
    rope_theta: int = 1_000_000

    @property
    def group(self) -> int:
        """Query heads a K/V head."""
        return self.n_heads // self.n_kv_heads


# ── what the engine asks of a family ─────────────────────────────────────

#: the state cannot be mapped into a second request: no shared prefixes
RECURRENT = True

#: one position a row a forward, one token out of each
BLOCK_LEN = 1


def kv_layers(cfg: BrumbyConfig) -> int:
    """Layers that hold keys and values in the block pool: none."""
    return 0


def kv_heads(cfg: BrumbyConfig) -> int:
    return cfg.n_kv_heads


def kv_kernel(cache_k: jax.Array, max_pages: int) -> bool:
    """No attention over pages, so no kernel over them."""
    return False


def state_bytes_per_slot(cfg: BrumbyConfig, dtype: Any) -> int:
    """Device bytes of one slot's state: ``S`` and ``z``, float32, every
    K/V head of every layer."""
    per_head = retention.rows(cfg.head_dim) * cfg.head_dim * (cfg.head_dim + 1)
    return int(cfg.n_layers * cfg.n_kv_heads * per_head * 4)


def cache_elements(cfg: BrumbyConfig, batch: int) -> int:
    """Cache elements ``batch`` rows hold, whatever their context."""
    return int(batch * state_bytes_per_slot(cfg, jnp.float32) // 4)


class RetentionCache(NamedTuple):
    """``k``/``v``: empty (the engine's cache starts ``k, v, pos``);
    ``pos``: [S] positions a slot has taken in; ``state``/``norm``: every
    layer's ``S`` and ``z`` a slot (:func:`retention.state_shapes`)."""

    k: jax.Array
    v: jax.Array
    pos: jax.Array
    state: jax.Array
    norm: jax.Array


PagedCache = RetentionCache


def init_paged_cache(
    cfg: BrumbyConfig,
    slots: int,
    num_blocks: int,
    block: int,
    dtype: Any = jnp.float32,
) -> RetentionCache:
    empty = (0, num_blocks, block, cfg.n_kv_heads, cfg.head_dim)
    S, z = retention.state_shapes(
        cfg.n_layers, slots, cfg.n_kv_heads, cfg.head_dim, cfg.head_dim
    )
    return RetentionCache(
        k=jnp.zeros(empty, dtype),
        v=jnp.zeros(empty, dtype),
        pos=jnp.zeros((slots,), jnp.int32),
        state=jnp.zeros(S, jnp.float32),
        norm=jnp.zeros(z, jnp.float32),
    )


# ── parameters ───────────────────────────────────────────────────────────

#: the gates' e-folding lengths, positions: ``1 / (1 - sigmoid(bg))``
#: log-uniform between these (some heads forget within a sentence, some
#: hold a prompt's start)
GATE_SPAN = (16.0, 8192.0)
#: ``Wg`` against fan-in scale: the input moves a gate a little
GATE_SCALE = 0.25


def layer_shapes(cfg: BrumbyConfig) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape of one layer's parameters."""
    d, dh, f = cfg.d_model, cfg.head_dim, cfg.d_ff
    kv = cfg.n_kv_heads * dh
    return {
        "norm_in": (d,), "wq": (d, cfg.n_heads * dh), "wk": (d, kv),
        "wv": (d, kv), "q_norm": (dh,), "k_norm": (dh,),
        "wg": (d, cfg.n_kv_heads), "bg": (cfg.n_kv_heads,),
        "wo": (cfg.n_heads * dh, d), "norm_ff": (d,),
        "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
    }


def gate_bias(key: jax.Array, shape) -> jax.Array:
    """``bg`` with e-folding lengths log-uniform over :data:`GATE_SPAN`."""
    lo, hi = jnp.log(GATE_SPAN[0]), jnp.log(GATE_SPAN[1])
    span = jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape))
    return jnp.log(span - 1.0)


def init(key: jax.Array, cfg: BrumbyConfig = BrumbyConfig()) -> dict:
    """Seeded parameters: matrices normal at fan-in scale (the gate's at a
    quarter of it), norms one, the gate's bias by :func:`gate_bias`."""
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
            k = jax.random.fold_in(lkey, j)
            if name == "bg":
                lp[name] = gate_bias(k, shape)
            elif "norm" in name:
                lp[name] = jnp.ones(shape)
            else:
                scale = GATE_SCALE if name == "wg" else 1.0
                lp[name] = jax.random.normal(k, shape) * scale * shape[-2] ** -0.5
        params["layers"].append(lp)
    return params


def bundle(cfg: BrumbyConfig, params: dict) -> dict:
    """Servable bundle for ``host-model`` / ``run-generation``."""
    import numpy as np

    return {
        "family": "brumby",
        "cfg": list(cfg),
        "params": jax.tree.map(np.asarray, params),
    }


def from_bundle(spec: dict) -> tuple[BrumbyConfig, dict]:
    """Inverse of :func:`bundle`; every leaf is checked by name and shape
    against the config."""
    if not isinstance(spec, dict) or spec.get("family") != "brumby":
        raise ValueError("not a generative brumby bundle")
    cfg = BrumbyConfig(*[int(v) for v in spec["cfg"]])
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


# ── the mathematics prefill and decode share ─────────────────────────────


def _inputs(x, lp, c, cfg, positions):
    """What the recurrence takes of the normed input ``x`` [..., d_model]:
    ``q`` [..., G, R, dh], ``k``, ``v`` [..., G, dh] and ``log_g`` [...,
    G], float32, head norms and rotation applied."""
    lead, G, dh = x.shape[:-1], cfg.n_kv_heads, cfg.head_dim
    q = _mm(x, c(lp["wq"])).reshape(*lead, cfg.n_heads, dh)
    k = _mm(x, c(lp["wk"])).reshape(*lead, G, dh)
    v = _mm(x, c(lp["wv"])).reshape(*lead, G, dh)
    q = _rope(_rms(q, lp["q_norm"]), positions, cfg.rope_theta)
    k = _rope(_rms(k, lp["k_norm"]), positions, cfg.rope_theta)
    log_g = jax.nn.log_sigmoid(
        _mm(x, c(lp["wg"])) + lp["bg"].astype(jnp.float32)
    )
    return q.reshape(*lead, G, cfg.group, dh), k, v, log_g


def _retention_seq(x, lp, c, cfg, state, norm, layer, slot, length, start):
    """The mixer over one sequence ``x`` [P, d_model] (normed) through
    ``slot``'s state; positions at and past ``length`` are padding."""
    P = x.shape[0]
    q, k, v, log_g = _inputs(x, lp, c, cfg, start + jnp.arange(P))
    real = jnp.arange(P) < length - start
    k = jnp.where(real[:, None, None], k, 0.0)
    log_g = jnp.where(real[:, None], log_g, 0.0)
    y, state, norm = retention.chunked(
        state, norm, layer, slot, q, k, v, log_g, length - start, start,
        cfg.head_dim**-0.5, mm_dtype=c(lp["wq"]).dtype,
        interpret=jax.default_backend() != "tpu",
    )
    return _mm(y.reshape(P, -1), c(lp["wo"])), state, norm


def _head(h, params, c):
    with jax.named_scope("lm_head"):
        return _mm(_rms(h, params["norm_f"]), c(params["head"]))


# ── serving: prefill and decode through the state ────────────────────────


def paged_prefill_chunk(
    params: dict,
    cache: RetentionCache,
    table: jax.Array,
    slot: jax.Array,
    chunk: jax.Array,
    start: jax.Array,
    length: jax.Array,
    cfg: BrumbyConfig = BrumbyConfig(),
    compute_dtype: Any | None = None,
) -> tuple[jax.Array, RetentionCache, jax.Array]:
    """Prefill of one slot's prompt: ``chunk`` [Pb] padded to a bucket,
    ``length`` its true length (traced: one program a bucket). ``start`` is
    the position of ``chunk[0]``: 0 from the engine (this family shares no
    prefix, so a prompt comes whole and the slot's state starts from
    zero); a later piece of a prompt continues from the state the slot
    holds. ``table`` is the engine's signature's and is not read: there are
    no pages. Returns the logits at position ``length - 1``; the cache with
    the slot's state as of that position and ``pos[slot] = length``; and a
    PAIR of counts (float32 ``[2]``): the chunks of the recurrence that
    held the prompt's own positions, and the chunks a layer's scan ran."""
    del table
    c = _caster(compute_dtype)
    h = c(params["embed"][chunk]).astype(jnp.float32)
    state, norm = cache.state, cache.norm
    for i, lp in enumerate(params["layers"]):

        def mixer(x, i=i, lp=lp):
            nonlocal state, norm
            out, state, norm = _retention_seq(
                x, lp, c, cfg, state, norm, i, slot, length, start
            )
            return out

        h = _layer(h, lp, c, mixer)
    h_last = lax.dynamic_index_in_dim(
        h, length - 1 - start, axis=0, keepdims=False
    )
    ran = retention.chunks_run(
        length - start, chunk.shape[0], cfg.head_dim, cfg.head_dim
    )
    return (
        _head(h_last, params, c),
        cache._replace(
            pos=cache.pos.at[slot].set(length), state=state, norm=norm
        ),
        jnp.stack(ran).astype(jnp.float32),
    )


def paged_decode_step(
    params: dict,
    cache: RetentionCache,
    table: jax.Array,
    token: jax.Array,
    cfg: BrumbyConfig = BrumbyConfig(),
    compute_dtype: Any | None = None,
    active: jax.Array | None = None,
) -> tuple[jax.Array, RetentionCache]:
    """One decode step for the first ``w`` slots, each at its own ``pos``:
    every layer advances the slot's state by one decayed rank-one update
    and reads it. Logits [w, vocab] float32 and the cache.

    ``active`` ([w] bool) freezes rows as in the transformer step: a frozen
    row's ``pos`` stays. Its state is NOT held: it is garbage afterwards,
    which the module's invariant allows, because the engine freezes only
    rows that have all their tokens."""
    del table
    c = _caster(compute_dtype)
    w = token.shape[0]
    t = cache.pos[:w]
    h = c(params["embed"][token]).astype(jnp.float32)
    state, norm = cache.state, cache.norm
    for i, lp in enumerate(params["layers"]):

        def mixer(x, i=i, lp=lp):
            nonlocal state, norm
            y, state, norm = retention.step(
                state, norm, i, *_inputs(x, lp, c, cfg, t),
                cfg.head_dim**-0.5, interpret=jax.default_backend() != "tpu",
            )
            return _mm(y.reshape(w, -1), c(lp["wo"]))

        h = _layer(h, lp, c, mixer)
    advance = (
        active.astype(jnp.int32) if active is not None
        else jnp.ones((w,), jnp.int32)
    )
    return _head(h, params, c), cache._replace(
        pos=cache.pos.at[:w].add(advance), state=state, norm=norm
    )
