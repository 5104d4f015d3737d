"""The adapter of ``"model_type": "jamba"`` (contract: ``models/gpt2.py``'s
docstring and ``spec.MODEL_CONTRACT``): the hybrid state-space / attention
block of the Jamba family, written from the published ``config.json`` and
the family's modelling code, in plain ``jax.numpy``.

Layer ``i`` of ``num_hidden_layers`` is attention iff ``i %
attn_layer_period == attn_layer_offset``, else Mamba; ``num_experts`` is 1,
so every layer's feed-forward is the dense gated MLP::

    h += mixer(rms(h, norm_in));  h += mlp(rms(h, norm_ff))
    rms(x, w) = x / sqrt(mean(x^2) + rms_norm_eps) * w
    mlp(x)    = (silu(x W_gate) * (x W_up)) W_down
    logits    = rms(h, norm_f) E^T                      (tied embedding)

    attention: q = x W_q (num_attention_heads of head_dim), k = x W_k,
      v = x W_v (num_key_value_heads, shared by the query heads of their
      group), no biases, no positional signal of any kind,
      softmax(q k^T / sqrt(head_dim) + causal) v, then W_o

    Mamba (d_inner = mamba_expand * hidden_size, n = mamba_d_state,
      r = mamba_dt_rank, K = mamba_d_conv):
      [u, z] = x W_in
      u_t   <- silu(b_c + sum_{j<K} c_j * u_{t-K+1+j})   depthwise, causal
      [d, B, C] = u W_x  (r, n, n);  d, B, C <- rms(.) each with its weight
      D_t   = softplus(d W_dt + b_dt)
      A     = -exp(A_log)                                [d_inner, n]
      s_t   = exp(D_t (x) A) * s_{t-1} + (D_t * u_t) (x) B_t
      y_t   = s_t C_t + D * u_t
      out   = (y * silu(z)) W_out

The reference (``logits``) computes exactly that, float32 at ``highest``
matmul precision, the recurrence one token at a time in a ``lax.scan``, no
cache, no chunking, and imports nothing of the program. Its weights are the
published shapes (``A_log`` ``[d_inner, n]``, ``conv_w`` ``[d_inner, K]``);
the program holds those two transposed, which ``to_reference`` undoes.

The weights are HELD in the type they are stored in (bf16 for the served
configuration: 6.06 GB) and raised to float32 a layer at a time inside
``logits``: 3.03e9 float32 parameters would be 12.1 GB of a 16 GB chip.

Departures from the published model: none in the mathematics. The weights
are random from ``--seed`` (no checkpoint is read), and the served context
is the deployment's limit on a slot's block table, not
``max_position_embeddings`` (the model has no positional table).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib import reference

MLP_MATRICES = ("w_gate", "w_up", "w_down")
ATTN_MATRICES = ("wq", "wk", "wv", "wo")
MAMBA_MATRICES = ("in_proj", "x_proj", "dt_proj", "out_proj")
MATRICES = MLP_MATRICES + ATTN_MATRICES + MAMBA_MATRICES
#: the two leaves the program holds transposed (``d_inner`` on the lanes)
TRANSPOSED = ("A_log", "conv_w")
#: the keys of the published config the mathematics reads
KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "vocab_size",
    "mamba_d_state", "mamba_d_conv", "mamba_dt_rank", "mamba_expand",
    "attn_layer_period", "attn_layer_offset",
)


def tiny(cfg: dict) -> dict:
    """Two periods of the pattern (attention at ``i % 2 == 1``), four
    query heads on one key/value head, the published ``d_state``."""
    deployment = dict(cfg["deployment"], served_context=128)
    return dict(
        num_hidden_layers=4, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=1, intermediate_size=128, vocab_size=128,
        mamba_d_state=16, mamba_dt_rank=8, attn_layer_period=2,
        attn_layer_offset=1, deployment=deployment,
    )


def _supported(cfg: dict) -> None:
    """What of the family this adapter (and the program) expresses."""
    want = {
        "num_experts": 1, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "tie_word_embeddings": True, "hidden_act": "silu",
        "rms_norm_eps": 1e-6,
    }
    off = {k: cfg.get(k) for k, v in want.items() if cfg.get(k, v) != v}
    if off:
        raise ValueError(f"the jamba adapter does not express {off}")


def is_attn(cfg: dict, layer: int) -> bool:
    return layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


# ── the weights ──────────────────────────────────────────────────────────


def layer_shapes(cfg: dict, attn: bool) -> dict:
    """Leaf -> (shape in the program's layout, scale, mean) of one layer,
    an attention layer's or a Mamba layer's.
    Matrices are ``[in, out]`` at fan-in scale; norms and ``D`` 1 +- 0.05,
    biases +- 0.02: off their neutral values, so that each takes part.
    ``A_log`` and ``dt_bias`` are not drawn this way (``_layer``)."""
    d, f, di = cfg["hidden_size"], cfg["intermediate_size"], d_inner(cfg)
    n, r, K = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    norm = lambda width: ((width,), 0.05, 1.0)  # noqa: E731
    mlp = {
        "norm_ff": norm(d), "w_gate": ((d, f), d**-0.5, 0.0),
        "w_up": ((d, f), d**-0.5, 0.0), "w_down": ((f, d), f**-0.5, 0.0),
    }
    if attn:
        kv = cfg["num_key_value_heads"] * head_dim(cfg)
        return {
            "norm_in": norm(d), "wq": ((d, d), d**-0.5, 0.0),
            "wk": ((d, kv), d**-0.5, 0.0), "wv": ((d, kv), d**-0.5, 0.0),
            "wo": ((d, d), d**-0.5, 0.0), **mlp,
        }
    return {
        "norm_in": norm(d), "in_proj": ((d, 2 * di), d**-0.5, 0.0),
        "conv_w": ((K, di), K**-0.5, 0.0), "conv_b": ((di,), 0.02, 0.0),
        "x_proj": ((di, r + 2 * n), di**-0.5, 0.0), "dt_norm": norm(r),
        "b_norm": norm(n), "c_norm": norm(n),
        # the published initialisation's spread: uniform +- r^-0.5
        "dt_proj": ((r, di), (3 * r) ** -0.5, 0.0),
        "dt_bias": ((di,), None, None), "A_log": ((n, di), None, None),
        "D": ((di,), 0.05, 1.0), "out_proj": ((di, d), di**-0.5, 0.0), **mlp,
    }


@functools.partial(jax.jit, static_argnames=("cfg_items", "attn", "dtype", "program"))
def _layer(key, cfg_items, attn, dtype, program):
    """One layer's leaves from its key. A call a layer: one call for all
    3.03e9 values kept 21.7 GB of float32 draws alive at once, by the TPU
    compiler's own count."""
    cfg, dtype, out = dict(cfg_items), jnp.dtype(dtype), {}
    for j, (name, (shape, scale, mean)) in enumerate(layer_shapes(cfg, attn).items()):
        k = jax.random.fold_in(key, j)
        if name == "A_log":
            # Mamba's published initialisation: A = -(1 .. n), every channel
            n = cfg["mamba_d_state"]
            leaf = jnp.broadcast_to(jnp.log(jnp.arange(1.0, n + 1))[:, None], shape)
        elif name == "dt_bias":
            # ... and the step: softplus(dt_bias) log-uniform in [0.001, 0.1],
            # so a state lives for hundreds of tokens
            lo, hi = np.log(0.001), np.log(0.1)
            dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(k, shape))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        else:
            leaf = mean + scale * jax.random.normal(k, shape, jnp.float32)
        if name in TRANSPOSED and not program:
            leaf = leaf.T  # the published layout
        out[name] = leaf.astype(dtype)
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _globals(key, cfg_items, dtype):
    cfg, dtype = dict(cfg_items), jnp.dtype(dtype)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed": (d**-0.5 * jax.random.normal(
            jax.random.fold_in(key, 0), (V, d), jnp.float32)).astype(dtype),
        "norm_f": (1.0 + 0.05 * jax.random.normal(
            jax.random.fold_in(key, 1), (d,), jnp.float32)).astype(dtype),
    }


def _make(key, cfg: dict, dtype: str, program: bool) -> dict:
    items = _items(cfg)
    return dict(
        _globals(key, items, dtype),
        layers=[
            _layer(jax.random.fold_in(key, 2 + i), items, is_attn(cfg, i), dtype, program)
            for i in range(cfg["num_hidden_layers"])
        ],
    )


def _items(cfg: dict):
    _supported(cfg)
    return tuple((k, int(cfg[k])) for k in KEYS)


def make_weights(seed: int, cfg: dict, dtype: str) -> dict:
    """The reference's weights, held in ``dtype`` (module docstring): the
    program's values in the published layout. A jitted call a layer."""
    return _make(reference.seed_key(seed), cfg, dtype, False)


def make_program_params(seed: int, cfg: dict, dtype: str) -> dict:
    """The same values as the program takes them: parameters by name
    (``embed``, ``norm_f``, ``layers``), in ``dtype``. A jitted call a
    layer, all enqueued at once. A program without this family (the
    parent of the PR that added it) is refused here, before 6 GB are
    drawn for a node that cannot host them."""
    import importlib.util

    if importlib.util.find_spec("pygrid_tpu.models.jamba") is None:
        raise RuntimeError(
            "the program beside this benchmark has no pygrid_tpu.models.jamba: "
            "it cannot host model_type 'jamba'"
        )
    return _make(reference.seed_key(seed), cfg, dtype, True)


def to_reference(params: dict, cfg: dict) -> dict:
    """The program's parameters in the published layout, values untouched
    (``logits`` raises them to float32 a layer at a time)."""
    return {
        "embed": params["embed"], "norm_f": params["norm_f"],
        "layers": [
            {k: v.T if k in TRANSPOSED else v for k, v in lp.items()}
            for lp in params["layers"]
        ],
    }


# ── the mathematics ──────────────────────────────────────────────────────


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _attention(x, lw, cfg):
    B, T, _ = x.shape
    H, G, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    q = (x @ lw["wq"]).reshape(B, T, G, H // G, dh)
    k = (x @ lw["wk"]).reshape(B, T, G, dh)
    v = (x @ lw["wv"]).reshape(B, T, G, dh)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) * dh**-0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, jnp.finfo(s.dtype).min)
    a = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, -1), v)
    return a.reshape(B, T, H * dh) @ lw["wo"]


def _mamba(x, lw, cfg):
    B, T, _ = x.shape
    di, n, r = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    K, eps = cfg["mamba_d_conv"], cfg["rms_norm_eps"]
    uz = x @ lw["in_proj"]
    u, z = uz[..., :di], uz[..., di:]
    u_pad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = lw["conv_b"] + sum(
        u_pad[:, j : j + T] * lw["conv_w"][:, j] for j in range(K)
    )
    u = jax.nn.silu(u)
    dbc = u @ lw["x_proj"]
    delta = _rms(dbc[..., :r], lw["dt_norm"], eps)
    Bm = _rms(dbc[..., r : r + n], lw["b_norm"], eps)
    Cm = _rms(dbc[..., r + n :], lw["c_norm"], eps)
    delta = jax.nn.softplus(delta @ lw["dt_proj"] + lw["dt_bias"])
    A = -jnp.exp(lw["A_log"])  # [d_inner, n]

    def step(s, xs):  # one token: s [B, d_inner, n]
        d_t, u_t, b_t, c_t = xs
        s = jnp.exp(d_t[..., None] * A) * s + (d_t * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("bdn,bn->bd", s, c_t)

    time_major = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    _, y = jax.lax.scan(
        step, jnp.zeros((B, di, n), x.dtype),
        tuple(map(time_major, (delta, u, Bm, Cm))),
    )
    y = time_major(y) + lw["D"] * u
    return (y * jax.nn.silu(z)) @ lw["out_proj"]


def hidden(w: dict, tokens, cfg: dict, dtype=jnp.float32):
    """Final hidden states ``[B, T, hidden_size]`` after the last norm.
    ``dtype`` is float32 for the reference; a control may pass a lower
    one, and every operation then runs and rounds in it. A layer's weights
    are raised to ``dtype`` where the layer uses them."""
    eps = cfg["rms_norm_eps"]
    c = lambda x: x.astype(dtype)  # noqa: E731
    h = c(w["embed"][tokens])
    for i, lw in enumerate(w["layers"]):
        mixer = _attention if is_attn(cfg, i) else _mamba

        lw = jax.tree.map(c, lw)
        h = h + mixer(_rms(h, lw["norm_in"], eps), lw, cfg)
        x = _rms(h, lw["norm_ff"], eps)
        h = h + (jax.nn.silu(x @ lw["w_gate"]) * (x @ lw["w_up"])) @ lw["w_down"]
    return _rms(h, c(w["norm_f"]), eps)


def logits(w: dict, tokens, cfg: dict, dtype=jnp.float32):
    """``[B, T, vocab]`` next-token logits (tied head)."""
    with jax.default_matmul_precision("highest"):
        return hidden(w, tokens, cfg, dtype) @ w["embed"].astype(dtype).T


def served_gaps(w, cfg: dict, request: dict, answer: dict, pad_to: int, control=None):
    """A causal decoder, one token a row a step: the shifted-by-one
    comparison of ``lib/reference.served_gaps`` on ``logits``."""
    return reference.served_gaps(
        logits, w, cfg, request["prompt"], answer["tokens"], pad_to, control
    )


# ── the controls ─────────────────────────────────────────────────────────


def leaf_norms(w: dict) -> dict:
    """L2 norm of every leaf, by its path (no cell trains this
    configuration; the contract asks for the function)."""
    flat = jax.tree_util.tree_flatten_with_path(w)[0]
    return reference.leaf_norms(
        {jax.tree_util.keystr(path): leaf for path, leaf in flat}, ()
    )


def _int8(x, axis: int):
    """``reference.int8_values`` computed in float32, held in ``x``'s type
    (the control keeps the reference's 6 GB, not 12)."""
    return reference.int8_values(x.astype(jnp.float32), axis).astype(x.dtype)


def control_weights(w: dict) -> dict:
    """The serving control's weights: every matrix and the embedding
    rounded to int8 with one scale per output channel (per row for the
    embedding). Norms, biases, the convolution, ``A_log`` and ``D`` stay."""
    return {
        "embed": _int8(w["embed"], -1), "norm_f": w["norm_f"],
        "layers": [
            {k: _int8(v, -2) if k in MATRICES else v for k, v in lw.items()}
            for lw in w["layers"]
        ],
    }


# ── the program ──────────────────────────────────────────────────────────


def jamba_config(cfg: dict):
    from pygrid_tpu.models.jamba import JambaConfig

    _supported(cfg)
    return JambaConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_len=cfg["deployment"]["served_context"],
        attn_period=cfg["attn_layer_period"],
        attn_offset=cfg["attn_layer_offset"], d_state=cfg["mamba_d_state"],
        d_conv=cfg["mamba_d_conv"], expand=cfg["mamba_expand"],
        dt_rank=cfg["mamba_dt_rank"],
    )


def hosted(cfg: dict, params) -> tuple:
    """What the node's ``HostedModel`` takes: the bundle's ``model`` and the
    parsed form (``generation_cache``) the first request would otherwise
    make from an uploaded blob."""
    jcfg = jamba_config(cfg)
    return {"family": "jamba", "cfg": list(jcfg)}, (jcfg, params)


def train_loss(cell: dict, interpret: bool):
    raise NotImplementedError(
        "no cell trains the jamba configuration: float32 parameters alone "
        "are 12.1 GB of one chip's 16"
    )


# ── the counts ───────────────────────────────────────────────────────────


def layer_params(cfg: dict, layer: int) -> int:
    shapes = layer_shapes(cfg, is_attn(cfg, layer))
    return sum(int(np.prod(s)) for s, _, _ in shapes.values())


def param_count(cfg: dict) -> int:
    """Every parameter, the tied embedding once."""
    layers = sum(layer_params(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return layers + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def matmul_params(cfg: dict) -> int:
    """Parameters a token passes through a matrix multiplication with: a
    layer's matrices and the tied output head."""
    total = cfg["vocab_size"] * cfg["hidden_size"]
    for i in range(cfg["num_hidden_layers"]):
        shapes = layer_shapes(cfg, is_attn(cfg, i))
        total += sum(
            int(np.prod(s)) for name, (s, _, _) in shapes.items() if name in MATRICES
        )
    return total


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations forward and backward for one token of a ``seq_len``-token
    sequence: 6 per matmul parameter; attention ``12 * hidden_size`` per
    attended position (``(seq_len + 1) / 2`` on average) in each attention
    layer; the recurrence 9 per element of a Mamba layer's state forward
    (the decay's product, exponential and multiply, the input's two
    products and the add, the read-out's multiply-add) and twice that
    backward."""
    n_mamba = cfg["num_hidden_layers"] - n_attn(cfg)
    attn = n_attn(cfg) * 12.0 * cfg["hidden_size"] * (seq_len + 1) / 2.0
    scan = n_mamba * 27.0 * d_inner(cfg) * cfg["mamba_d_state"]
    return 6.0 * matmul_params(cfg) + attn + scan


def n_attn(cfg: dict) -> int:
    return sum(is_attn(cfg, i) for i in range(cfg["num_hidden_layers"]))


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return 2 * n_attn(cfg) * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def state_bytes_per_slot(cfg: dict, taps_itemsize: int = 2) -> int:
    """A slot's recurrent state: float32 ``[d_inner, n]`` and ``K - 1``
    convolution taps of ``d_inner``, every Mamba layer."""
    per = d_inner(cfg) * (
        cfg["mamba_d_state"] * 4 + (cfg["mamba_d_conv"] - 1) * taps_itemsize
    )
    return (cfg["num_hidden_layers"] - n_attn(cfg)) * per


def decode_step_bytes(cfg: dict, live_rows: int, kv_tokens: int,
                      weights_itemsize: int = 2, cache_itemsize: int = 2) -> int:
    """Bytes one decode step has to move through HBM: every weight once
    (the tied embedding as the output head), each live row's recurrent
    state read and written, and the ``kv_tokens`` cached positions the
    live rows attend over. Activations stay on the chip."""
    return (
        param_count(cfg) * weights_itemsize
        + 2 * live_rows * state_bytes_per_slot(cfg, cache_itemsize)
        + kv_tokens * kv_bytes_per_token(cfg, cache_itemsize)
    )
