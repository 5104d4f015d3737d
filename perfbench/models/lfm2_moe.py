"""The adapter of ``"model_type": "lfm2_moe"`` (contract: ``models/gpt2.py``'s
docstring and ``spec.MODEL_CONTRACT``): the hybrid of gated short
convolutions and grouped-query attention with a dense layer ahead of its
expert layers, of the LFM2-MoE family, written from the published
``config.json`` in plain ``jax.numpy``.

Pre-norm residual, a tied head::

    h += mixer(rms(h; operator_norm));  h += ffn(rms(h; ffn_norm))
    rms(x; w) = x / sqrt(mean(x^2) + norm_eps) * w
    logits    = rms(h; embedding_norm) W_embed^T

    conv mixer (layer_types[i] == "conv"):
      [B, C, X] = x W_in          (W_in [hidden, 3 hidden], split in that
                                   order, conv_bias false: no bias)
      u = B * X
      c_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t     a channel: a causal
        depthwise convolution of conv_L_cache = 3 taps, zeros before the
        prompt, no bias, NO activation
      out = (C * c) W_out

    attention mixer (layer_types[i] == "full_attention"):
      q, k, v = x W_q, x W_k, x W_v -> num_attention_heads,
        num_key_value_heads, num_key_value_heads heads of head_dim, no
        biases;  q <- rms(q; q_layernorm), k <- rms(k; k_layernorm), a
        head over its head_dim;  rotary over the whole head, rotate-half,
        theta rope_parameters.rope_theta;  softmax(q k^T / sqrt(head_dim)
        + causal) v, a K/V head under its group of query heads;  W_out

    ffn, layer i < num_dense_layers:  W_2 (silu(x W_1) * (x W_3)) of
      intermediate_size
    ffn, every other layer:  s = sigmoid(x W_r) over all num_experts in
      float32;  the num_experts_per_tok chosen are the largest of s +
      expert_bias (use_expert_bias true);  their weights are s, WITHOUT
      the bias, over (their sum + 1e-6) (norm_topk_prob true), times
      routed_scaling_factor;  y = sum_e p_e W_2,e (silu(x W_1,e) * (x
      W_3,e)) of moe_intermediate_size;  no drops, no capacity, no shared
      expert

The reference (``logits``) computes exactly the above, float32 at
``highest`` matmul precision, attention in blocks of queries (a block's
scores, not the whole ``[heads, T, T]``), no cache, no kernel, and imports
nothing of the program. An expert runs over the positions routed to it and
no others, gathered ``ROUND`` at a time for as many rounds as there are
(every expert over all 4,608 positions of a replay would be sixteen times
the work). The weights are HELD in the type they are stored in (bf16 for
the served configuration: 10.36 GB) and raised to float32 a layer at a
time, an expert's matrices where the loop over the experts reaches it. The
int8 control is therefore no second copy: ``control_weights`` wraps the
same arrays, and a matrix is rounded where it is raised.

The program holds one leaf laid out for the device: the convolution as
``conv_w [taps, hidden]`` (the reference: ``conv`` as published,
``[hidden, taps]``); ``to_reference`` undoes it.

Departures from the published model: none in the mathematics; what the
config has no key for is under ``assumed`` in the configuration file. The
weights are random from ``--seed`` and the served context is the
deployment's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from lib import reference

EXPERT_MATRICES = ("w_gate", "w_up", "w_down")
MATRICES = ("w_in", "w_out", "wq", "wk", "wv", "wo", "router") + EXPERT_MATRICES
#: the keys of the published config the mathematics reads (numbers)
KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "vocab_size", "intermediate_size",
    "moe_intermediate_size", "num_experts", "num_experts_per_tok",
    "num_dense_layers", "conv_L_cache",
)
#: under the sum the chosen scores are normalised by (the family's
#: published code; the config has no key)
TOPK_EPS = 1e-6
#: the standard deviation ``expert_bias`` is drawn at (the configuration
#: file's ``assumed.expert_bias`` says why)
EXPERT_BIAS_SCALE = 0.0125


def tiny(cfg: dict) -> dict:
    """The first five of the nine layers held (the dense conv layer, an
    attention layer, three conv layers with experts), four heads of 16 on
    two K/V heads, 8 experts of 32, two a token."""
    return dict(
        num_hidden_layers=5, layer_types=list(cfg["layer_types"][:5]),
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=128, intermediate_size=96, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=2,
        deployment=dict(cfg["deployment"], served_context=128),
    )


def _supported(cfg: dict) -> None:
    """What of the family this adapter (and the program) expresses."""
    want = {
        "conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
        "norm_eps": 1e-5, "routed_scaling_factor": 1,
    }
    off = {k: cfg.get(k) for k, v in want.items() if cfg.get(k, v) != v}
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"] or set(types) - {"conv", "full_attention"}:
        off["layer_types"] = types
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        off["num_attention_heads"] = cfg["num_attention_heads"]
    if off:
        raise ValueError(f"the lfm2_moe adapter does not express {off}")


def head_dim(cfg: dict) -> int:
    """The family's rule (the published config gives no ``head_dim``)."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def is_attention(cfg: dict, layer: int) -> bool:
    return cfg["layer_types"][layer] == "full_attention"


def is_dense(cfg: dict, layer: int) -> bool:
    """A gated MLP and no experts: the layers below ``num_dense_layers``."""
    return layer < cfg["num_dense_layers"]


def n_attention(cfg: dict) -> int:
    return sum(is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))


def n_conv(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - n_attention(cfg)


def n_expert_layers(cfg: dict) -> int:
    return sum(not is_dense(cfg, i) for i in range(cfg["num_hidden_layers"]))


def rope_theta(cfg: dict) -> float:
    return float(cfg["rope_parameters"]["rope_theta"])


# ── the weights ──────────────────────────────────────────────────────────


def layer_shapes(cfg: dict, attention: bool, dense: bool) -> dict:
    """Leaf -> (shape, scale, mean) of one layer in the PROGRAM's layout.
    Matrices ``[in, out]`` at fan-in scale (a layer's experts stacked on
    axis 0), norms 1 +- 0.05, the convolution's taps at ``taps^-1/2``. The
    router and ``expert_bias`` are drawn in ``_layer``: the router's
    columns come in PAIRS ``w, -w`` (experts ``2i`` and ``2i + 1``), so
    that the set of the experts' handicaps is symmetric and alike in shape
    for every seed (``models/solar_open2.py``, PERF.md §6, PR 36), and the
    bias at ``EXPERT_BIAS_SCALE``."""
    d, dh = cfg["hidden_size"], head_dim(cfg)
    H, G, K = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["conv_L_cache"]
    norm = lambda width: ((width,), 0.05, 1.0)  # noqa: E731
    mat = lambda *shape: (shape, shape[-2] ** -0.5, 0.0)  # noqa: E731
    if attention:
        mixer = {
            "norm_in": norm(d), "wq": mat(d, H * dh), "wk": mat(d, G * dh),
            "wv": mat(d, G * dh), "q_norm": norm(dh), "k_norm": norm(dh),
            "wo": mat(H * dh, d),
        }
    else:
        mixer = {
            "norm_in": norm(d), "w_in": mat(d, 3 * d),
            "conv_w": ((K, d), K**-0.5, 0.0), "w_out": mat(d, d),
        }
    if dense:
        f = cfg["intermediate_size"]
        ffn = {"norm_ff": norm(d), "w_gate": mat(d, f), "w_up": mat(d, f), "w_down": mat(f, d)}
    else:
        E, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        ffn = {
            "norm_ff": norm(d), "router": mat(d, E), "expert_bias": ((E,), None, None),
            "w_gate": mat(E, d, f), "w_up": mat(E, d, f), "w_down": mat(E, f, d),
        }
    return {**mixer, **ffn}


@functools.partial(jax.jit, static_argnames=("cfg_items", "attention", "dense", "dtype"))
def _layer(key, cfg_items, attention, dense, dtype):
    """One layer's leaves from its key, a jitted call a layer (all 5.2e9
    values in one call would hold their float32 draws alive at once)."""
    cfg, out = dict(cfg_items), {}
    for j, (name, (shape, scale, mean)) in enumerate(
        layer_shapes(cfg, attention, dense).items()
    ):
        k = jax.random.fold_in(key, j)
        if name == "router":
            # columns in pairs w, -w (``layer_shapes``)
            half = scale * jax.random.normal(k, (shape[0], shape[1] // 2), jnp.float32)
            leaf = jnp.stack([half, -half], -1).reshape(shape)
        elif name == "expert_bias":
            leaf = EXPERT_BIAS_SCALE * jax.random.normal(k, shape, jnp.float32)
        else:
            leaf = mean + scale * jax.random.normal(k, shape, jnp.float32)
        out[name] = leaf.astype(dtype)
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _globals(key, cfg_items, dtype):
    cfg = dict(cfg_items)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    draw = lambda i, shape: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape, jnp.float32
    )
    return {
        "embed": (d**-0.5 * draw(0, (V, d))).astype(dtype),
        "norm_f": (1.0 + 0.05 * draw(2, (d,))).astype(dtype),
    }


def _items(cfg: dict):
    """The numbers ``layer_shapes`` reads, hashable (a jitted draw's static
    argument)."""
    _supported(cfg)
    return tuple((k, int(cfg[k])) for k in KEYS)


def make_program_params(seed: int, cfg: dict, dtype: str) -> dict:
    """The weights as the program takes them: parameters by name, in
    ``dtype``, a jitted call a layer. A program without this family (the
    parent of the PR that added it) is refused here, before 10.4 GB are
    drawn for a node that cannot host them."""
    import importlib.util

    if importlib.util.find_spec("pygrid_tpu.models.lfm2_moe") is None:
        raise RuntimeError(
            "the program beside this benchmark has no pygrid_tpu.models."
            "lfm2_moe: it cannot host model_type 'lfm2_moe'"
        )
    return _make(seed, cfg, dtype)


def _make(seed: int, cfg: dict, dtype: str) -> dict:
    key, items = reference.seed_key(seed), _items(cfg)
    return dict(
        _globals(key, items, dtype),
        layers=[
            _layer(
                jax.random.fold_in(key, 3 + i), items, is_attention(cfg, i),
                is_dense(cfg, i), dtype,
            )
            for i in range(cfg["num_hidden_layers"])
        ],
    )


def make_weights(seed: int, cfg: dict, dtype: str) -> dict:
    """The reference's weights, held in ``dtype``: the program's values in
    the published layout."""
    return to_reference(_make(seed, cfg, dtype), cfg)


def to_reference(params: dict, cfg: dict) -> dict:
    """The program's parameters in the published layout, values untouched:
    the convolution ``[channels, taps]``."""

    def layer(lp):
        lp = dict(lp)
        if "conv_w" in lp:
            lp["conv"] = lp.pop("conv_w").T
        return lp

    return dict(params, layers=[layer(lp) for lp in params["layers"]])


# ── the mathematics ──────────────────────────────────────────────────────


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _matrix(x, dtype, int8: bool):
    """A stored matrix in ``dtype``; the control's rounded to int8 with
    one scale per output channel first."""
    if int8:
        x = reference.int8_values(x.astype(jnp.float32), -2)
    return x.astype(dtype)


def _rope(x, theta: float):
    """Rotary positions over the whole of the last axis, rotate-half; ``x``
    [T, heads, dh] at positions 0 .. T - 1."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=x.dtype) / half)
    angle = jnp.arange(T, dtype=x.dtype)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _query_block(T: int) -> int:
    """Queries a block of the softmax attention: the scores held at once
    are ``[heads, block, T]``."""
    return next((b for b in (256, 128, 64, 32) if T % b == 0 and T > b), T)


def _attention(x, lw, cfg):
    """Causal grouped-query attention over one sequence ``x`` [T, d], a
    block of queries at a time."""
    T, eps = x.shape[0], cfg["norm_eps"]
    H, G, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    q = _rms((x @ lw["wq"]).reshape(T, H, dh), lw["q_norm"], eps)
    k = _rms((x @ lw["wk"]).reshape(T, G, dh), lw["k_norm"], eps)
    q = _rope(q, rope_theta(cfg)).reshape(T, G, H // G, dh)
    k = _rope(k, rope_theta(cfg))
    v = (x @ lw["wv"]).reshape(T, G, dh)
    qb = _query_block(T)

    def block(args):
        qs, first = args
        s = jnp.einsum("qgrd,kgd->grqk", qs, k) * dh**-0.5
        seen = jnp.arange(T)[None, :] <= first + jnp.arange(qb)[:, None]
        s = jnp.where(seen, s, jnp.finfo(s.dtype).min)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1), v)

    a = jax.lax.map(
        block, (q.reshape(T // qb, qb, G, H // G, dh), jnp.arange(0, T, qb))
    ).reshape(T, H * dh)
    return a @ lw["wo"]


def _short_conv(x, lw, cfg):
    """The double-gated short convolution over one sequence ``x`` [T, d]:
    ``(C * conv(B * X)) W_out``, zeros before the sequence, no
    activation."""
    T, K = x.shape[0], lw["conv"].shape[1]
    B, C, X = jnp.split(x @ lw["w_in"], 3, -1)
    u_pad = jnp.pad(B * X, ((K - 1, 0), (0, 0)))
    c = sum(u_pad[j : j + T] * lw["conv"][:, j] for j in range(K))
    return (C * c) @ lw["w_out"]


def route(m, router, bias, cfg: dict):
    """The router over ``m`` [T, d]: the chosen experts ``idx`` [T, k] (the
    largest of ``sigmoid(m W_r) + bias``) and their weights ``p`` [T, k]
    (the scores WITHOUT the bias over their sum + 1e-6, times
    ``routed_scaling_factor``). ``bias`` None: a router that ignores it."""
    s = jax.nn.sigmoid(m @ router)
    _, idx = jax.lax.top_k(s if bias is None else s + bias, cfg["num_experts_per_tok"])
    p = jnp.take_along_axis(s, idx, -1)
    p = p / (p.sum(-1, keepdims=True) + TOPK_EPS) * cfg["routed_scaling_factor"]
    return idx, p


#: positions an expert takes a round: it runs over the positions routed to
#: it, this many gathered at a time, for as many rounds as that takes
ROUND = 512


def _experts(m, lw, cfg, int8: bool):
    """``sum over the experts a position chose``: one expert at a time (its
    three matrices raised to ``m``'s type there) over the positions routed
    to it, ``ROUND`` of them a round until none is left (an expert nobody
    chose takes no round; the padding behind a sequence, which all goes one
    way, takes several)."""
    T = m.shape[0]
    idx, p = route(m, lw["router"], lw["expert_bias"], cfg)
    weight = jnp.zeros((T, cfg["num_experts"]), m.dtype).at[jnp.arange(T)[:, None], idx].set(p)
    chosen = jnp.zeros((T, cfg["num_experts"]), bool).at[jnp.arange(T)[:, None], idx].set(True)
    ffn = lambda x, gate, up, down: (jax.nn.silu(x @ gate) * (x @ up)) @ down  # noqa: E731

    def one(total, e):
        gate, up, down = (_matrix(x, m.dtype, int8) for x in e[:3])

        def a_round(state):
            total, left = state
            rows = jnp.nonzero(left, size=min(T, ROUND), fill_value=T)[0]
            x = m.at[rows].get(mode="fill", fill_value=0.0)
            w = e[3].at[rows].get(mode="fill", fill_value=0.0)
            total = total.at[rows].add(w[:, None] * ffn(x, gate, up, down), mode="drop")
            return total, left.at[rows].set(False, mode="drop")

        total, _ = jax.lax.while_loop(lambda s: s[1].any(), a_round, (total, e[4]))
        return total, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (lw["w_gate"], lw["w_up"], lw["w_down"], weight.T, chosen.T),
    )
    return routed


def _mlp(m, lw, int8: bool):
    gate, up, down = (_matrix(lw[n], m.dtype, int8) for n in EXPERT_MATRICES)
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _raise(lw: dict, dtype, int8: bool) -> dict:
    """A layer's leaves in ``dtype`` but its FFN's matrices (the dense
    MLP's, the experts'), which stay as they are stored until they are
    used."""
    return {
        name: x if name in EXPERT_MATRICES
        else _matrix(x, dtype, int8 and name in MATRICES)
        for name, x in lw.items()
    }


def hidden(w: dict, tokens, cfg: dict, dtype=jnp.float32):
    """Final hidden states ``[T, hidden_size]`` after the last norm for ONE
    sequence ``tokens`` [T]. A layer's weights are raised to ``dtype``
    where the layer uses them."""
    eps, int8 = cfg["norm_eps"], "int8" in w
    stored = w.get("int8", w)
    h = _embedding(w, dtype)[tokens]
    for i, lw in enumerate(stored["layers"]):
        lw = _raise(lw, dtype, int8)
        mixer = _attention if is_attention(cfg, i) else _short_conv
        h = h + mixer(_rms(h, lw["norm_in"], eps), lw, cfg)
        m = _rms(h, lw["norm_ff"], eps)
        h = h + (_mlp(m, lw, int8) if is_dense(cfg, i) else _experts(m, lw, cfg, int8))
    return _rms(h, stored["norm_f"].astype(dtype), eps)


def _embedding(w: dict, dtype):
    """The embedding ``[vocab, hidden]`` in ``dtype`` (the control's: int8
    with one scale a row, which is the tied head's output channel too)."""
    embed = w.get("int8", w)["embed"]
    if "int8" in w:
        embed = reference.int8_values(embed.astype(jnp.float32), -1)
    return embed.astype(dtype)


def logits(w: dict, tokens, cfg: dict, dtype=jnp.float32):
    """``[B, T, vocab]`` next-token logits (the head tied to the
    embedding)."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(
            [hidden(w, row, cfg, dtype) @ _embedding(w, dtype).T for row in tokens]
        )


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


def control_weights(w: dict) -> dict:
    """The serving control: every matrix (the mixers', the dense MLP's, the
    experts', the router) and the embedding, which is the head, rounded to
    int8 with one scale per output channel (per row for the embedding);
    norms, the convolution's taps and ``expert_bias`` stay. The SAME arrays
    in a wrapper: ``hidden`` rounds a matrix where it raises it."""
    return {"int8": w}


# ── the program ──────────────────────────────────────────────────────────


def lfm2_config(cfg: dict):
    from pygrid_tpu.models.lfm2_moe import Lfm2Config

    _supported(cfg)
    attention = sum(1 << i for i in range(cfg["num_hidden_layers"]) if is_attention(cfg, i))
    return Lfm2Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        n_layers=cfg["num_hidden_layers"], attn_layers=attention,
        n_dense=cfg["num_dense_layers"], d_ff=cfg["intermediate_size"],
        n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], d_conv=cfg["conv_L_cache"],
        max_len=cfg["deployment"]["served_context"],
        rope_theta=int(rope_theta(cfg)),
    )


def hosted(cfg: dict, params) -> tuple:
    """What the node's ``HostedModel`` takes: the bundle's ``model`` and the
    parsed form (``generation_cache``) the first request would otherwise
    make from an uploaded blob."""
    lcfg = lfm2_config(cfg)
    return {"family": "lfm2_moe", "cfg": list(lcfg)}, (lcfg, params)


def train_loss(cell: dict, interpret: bool):
    raise NotImplementedError(
        "no cell trains the lfm2_moe configuration: the program's trained "
        "expert layer is the top-1 GELU layer with capacity, not this one"
    )


# ── the counts ───────────────────────────────────────────────────────────


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict, layer: int) -> int:
    shapes = layer_shapes(cfg, is_attention(cfg, layer), is_dense(cfg, layer))
    return sum(int(np.prod(s)) for s, _, _ in shapes.values())


def dense_layer_params(cfg: dict, layer: int) -> int:
    """A layer outside its routed experts: the mixer, the norms, and the
    dense MLP or the router and its bias."""
    if is_dense(cfg, layer):
        return layer_params(cfg, layer)
    return layer_params(cfg, layer) - cfg["num_experts"] * expert_params(cfg)


def param_count(cfg: dict) -> int:
    """Every parameter held here: the layers, the embedding (which is the
    head) and the last norm."""
    layers = sum(layer_params(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return layers + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def weights_bytes(cfg: dict, itemsize: int = 2) -> int:
    return param_count(cfg) * itemsize


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return 2 * n_attention(cfg) * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def state_bytes_per_slot(cfg: dict, taps_itemsize: int = 2) -> int:
    """A slot's recurrent state: the last ``conv_L_cache - 1`` inputs of
    the convolution, every conv layer."""
    return n_conv(cfg) * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * taps_itemsize


def expert_ffn_bytes(cfg: dict, touched_pairs: float, itemsize: int = 2) -> float:
    """Bytes the grouped expert kernel has to read: each touched (layer,
    expert) pair's three matrices once."""
    return touched_pairs * expert_params(cfg) * itemsize


def expert_ffn_flops(cfg: dict, rows: float) -> float:
    """Operations of the grouped expert kernel over ``rows`` assignments."""
    return 2.0 * rows * expert_params(cfg)


def forward_flops_per_position(cfg: dict, context: float, head: bool = True) -> float:
    """Operations one position of a forward needs: 2 a matmul parameter it
    passes through (a mixer's matrices, the dense MLP or the router and the
    ``num_experts_per_tok`` experts it reaches, the head where the forward
    computes it: a prefill does for its last position alone); in an
    attention layer ``4 x heads x head_dim`` per attended position; in a
    conv layer the taps and the two gates (``2 taps + 2`` a channel)."""
    d = cfg["hidden_size"]
    total = 2.0 * d * cfg["vocab_size"] if head else 0.0
    for i in range(cfg["num_hidden_layers"]):
        attention, dense = is_attention(cfg, i), is_dense(cfg, i)
        shapes = layer_shapes(cfg, attention, dense)
        mats = sum(
            int(np.prod(s)) for name, (s, _, _) in shapes.items()
            if name in MATRICES and (dense or name not in EXPERT_MATRICES)
        )
        if not dense:
            mats += cfg["num_experts_per_tok"] * expert_params(cfg)
        total += 2.0 * mats
        if attention:
            total += 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * context
        else:
            total += (2.0 * cfg["conv_L_cache"] + 2.0) * d
    return total


def dense_step_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes a decode step reads whatever the routing: every layer outside
    its routed experts and the head, which is the embedding (its rows for
    the step's tokens are gathered from the same matrix)."""
    layers = sum(dense_layer_params(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return itemsize * (layers + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def decode_step_bytes(cfg: dict, live_rows: int, kv_tokens: int, touched_pairs: float,
                      weights_itemsize: int = 2, cache_itemsize: int = 2) -> float:
    """Bytes one decode step has to move through HBM: the weights outside
    the routed experts, the ``touched_pairs`` experts some row reached,
    each live row's convolution state read and written, and the
    ``kv_tokens`` cached positions the live rows attend over."""
    return (
        dense_step_bytes(cfg, weights_itemsize)
        + expert_ffn_bytes(cfg, touched_pairs, weights_itemsize)
        + 2 * live_rows * state_bytes_per_slot(cfg, cache_itemsize)
        + kv_tokens * kv_bytes_per_token(cfg, cache_itemsize)
    )


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward for one token of a ``seq_len``-token sequence:
    three times the forward's (no cell trains this configuration; the
    contract asks for the function)."""
    return 3.0 * forward_flops_per_position(cfg, (seq_len + 1) / 2.0)
