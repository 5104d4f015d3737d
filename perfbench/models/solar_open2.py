"""The adapter of ``"model_type": "solar_open2"`` (contract: ``models/gpt2.py``'s
docstring and ``spec.MODEL_CONTRACT``): the hybrid of delta-rule and softmax
layers with routed and shared experts of the Solar-Open2 family, written
from the published ``config.json`` and the Kimi Delta Attention paper
(arXiv:2510.26692), in plain ``jax.numpy``.

Pre-norm residual, every layer an expert layer, an untied head::

    h += mixer(rms(h; norm_in));  h += experts(rms(h; norm_ff))
    rms(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w
    logits    = rms(h; norm_f) W_head

    softmax mixer (layer i in gqa_layers): q = x W_q -> num_attention_heads
      of head_dim, k = x W_k, v = x W_v -> num_key_value_heads, no biases,
      use_rope false: no positional signal; softmax(q k^T / sqrt(head_dim)
      + causal) v, each K/V head under its group of query heads;
      use_gqa_gate true: out = [attn * sigmoid(x W_gate)] W_o

    delta-rule mixer (every other layer), H = linear_attn_config.num_heads
      heads, d_k = d_v = linear_attn_config.head_dim:
      q, k, v = x W_q, x W_k, x W_v;  each <- silu(causal depthwise
        convolution over time, short_conv_kernel_size taps, no bias)
      a head's q <- q / |q|_2 * d_k^-1/2,  k <- k / |k|_2
      g_t = -exp(A_log_h) * softplus(x W_f_down W_f_up + dt_bias)  [H, d_k]
        (kda_use_full_proj false: rank head_dim);  a_t = exp(g_t)
      b_t = 2 sigmoid(x W_beta)  [H]      (kda_allow_neg_eigval true: the 2)
      S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,  S_0 = 0
      o_t = S_t^T q_t
      out = [rms_{d_v}(o_t; o_norm) * sigmoid(x W_g_down W_g_up)] W_o

    experts: p = softmax(x W_r) over ALL the router's outputs, the
      num_experts_per_tok largest renormalised (norm_topk_prob true,
      routed_scaling_factor 1);  y = shared(x) + sum_{e in top-k, held}
      p_e expert_e(x), each (silu(x W_gate) * (x W_up)) W_down of
      moe_intermediate_size

**The share.** ``n_routed_experts`` counts the experts HELD here, experts
``deployment.held_first ..`` of the router's ``deployment.router_experts``
outputs (the published count): the chip's share of each layer under
expert parallelism. What the absent experts would add is left out, here
as in the program, and that partial sum goes on to the next layer.
``vocab_size`` is the slice of the vocabulary held here: a smaller
vocabulary.

The reference (``logits``) computes exactly the above, float32 at
``highest`` matmul precision, the recurrence ONE TOKEN AT A TIME in a
``lax.scan`` (no chunks), attention in blocks of queries (so that 8,448
positions fit: a block's scores, not the whole ``[heads, T, T]``), no
cache, no kernel, and imports nothing of the program. An expert runs over
the positions routed to it and no others, gathered ``ROUND`` at a time
for as many rounds as there are (every held expert over all 8,448
positions would be forty times the work). The weights are HELD in the type they are stored in
(bf16 for the served configuration: 6.6 GB) and raised to float32 a layer
at a time, an expert's matrices where the loop over the experts reaches
it. The int8 control is therefore no second copy: ``control_weights``
wraps the same arrays, and a matrix is rounded where it is raised.

The program holds three leaves laid out for the device: the three
convolutions side by side as ``conv_w [K, 3 H d_k]`` (the reference:
``conv_q``, ``conv_k``, ``conv_v`` as published, ``[H d_k, K]``), and a
head's state transposed; ``to_reference`` undoes the first, the second
never leaves the program.

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
MATRICES = (
    "wq", "wk", "wv", "wg", "wo", "f_down", "f_up", "g_down", "g_up",
    "w_beta", "router", "s_gate", "s_up", "s_down",
) + EXPERT_MATRICES
CONVS = ("conv_q", "conv_k", "conv_v")
#: the keys of the published config the mathematics reads (numbers)
KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size", "n_routed_experts",
    "num_experts_per_tok", "moe_intermediate_size", "gqa_interval",
)
L2_EPS = 1e-6


def tiny(cfg: dict) -> dict:
    """One period of the pattern (softmax at layer 0, three delta-rule
    layers), four heads of 16 on two K/V heads, 2 of the router's 16
    experts held (an eighth, as the cell's 40 of 320), two a token."""
    deployment = dict(
        cfg["deployment"], served_context=128, router_experts=16, held_first=0
    )
    return dict(
        num_hidden_layers=4, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, vocab_size=128,
        n_routed_experts=2, num_experts_per_tok=2, moe_intermediate_size=32,
        linear_attn_config=dict(cfg["linear_attn_config"], head_dim=16, num_heads=4),
        deployment=deployment,
    )


def _supported(cfg: dict) -> None:
    """What of the family this adapter (and the program) expresses."""
    want = {
        "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "n_shared_experts": 1,
        "first_k_dense_replace": 0, "tie_word_embeddings": False,
        "rms_norm_eps": 1e-5,
    }
    off = {k: cfg.get(k) for k, v in want.items() if cfg.get(k, v) != v}
    lin = cfg["linear_attn_config"]
    if (lin["num_heads"], lin["head_dim"]) != (cfg["num_attention_heads"], cfg["head_dim"]):
        off["linear_attn_config"] = lin
    period, depth = cfg["gqa_interval"] + 1, cfg["num_hidden_layers"]
    if [i for i in cfg["gqa_layers"] if i < depth] != list(range(0, depth, period)):
        off["gqa_layers"] = cfg["gqa_layers"]
    if off:
        raise ValueError(f"the solar_open2 adapter does not express {off}")


def is_gqa(cfg: dict, layer: int) -> bool:
    """``gqa_layers`` is the published list of all 48 layers' softmax
    layers; the layers held here are its first ``num_hidden_layers``."""
    return layer in cfg["gqa_layers"]


def n_gqa(cfg: dict) -> int:
    return sum(is_gqa(cfg, i) for i in range(cfg["num_hidden_layers"]))


def router_experts(cfg: dict) -> int:
    """The router's outputs: the published count of routed experts."""
    return int(cfg["deployment"]["router_experts"])


def conv_taps(cfg: dict) -> int:
    return int(cfg["linear_attn_config"]["short_conv_kernel_size"])


# ── the weights ──────────────────────────────────────────────────────────


def layer_shapes(cfg: dict, gqa: bool) -> dict:
    """Leaf -> (shape, scale, mean) of one layer in the PROGRAM's layout.
    Matrices ``[in, out]`` at fan-in scale (a layer's held experts stacked
    on axis 0), norms 1 +- 0.05; ``A_log`` and ``dt_bias`` are drawn in
    ``_layer``, and so is the router: its columns come in PAIRS ``w, -w``
    (experts ``2i`` and ``2i + 1``), so that the set of the experts'
    handicaps (a column's product with the direction the hidden states
    of random weights share) is symmetric and the same in shape for
    every seed. A seed then changes which experts are favoured, not how
    unevenly the router spreads its load, as it changes a prompt's tokens
    and not the lengths: six runs spread 1.21% in ``gen_tokens_per_s``
    against 2.36% with every column drawn alone (PERF.md §6, PR 36). The
    eighth of the experts held here still reads 11-14% of the
    assignments from seed to seed: a pair does not share its load."""
    d, dh, f = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"]
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, K, r = cfg["n_routed_experts"], conv_taps(cfg), dh
    norm = lambda width: ((width,), 0.05, 1.0)  # noqa: E731
    mat = lambda *shape: (shape, shape[-2] ** -0.5, 0.0)  # noqa: E731
    experts = {
        "norm_ff": norm(d), "router": mat(d, router_experts(cfg)),
        "w_gate": mat(E, d, f), "w_up": mat(E, d, f), "w_down": mat(E, f, d),
        "s_gate": mat(d, f), "s_up": mat(d, f), "s_down": mat(f, d),
    }
    if gqa:
        return {
            "norm_in": norm(d), "wq": mat(d, H * dh), "wk": mat(d, G * dh),
            "wv": mat(d, G * dh), "wg": mat(d, H * dh), "wo": mat(H * dh, d),
            **experts,
        }
    return {
        "norm_in": norm(d), "wq": mat(d, H * dh), "wk": mat(d, H * dh),
        "wv": mat(d, H * dh), "conv_w": mat(K, 3 * H * dh),
        "f_down": mat(d, r), "f_up": mat(r, H * dh),
        "dt_bias": ((H * dh,), None, None), "A_log": ((H,), None, None),
        "g_down": mat(d, r), "g_up": mat(r, H * dh), "w_beta": mat(d, H),
        "o_norm": norm(dh), "wo": mat(H * dh, d), **experts,
    }


@functools.partial(jax.jit, static_argnames=("cfg_items", "gqa", "dtype"))
def _layer(key, cfg_items, gqa, dtype):
    """One layer's leaves from its key, a jitted call a layer (all 3.3e9
    values in one call would hold their float32 draws alive at once)."""
    cfg, out = _cfg_of(cfg_items), {}
    for j, (name, (shape, scale, mean)) in enumerate(layer_shapes(cfg, gqa).items()):
        k = jax.random.fold_in(key, j)
        if name == "A_log":
            # the delta rule's published initialisation: A uniform in [1, 16]
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            # ... and softplus(dt_bias) log-uniform in [0.001, 0.1]
            lo, hi = np.log(0.001), np.log(0.1)
            dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(k, shape))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "router":
            # columns in pairs w, -w (``layer_shapes``)
            half = scale * jax.random.normal(k, (shape[0], shape[1] // 2), jnp.float32)
            leaf = jnp.stack([half, -half], -1).reshape(shape)
        else:
            leaf = mean + scale * jax.random.normal(k, shape, jnp.float32)
        out[name] = leaf.astype(dtype)
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _globals(key, cfg_items, dtype):
    cfg = _cfg_of(cfg_items)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    draw = lambda i, shape: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape, jnp.float32
    )
    return {
        "embed": (d**-0.5 * draw(0, (V, d))).astype(dtype),
        "head": (d**-0.5 * draw(1, (d, V))).astype(dtype),
        "norm_f": (1.0 + 0.05 * draw(2, (d,))).astype(dtype),
    }


def _items(cfg: dict):
    _supported(cfg)
    return (
        tuple((k, int(cfg[k])) for k in KEYS)
        + (("router_experts", router_experts(cfg)), ("conv_taps", conv_taps(cfg)))
    )


def _cfg_of(items) -> dict:
    """The hashable ``_items`` back as the dict ``layer_shapes`` reads."""
    cfg = dict(items)
    cfg["deployment"] = {"router_experts": cfg.pop("router_experts")}
    cfg["linear_attn_config"] = {"short_conv_kernel_size": cfg.pop("conv_taps")}
    return cfg


def make_program_params(seed: int, cfg: dict, dtype: str) -> dict:
    """The weights as the program takes them: parameters by name, in
    ``dtype``, a jitted call a layer. A program without this family (the
    parent of the PR that added it) is refused here, before 6.6 GB are
    drawn for a node that cannot host them."""
    import importlib.util

    if importlib.util.find_spec("pygrid_tpu.models.solar_open2") is None:
        raise RuntimeError(
            "the program beside this benchmark has no pygrid_tpu.models."
            "solar_open2: it cannot host model_type 'solar_open2'"
        )
    return _make(seed, cfg, dtype)


def _make(seed: int, cfg: dict, dtype: str) -> dict:
    key, items = reference.seed_key(seed), _items(cfg)
    return dict(
        _globals(key, items, dtype),
        layers=[
            _layer(jax.random.fold_in(key, 3 + i), items, is_gqa(cfg, i), dtype)
            for i in range(cfg["num_hidden_layers"])
        ],
    )


def make_weights(seed: int, cfg: dict, dtype: str) -> dict:
    """The reference's weights, held in ``dtype``: the program's values in
    the published layout."""
    return to_reference(_make(seed, cfg, dtype), cfg)


def to_reference(params: dict, cfg: dict) -> dict:
    """The program's parameters in the published layout, values untouched:
    the three convolutions apart, ``[channels, taps]`` each."""

    def layer(lp):
        lp = dict(lp)
        if "conv_w" in lp:
            parts = jnp.split(lp.pop("conv_w"), 3, axis=1)
            lp.update({name: part.T for name, part in zip(CONVS, parts)})
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


def _query_block(T: int) -> int:
    """Queries a block of the softmax attention: the scores held at once
    are ``[heads, block, T]``."""
    return next((b for b in (256, 128, 64, 32) if T % b == 0 and T > b), T)


def _attention(x, lw, cfg):
    """Causal grouped-query attention over one sequence ``x`` [T, d], a
    block of queries at a time, gated."""
    T = x.shape[0]
    H, G, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (x @ lw["wq"]).reshape(T, G, H // G, dh)
    k = (x @ lw["wk"]).reshape(T, G, dh)
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
    return (a * jax.nn.sigmoid(x @ lw["wg"])) @ lw["wo"]


def _conv_silu(u, w):
    """``silu`` of the causal depthwise convolution of ``u`` [T, C] with
    ``w`` [C, K] (tap ``K - 1`` is the current position's)."""
    T, K = u.shape[0], w.shape[1]
    u_pad = jnp.pad(u, ((K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(u_pad[j : j + T] * w[:, j] for j in range(K)))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _delta_rule(x, lw, cfg):
    """The delta-rule mixer over one sequence ``x`` [T, d] from a zero
    state, the recurrence one token at a time."""
    T = x.shape[0]
    H, dh = cfg["num_attention_heads"], cfg["head_dim"]
    heads = lambda t: t.reshape(T, H, dh)  # noqa: E731
    q = _unit(heads(_conv_silu(x @ lw["wq"], lw["conv_q"]))) * dh**-0.5
    k = _unit(heads(_conv_silu(x @ lw["wk"], lw["conv_k"])))
    v = heads(_conv_silu(x @ lw["wv"], lw["conv_v"]))
    g = -jnp.exp(lw["A_log"])[:, None] * heads(
        jax.nn.softplus(x @ lw["f_down"] @ lw["f_up"] + lw["dt_bias"])
    )
    beta = 2.0 * jax.nn.sigmoid(x @ lw["w_beta"])

    def step(S, xs):  # one token: S [H, d_k, d_v]
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S
        u = v_t - jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + b_t[:, None, None] * k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, dh, dh), x.dtype), (q, k, v, g, beta))
    o = _rms(o, lw["o_norm"], cfg["rms_norm_eps"]).reshape(T, H * dh)
    return (o * jax.nn.sigmoid(x @ lw["g_down"] @ lw["g_up"])) @ lw["wo"]


#: positions an expert takes a round: it runs over the positions routed to
#: it, this many gathered at a time, for as many rounds as that takes
ROUND = 512


def _experts(m, lw, cfg, int8: bool):
    """``shared(m) + sum over the held experts a position chose``: one held
    expert at a time (its three matrices raised to ``m``'s type there) over
    the positions routed to it, ``ROUND`` of them a round until none is
    left (an expert nobody chose takes no round; the padding behind a
    sequence, which all goes one way, takes several)."""
    T, k = m.shape[0], cfg["num_experts_per_tok"]
    first, held = int(cfg["deployment"]["held_first"]), cfg["n_routed_experts"]
    r = jax.nn.softmax(m @ lw["router"], -1)
    top, idx = jax.lax.top_k(r, k)
    weight = jnp.zeros_like(r).at[jnp.arange(T)[:, None], idx].set(
        top / top.sum(-1, keepdims=True)
    )
    ffn = lambda x, gate, up, down: (jax.nn.silu(x @ gate) * (x @ up)) @ down  # noqa: E731

    def one(total, e):
        gate, up, down = (_matrix(x, m.dtype, int8) for x in e[:3])

        def a_round(state):
            total, left = state
            rows = jnp.nonzero(left, size=min(T, ROUND), fill_value=T)[0]
            x = m.at[rows].get(mode="fill", fill_value=0.0)
            p = e[3].at[rows].get(mode="fill", fill_value=0.0)
            total = total.at[rows].add(p[:, None] * ffn(x, gate, up, down), mode="drop")
            return total, left.at[rows].set(False, mode="drop")

        total, _ = jax.lax.while_loop(lambda s: s[1].any(), a_round, (total, e[3] > 0))
        return total, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (lw["w_gate"], lw["w_up"], lw["w_down"], weight.T[first : first + held]),
    )
    shared = ffn(m, *(_matrix(lw[n], m.dtype, int8) for n in ("s_gate", "s_up", "s_down")))
    return shared + routed


def _raise(lw: dict, dtype, int8: bool) -> dict:
    """A layer's leaves in ``dtype`` but its experts' (routed and shared),
    which stay as they are stored until ``_experts`` raises them."""
    late = EXPERT_MATRICES + ("s_gate", "s_up", "s_down")
    return {
        name: x if name in late else _matrix(x, dtype, int8 and name in MATRICES)
        for name, x in lw.items()
    }


def hidden(w: dict, tokens, cfg: dict, dtype=jnp.float32):
    """Final hidden states ``[T, hidden_size]`` after the last norm for ONE
    sequence ``tokens`` [T]. A layer's weights are raised to ``dtype``
    where the layer uses them."""
    eps, int8 = cfg["rms_norm_eps"], "int8" in w
    w = w.get("int8", w)
    embed = w["embed"]
    if int8:
        embed = reference.int8_values(embed.astype(jnp.float32), -1)
    h = embed[tokens].astype(dtype)
    for i, lw in enumerate(w["layers"]):
        mixer = _attention if is_gqa(cfg, i) else _delta_rule
        lw = _raise(lw, dtype, int8)
        h = h + mixer(_rms(h, lw["norm_in"], eps), lw, cfg)
        h = h + _experts(_rms(h, lw["norm_ff"], eps), lw, cfg, int8)
    return _rms(h, w["norm_f"].astype(dtype), eps)


def logits(w: dict, tokens, cfg: dict, dtype=jnp.float32):
    """``[B, T, vocab]`` next-token logits (an untied head)."""
    head = lambda: _matrix(w.get("int8", w)["head"], dtype, "int8" in w)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        return jnp.stack([hidden(w, row, cfg, dtype) @ head() for row in tokens])


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
    """The serving control: every matrix (the experts', routed and shared,
    the low ranks, the router), the embedding and the head rounded to int8
    with one scale per output channel (per row for the embedding); norms,
    the convolutions, ``A_log`` and ``dt_bias`` stay. The SAME arrays in a
    wrapper: ``hidden`` rounds a matrix where it raises it."""
    return {"int8": w}


# ── the program ──────────────────────────────────────────────────────────


def solar_config(cfg: dict):
    from pygrid_tpu.models.solar_open2 import SolarConfig

    _supported(cfg)
    return SolarConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=cfg["num_hidden_layers"], n_experts=router_experts(cfg),
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["moe_intermediate_size"],
        held_first=int(cfg["deployment"]["held_first"]),
        held_count=cfg["n_routed_experts"],
        max_len=cfg["deployment"]["served_context"],
        gqa_period=cfg["gqa_interval"] + 1, gqa_offset=0,
        kda_rank=cfg["head_dim"], d_conv=conv_taps(cfg),
    )


def hosted(cfg: dict, params) -> tuple:
    """What the node's ``HostedModel`` takes: the bundle's ``model`` and the
    parsed form (``generation_cache``) the first request would otherwise
    make from an uploaded blob."""
    scfg = solar_config(cfg)
    return {"family": "solar_open2", "cfg": list(scfg)}, (scfg, params)


def train_loss(cell: dict, interpret: bool):
    raise NotImplementedError(
        "no cell trains the solar_open2 configuration: the least cut inside "
        "the floors is 1.29e9 parameters, 20.7 GB at 16 B a parameter"
    )


# ── the counts ───────────────────────────────────────────────────────────


def expert_params(cfg: dict) -> int:
    """One expert's three matrices (a routed one's, and the shared one's)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict, layer: int) -> int:
    shapes = layer_shapes(cfg, is_gqa(cfg, layer))
    return sum(int(np.prod(s)) for s, _, _ in shapes.values())


def dense_layer_params(cfg: dict, layer: int) -> int:
    """A layer outside its routed experts: the mixer, the norms, the
    router and the shared expert."""
    return layer_params(cfg, layer) - cfg["n_routed_experts"] * expert_params(cfg)


def param_count(cfg: dict) -> int:
    """Every parameter held here: the layers, embedding, head, last norm."""
    layers = sum(layer_params(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return layers + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def weights_bytes(cfg: dict, itemsize: int = 2) -> int:
    return param_count(cfg) * itemsize


def n_kda(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - n_gqa(cfg)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return (
        2 * n_gqa(cfg) * cfg["num_key_value_heads"]
        * cfg["head_dim"] * itemsize
    )


def state_bytes_per_slot(cfg: dict, taps_itemsize: int = 2) -> int:
    """A slot's recurrent state: a float32 ``[d_k, d_v]`` matrix a head and
    ``K - 1`` taps of the three convolutions, every delta-rule layer."""
    H, dh = cfg["num_attention_heads"], cfg["head_dim"]
    per = H * dh * dh * 4 + (conv_taps(cfg) - 1) * 3 * H * dh * taps_itemsize
    return n_kda(cfg) * per


CHUNK = 64  # positions a chunk of the program's chunked form


def kda_chunk_flops(cfg: dict) -> float:
    """Operations the chunked delta rule needs for one position of one
    layer, every head: against the state, its read for ``u`` and for ``o``
    and its update (``6 d_k d_v``); inside the chunk, the causal half of
    the two ``[CHUNK, CHUNK]`` decay-weighted products over ``d_k`` (``2
    CHUNK d_k``) and of the solve and the read-out over ``d_v`` (``2 CHUNK
    d_v``). The exponentials and the triangular inverse's own products are
    not counted."""
    H, dh = cfg["num_attention_heads"], cfg["head_dim"]
    return float(H * (6 * dh * dh + 4 * CHUNK * dh))


def kda_chunk_bytes(cfg: dict) -> float:
    """Bytes the chunked delta rule has to move for one position of one
    layer: ``q, k, v, g`` in and ``o`` out, float32 as the program holds
    them, and ``beta``. The state stays on the chip over a prompt."""
    H, dh = cfg["num_attention_heads"], cfg["head_dim"]
    return float(4 * (5 * H * dh + H))


def expert_ffn_bytes(cfg: dict, touched_pairs: float, itemsize: int = 2) -> float:
    """Bytes the grouped expert kernel has to read: each touched (layer,
    held expert) pair's three matrices once."""
    return touched_pairs * expert_params(cfg) * itemsize


def expert_ffn_flops(cfg: dict, held_rows: float) -> float:
    """Operations of the grouped expert kernel over ``held_rows``
    assignments that fell on a held expert."""
    return 2.0 * held_rows * expert_params(cfg)


def forward_flops_per_position(cfg: dict, context: float, held_per_position: float | None = None) -> float:
    """Operations one position of a forward needs: 2 a matmul parameter it
    passes through (a mixer's matrices, the router, the shared expert, the
    ``held_per_position`` routed experts it reaches here: the even share
    ``num_experts_per_tok x held / routed`` unless counted, the head); in a
    softmax layer ``4 x heads x head_dim`` per attended position; in a
    delta-rule layer the recurrence (``kda_chunk_flops``)."""
    d = cfg["hidden_size"]
    if held_per_position is None:
        held_per_position = (
            cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / router_experts(cfg)
        )
    total = 2.0 * d * cfg["vocab_size"]
    for i in range(cfg["num_hidden_layers"]):
        gqa = is_gqa(cfg, i)
        shapes = layer_shapes(cfg, gqa)
        mats = sum(
            int(np.prod(s)) for name, (s, _, _) in shapes.items()
            if name in MATRICES and name not in EXPERT_MATRICES
        )
        total += 2.0 * (mats + held_per_position * expert_params(cfg))
        if gqa:
            total += 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * context
        else:
            total += kda_chunk_flops(cfg)
    return total


def dense_step_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes a decode step reads whatever the routing: every layer outside
    its routed experts and the head (the embedding is gathered by row)."""
    layers = sum(dense_layer_params(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return itemsize * (layers + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def decode_step_bytes(cfg: dict, live_rows: int, kv_tokens: int, touched_pairs: float,
                      weights_itemsize: int = 2, cache_itemsize: int = 2) -> float:
    """Bytes one decode step has to move through HBM: the weights outside
    the routed experts, the ``touched_pairs`` held experts some row
    reached, each live row's recurrent state read and written, and the
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
