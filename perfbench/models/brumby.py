"""The adapter of ``"model_type": "brumby"`` (contract: ``models/gpt2.py``'s
docstring and ``spec.MODEL_CONTRACT``): the dense decoder with power
retention in every layer of the Brumby family, written from the published
``config.json`` (whose keys spell out the Qwen3 dense block) and Manifest
AI's "Scaling Context Requires Rethinking Attention" (arXiv:2507.04239), in
plain ``jax.numpy``.

Pre-norm residual, an untied head::

    h += mixer(rms(h; norm_in));  h += mlp(rms(h; norm_ff))
    rms(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w
    logits    = rms(h; norm_f) W_head

    mixer: n the normed input;  q = n W_q -> num_attention_heads of
      head_dim, k = n W_k, v = n W_v -> num_key_value_heads, no biases
      (attention_bias false);  q, k <- rms over head_dim with a learned
      weight, then rotary positions (rope_theta, rotate-half, all of
      head_dim);  log g_t = log sigmoid(n W_g + b_g), one number a K/V head
      for K/V head h and each query head of its group, s = head_dim^-1/2:
        a_tj = exp(log g_{j+1} + .. + log g_t) (s q_t . k_j)^2     (j <= t)
        y_t  = sum_j a_tj v_j / (sum_j a_tj + eps)
      out = y W_o
    mlp: (silu(m W_gate) * (m W_up)) W_down of intermediate_size

The reference (``logits``) computes exactly that: the QUADRATIC sum over
``j``, float32 at ``highest`` matmul precision, a block of queries at a
time (so that 5,120 positions fit: a block's weights ``a``, not the whole
``[heads, T, T]``), the log-gates summed once over the sequence; no feature
map, no state, no chunks, no kernel, and it imports nothing of the program.
The program carries ``sum_j a_tj v_j`` as a state over a feature map of
8,320 numbers a head (``pygrid_tpu/models/retention.py``): that the two
agree is what shows the map, its weights and the carried state to be right.
The weights are HELD in the type they are stored in (bf16 for the served
configuration: 8.4 GB) and raised to float32 a layer at a time; the int8
control is the same arrays in a wrapper, a matrix rounded where it is
raised. ``served_gaps`` is ``lib/reference.served_gaps``'s comparison (a
causal decoder, teacher forcing shifted by one) with the head applied to
the served rows alone and a slice of the vocabulary at a time: every row's
logits over 151,936 ids would be 3.1 GB beside the weights.

Departures from the published model: none in the mathematics that the
config's keys state; what it has no key for (the degree, the gate's form
and its bias, the normaliser's epsilon) is under ``assumed`` in the
configuration file. The weights are random from ``--seed`` and the served
context is the deployment's.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from lib import reference

MATRICES = ("wq", "wk", "wv", "wg", "wo", "w_gate", "w_up", "w_down")
#: the keys of the published config the mathematics reads (numbers)
KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size", "intermediate_size",
    "rope_theta",
)
#: added to the normaliser (``assumed.eps``)
EPS = 1e-5
#: the gates' e-folding lengths, positions (``assumed.gate_bias``)
GATE_SPAN = (16.0, 8192.0)
GATE_SCALE = 0.25


def tiny(cfg: dict) -> dict:
    """Two layers, four heads of 16 on two K/V heads, a context of 128."""
    return dict(
        num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, vocab_size=128,
        intermediate_size=128,
        deployment=dict(cfg["deployment"], served_context=128),
    )


def _supported(cfg: dict) -> None:
    """What of the family this adapter (and the program) expresses."""
    want = {
        "attention_bias": False, "hidden_act": "silu", "rope_scaling": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "rms_norm_eps": 1e-6,
    }
    off = {k: cfg.get(k) for k, v in want.items() if cfg.get(k, v) != v}
    if off:
        raise ValueError(f"the brumby adapter does not express {off}")


# ── the weights ──────────────────────────────────────────────────────────


def layer_shapes(cfg: dict) -> dict:
    """Leaf -> (shape, scale, mean) of one layer, the program's layout and
    the reference's alike: matrices ``[in, out]`` at fan-in scale (the
    gate's at a quarter of it), norms 1 +- 0.05; ``bg`` is drawn in
    ``_layer``."""
    d, dh, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    norm = lambda width: ((width,), 0.05, 1.0)  # noqa: E731
    mat = lambda *shape: (shape, shape[-2] ** -0.5, 0.0)  # noqa: E731
    return {
        "norm_in": norm(d), "wq": mat(d, H * dh), "wk": mat(d, G * dh),
        "wv": mat(d, G * dh), "q_norm": norm(dh), "k_norm": norm(dh),
        "wg": ((d, G), GATE_SCALE * d**-0.5, 0.0), "bg": ((G,), None, None),
        "wo": mat(H * dh, d), "norm_ff": norm(d), "w_gate": mat(d, f),
        "w_up": mat(d, f), "w_down": mat(f, d),
    }


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _layer(key, cfg_items, dtype):
    """One layer's leaves from its key, a jitted call a layer (all 4.2e9
    values in one call would hold their float32 draws alive at once)."""
    out = {}
    for j, (name, (shape, scale, mean)) in enumerate(layer_shapes(dict(cfg_items)).items()):
        k = jax.random.fold_in(key, j)
        if name == "bg":
            # 1 / (1 - sigmoid(bg)), the e-folding length, log-uniform
            lo, hi = np.log(GATE_SPAN[0]), np.log(GATE_SPAN[1])
            span = jnp.exp(lo + (hi - lo) * jax.random.uniform(k, shape))
            leaf = jnp.log(span - 1.0)
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
        "head": (d**-0.5 * draw(1, (d, V))).astype(dtype),
        "norm_f": (1.0 + 0.05 * draw(2, (d,))).astype(dtype),
    }


def _items(cfg: dict):
    _supported(cfg)
    return tuple((k, int(cfg[k])) for k in KEYS)


def make_program_params(seed: int, cfg: dict, dtype: str) -> dict:
    """The weights as the program takes them: parameters by name, in
    ``dtype``, a jitted call a layer. A program without this family (the
    parent of the PR that added it) is refused here, before 8.4 GB are
    drawn for a node that cannot host them."""
    import importlib.util

    if importlib.util.find_spec("pygrid_tpu.models.brumby") is None:
        raise RuntimeError(
            "the program beside this benchmark has no pygrid_tpu.models."
            "brumby: it cannot host model_type 'brumby'"
        )
    return _make(seed, cfg, dtype)


def _make(seed: int, cfg: dict, dtype: str) -> dict:
    key, items = reference.seed_key(seed), _items(cfg)
    return dict(
        _globals(key, items, dtype),
        layers=[
            _layer(jax.random.fold_in(key, 3 + i), items, dtype)
            for i in range(cfg["num_hidden_layers"])
        ],
    )


def make_weights(seed: int, cfg: dict, dtype: str) -> dict:
    """The reference's weights, held in ``dtype``: the program's values."""
    return to_reference(_make(seed, cfg, dtype), cfg)


def to_reference(params: dict, cfg: dict) -> dict:
    """The program's parameters as the reference takes them: the same
    names, the same layout (the program lays nothing out for the device
    but its state, which never leaves it)."""
    return params


# ── the mathematics ──────────────────────────────────────────────────────


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _matrix(x, dtype, int8: bool):
    """A stored matrix in ``dtype``; the control's rounded to int8 with
    one scale per output channel first."""
    if int8:
        x = reference.int8_values(x.astype(jnp.float32), -2)
    return x.astype(dtype)


def _rope(x, theta):
    """Rotary positions 0.. on ``x`` [T, heads, dh], rotate-half."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv_freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _query_block(T: int) -> int:
    """Queries a block: the weights held at once are ``[heads, block, T]``."""
    return next((b for b in (256, 128, 64, 32) if T % b == 0 and T > b), T)


def _retention(x, lw, cfg):
    """Power retention over one sequence ``x`` [T, d], the quadratic sum a
    block of queries at a time."""
    T = x.shape[0]
    H, G, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = _rope(_rms((x @ lw["wq"]).reshape(T, H, dh), lw["q_norm"], eps), cfg["rope_theta"])
    k = _rope(_rms((x @ lw["wk"]).reshape(T, G, dh), lw["k_norm"], eps), cfg["rope_theta"])
    v = (x @ lw["wv"]).reshape(T, G, dh)
    q = q.reshape(T, G, H // G, dh)
    # log g_1 + .. + log g_t, summed once over the sequence, float32
    run = jnp.cumsum(
        jax.nn.log_sigmoid((x @ lw["wg"] + lw["bg"]).astype(jnp.float32)), 0
    ).astype(x.dtype)
    qb = _query_block(T)

    def block(args):
        qs, run_t, first = args
        s = jnp.einsum("qgrd,kgd->grqk", qs, k) * dh**-0.5
        seen = jnp.arange(T)[None, :] <= first + jnp.arange(qb)[:, None]
        gap = run_t.T[:, :, None] - run.T[:, None, :]  # [G, q, k]
        a = jnp.where(seen, s * s * jnp.exp(jnp.where(seen, gap, 0.0))[:, None], 0.0)
        num = jnp.einsum("grqk,kgd->qgrd", a, v)
        return num / (a.sum(-1).transpose(2, 0, 1)[..., None] + EPS)

    y = jax.lax.map(
        block,
        (
            q.reshape(T // qb, qb, G, H // G, dh), run.reshape(T // qb, qb, G),
            jnp.arange(0, T, qb),
        ),
    ).reshape(T, H * dh)
    return y @ lw["wo"]


def _mlp(m, lw):
    return (jax.nn.silu(m @ lw["w_gate"]) * (m @ lw["w_up"])) @ lw["w_down"]


def _embedded(w: dict, tokens, dtype):
    rows = w.get("int8", w)["embed"][tokens]
    if "int8" in w:
        # one scale a row: the rows gathered round as the whole table would
        rows = reference.int8_values(rows.astype(jnp.float32), -1)
    return rows.astype(dtype)


def _block(h, lw: dict, cfg: dict, dtype, int8: bool):
    """One layer over ``h`` [T, d], its weights raised to ``dtype`` here."""
    eps = cfg["rms_norm_eps"]
    lw = {
        name: _matrix(x, dtype, int8 and name in MATRICES)
        for name, x in lw.items()
    }
    h = h + _retention(_rms(h, lw["norm_in"], eps), lw, cfg)
    return h + _mlp(_rms(h, lw["norm_ff"], eps), lw)


def hidden(w: dict, tokens, cfg: dict, dtype=jnp.float32):
    """Final hidden states ``[T, hidden_size]`` after the last norm for ONE
    sequence ``tokens`` [T]. A layer's weights are raised to ``dtype``
    where the layer uses them."""
    h = _embedded(w, tokens, dtype)
    for lw in w.get("int8", w)["layers"]:
        h = _block(h, lw, cfg, dtype, "int8" in w)
    return _rms(h, w.get("int8", w)["norm_f"].astype(dtype), cfg["rms_norm_eps"])


def _head(w: dict, dtype):
    return _matrix(w.get("int8", w)["head"], dtype, "int8" in w)


def logits(w: dict, tokens, cfg: dict, dtype=jnp.float32):
    """``[B, T, vocab]`` next-token logits (an untied head)."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([hidden(w, row, cfg, dtype) @ _head(w, dtype) for row in tokens])


#: ids a slice of the head takes in ``served_gaps``
VOCAB_SLICE = 1 << 15


@functools.partial(jax.jit, static_argnames=("cfg_json", "int8"), donate_argnums=(0,))
def _block_rows(h, lw, cfg_json, int8):
    with jax.default_matmul_precision("highest"):
        return _block(h, lw, json.loads(cfg_json), h.dtype, int8)


def _hidden_rows(w, seq, cfg_json):
    """``hidden`` a compiled call a layer: one layer's 1.3 GB of float32
    weights alive at a time beside the 8.4 GB they are stored in, whatever
    order a compiler would raise them in."""
    h = _embedded(w, seq, jnp.float32)
    for lw in w.get("int8", w)["layers"]:
        h = _block_rows(h, lw, cfg_json, "int8" in w)
    eps = json.loads(cfg_json)["rms_norm_eps"]
    return _rms(h, w.get("int8", w)["norm_f"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("rows",))
def _served_logits(w, h, first, rows):
    """The logits of ``rows`` positions of ``h`` from ``first`` on, the
    head a slice of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        h = jax.lax.dynamic_slice_in_dim(h, first, rows, 0)
        head = w.get("int8", w)["head"]
        parts = []
        for lo in range(0, head.shape[1], VOCAB_SLICE):
            part = head[:, lo : lo + VOCAB_SLICE]
            if "int8" in w:
                # one scale an output channel: a slice rounds as the whole
                part = reference.int8_values(part.astype(jnp.float32), -2)
            parts.append(h @ part.astype(h.dtype))
        return jnp.concatenate(parts, -1)


def served_gaps(w, cfg: dict, request: dict, answer: dict, pad_to: int, control=None):
    """A causal decoder, one token a row a step: the shifted-by-one
    comparison of ``lib/reference.served_gaps`` (the gap at each served
    position between the reference's best logit and the served token's,
    teacher forced on what was served; with ``control`` weights also the
    gap of the token the control puts first), the head over the served
    rows alone."""
    prompt, served = request["prompt"], answer["tokens"]
    p_len, n_new = prompt.shape[-1], served.shape[-1]
    seq = np.zeros((pad_to,), np.int32)
    seq[:p_len] = prompt.reshape(-1)
    seq[p_len : p_len + n_new - 1] = served.reshape(-1)[:-1]
    # causal: padding after the sequence cannot reach back into it
    cfg_json = json.dumps(cfg, sort_keys=True)
    # a compiled head a power of two of rows, not a request: the rows
    # start where they stay inside the sequence
    rows = min(pad_to, max(128, 1 << (n_new - 1).bit_length()))
    first = min(p_len - 1, pad_to - rows)
    skip = p_len - 1 - first

    def served_rows(weights):
        h = _hidden_rows(weights, jnp.asarray(seq), cfg_json)
        out = _served_logits(weights, h, jnp.int32(first), rows)
        return np.asarray(out[skip : skip + n_new])

    at = served_rows(w)
    best = at.max(-1)
    gaps = best - at[np.arange(n_new), served.reshape(-1)]
    if control is None:
        return gaps, None
    picks = served_rows(control).argmax(-1)
    return gaps, best - at[np.arange(n_new), picks]


# ── the controls ─────────────────────────────────────────────────────────


def leaf_norms(w: dict) -> dict:
    """L2 norm of every leaf, by its path (no cell trains this
    configuration; the contract asks for the function)."""
    flat = jax.tree_util.tree_flatten_with_path(w)[0]
    return reference.leaf_norms(
        {jax.tree_util.keystr(path): leaf for path, leaf in flat}, ()
    )


def control_weights(w: dict) -> dict:
    """The serving control: every matrix (the gate's with them), the
    embedding and the head rounded to int8 with one scale per output
    channel (per row for the embedding); norms and ``bg`` stay. The SAME
    arrays in a wrapper: ``hidden`` rounds a matrix where it raises it."""
    return {"int8": w}


# ── the program ──────────────────────────────────────────────────────────


def brumby_config(cfg: dict):
    from pygrid_tpu.models.brumby import BrumbyConfig

    _supported(cfg)
    return BrumbyConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_len=cfg["deployment"]["served_context"],
        rope_theta=cfg["rope_theta"],
    )


def hosted(cfg: dict, params) -> tuple:
    """What the node's ``HostedModel`` takes: the bundle's ``model`` and the
    parsed form (``generation_cache``) the first request would otherwise
    make from an uploaded blob."""
    bcfg = brumby_config(cfg)
    return {"family": "brumby", "cfg": list(bcfg)}, (bcfg, params)


def train_loss(cell: dict, interpret: bool):
    raise NotImplementedError(
        "no cell trains the brumby configuration: four layers and an eighth "
        "of the vocabulary are 1.52e9 parameters, 24 GB at 16 B a parameter"
    )


# ── the counts ───────────────────────────────────────────────────────────


def layer_params(cfg: dict) -> int:
    """A layer's parameters in the published form (``bg``, the assumed
    bias of ``num_key_value_heads`` numbers, is counted apart)."""
    return sum(
        int(np.prod(s)) for name, (s, _, _) in layer_shapes(cfg).items()
        if name != "bg"
    )


def gate_biases(cfg: dict) -> int:
    return cfg["num_hidden_layers"] * cfg["num_key_value_heads"]


def param_count(cfg: dict) -> int:
    """Every parameter of the published form held here: the layers,
    embedding, head, last norm."""
    return (
        cfg["num_hidden_layers"] * layer_params(cfg)
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    )


def weights_bytes(cfg: dict, itemsize: int = 2) -> int:
    return param_count(cfg) * itemsize


def monomials(cfg: dict) -> int:
    """Distinct products ``x_a x_b`` (``a <= b``) of a head's ``head_dim``
    numbers: what a degree-2 feature map has to hold (the program's lays
    out ``(head_dim / 2 + 1) head_dim``: 0.8% more at 128)."""
    dh = cfg["head_dim"]
    return dh * (dh + 1) // 2


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return 0


def state_bytes_per_slot(cfg: dict) -> int:
    """A slot's state as the mathematics needs it: a float32 ``[monomials,
    head_dim]`` matrix and a normaliser of ``monomials`` a K/V head, every
    layer."""
    per_head = monomials(cfg) * (cfg["head_dim"] + 1) * 4
    return cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * per_head


CHUNK = 128  # positions a chunk of the program's chunked form


def retention_chunk_flops(cfg: dict) -> float:
    """Operations the chunked form needs for one position of one layer,
    every head: against the state, each query head's read (``2 monomials
    d_v``) and each K/V head's update (the same); inside the chunk, the
    causal half of the ``[CHUNK, CHUNK]`` scores and of their product with
    ``v``, a query head (``2 CHUNK d``). The feature map's own products,
    the decays and the normaliser are not counted."""
    H, G, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return float((H + G) * 2 * monomials(cfg) * dh + H * 2 * CHUNK * dh)


def retention_chunk_bytes(cfg: dict) -> float:
    """Bytes the chunked form has to move for one position of one layer:
    ``q``, ``k``, ``v`` in and ``y`` out, float32 as the program holds
    them, and the gate. The state stays on the chip over a prompt."""
    H, G, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return float(4 * (2 * H * dh + 2 * G * dh + G))


def retention_step_bytes(cfg: dict) -> float:
    """Bytes one decode step has to move for one live row of one layer: the
    row's state read once and written once. (``q``, ``k``, ``v`` and ``y``
    are a thousandth of it.)"""
    return 2.0 * state_bytes_per_slot(cfg) / cfg["num_hidden_layers"]


def forward_flops_per_position(cfg: dict, context: float = 0.0) -> float:
    """Operations one position of a forward needs: 2 a matmul parameter it
    passes through (the layers' matrices, the head) and, a layer, the
    recurrence against the state (``retention_chunk_flops`` less its
    within-chunk part: what a decode step does too). ``context`` moves
    nothing: the state is as large at the first position as at the
    last."""
    H, dh = cfg["num_attention_heads"], cfg["head_dim"]
    mats = sum(
        int(np.prod(s)) for name, (s, _, _) in layer_shapes(cfg).items()
        if name in MATRICES
    )
    recurrence = retention_chunk_flops(cfg) - H * 2 * CHUNK * dh
    return (
        2.0 * cfg["hidden_size"] * cfg["vocab_size"]
        + cfg["num_hidden_layers"] * (2.0 * mats + recurrence)
    )


def dense_step_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes a decode step reads whatever the batch: every layer and the
    head (the embedding is gathered by row)."""
    return itemsize * (
        cfg["num_hidden_layers"] * layer_params(cfg)
        + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    )


def decode_step_bytes(cfg: dict, live_rows: int, weights_itemsize: int = 2) -> float:
    """Bytes one decode step has to move through HBM: the weights, and
    each live row's state read and written."""
    return dense_step_bytes(cfg, weights_itemsize) + 2.0 * live_rows * state_bytes_per_slot(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward for one token: three times the forward's (no
    cell trains this configuration; the contract asks for the function)."""
    return 3.0 * forward_flops_per_position(cfg)
