"""The adapter of ``"model_type": "sdar_moe"`` (contract: ``models/gpt2.py``'s
docstring and ``spec.MODEL_CONTRACT``): the block-diffusion decoder with
routed experts of the SDAR family, written from the published
``config.json`` and the family's modelling code, in plain ``jax.numpy``.

For hidden ``h [T, hidden_size]``, position ``i`` in block ``i // 4``::

    a = rms(h; norm_in);  q = a W_q -> [T, num_attention_heads, head_dim]
    k = a W_k, v = a W_v -> [T, num_key_value_heads, head_dim], no biases
    q = rms_head_dim(q; q_norm), k = rms_head_dim(k; k_norm)
    rotary on all head_dim dimensions, rotate-half, theta rope_theta, at
      the absolute position
    scores q.k / sqrt(head_dim), each K/V head under its query heads,
      mask M[i, j] = 1 iff j // 4 <= i // 4, softmax in float32
    h += o W_o
    m = rms(h; norm_ff);  r = softmax(m W_r) [T, num_experts]
    S = the num_experts_per_tok largest of r, p = r_S / sum(r_S)
    h += sum_{e in S} p_e W_down,e (silu(W_gate,e m) * W_up,e m)
    logits = rms(h; norm_f) W_head                     (an untied head)
    rms(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w

Generation (the family's published script; greedy): ``total = ceil((P +
n_new) / 4) * 4``. The prompt's whole blocks are committed as they are.
Each later block starts with its prompt tail known and the rest MASKED
(the embedding row of ``mask_token_id`` stands there). A denoising forward
runs the sequence up to and with the block and reveals, of the positions
still masked, the ``min(4 / denoising_steps, masked)`` whose own logits'
softmax maximum is largest (the earlier at a tie), each with its argmax;
when none is masked the block is committed and the next begins. Tokens
past ``n_new`` in the last block are made and dropped. ``generate`` below
is that loop in plain Python over ``logits``.

The reference (``logits``) computes exactly the above, float32 at
``highest`` matmul precision, every expert over every position and the
router's choice applied as a weight (zero for an expert not chosen), no
cache, no kernel, and imports nothing of the program. The weights are HELD
in the type they are stored in (bf16 for the served configuration: 8.7 GB)
and raised to float32 a layer at a time: 4.36e9 float32 parameters would
be 17.4 GB of a 16 GB chip; an expert's matrices are raised where the
loop over the experts reaches it. The int8 control is therefore not a
second copy either: ``control_weights`` wraps the same arrays, and a
matrix is rounded where it is raised.

``served_gaps`` replays the states an answer names. ``reveal_step`` says
at which forward of its block each token was revealed; all of a request's
blocks at forward index ``s`` are replayed in ONE forward by the family's
training layout: the committed sequence followed by its noised copy (a
position revealed before ``s`` known, the rest masked), each noised block
attending the clean blocks before it and itself, both halves at the same
absolute positions. So a request costs at most four reference forwards.

Departures from the published model: none in the mathematics; the weights
are random from ``--seed`` and the served context is the deployment's.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from lib import reference

BLOCK = 4
EXPERT_MATRICES = ("w_gate", "w_up", "w_down")
MATRICES = ("wq", "wk", "wv", "wo", "router") + EXPERT_MATRICES
#: the keys of the published config the mathematics reads
KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size", "num_experts",
    "num_experts_per_tok", "moe_intermediate_size", "rope_theta",
)


def tiny(cfg: dict) -> dict:
    """Two layers, 8 experts top-2, four query heads on two K/V heads of
    16, the mask token the vocabulary's last id."""
    deployment = dict(cfg["deployment"], served_context=128)
    return dict(
        num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, vocab_size=128, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32,
        assumed=dict(cfg["assumed"], mask_token_id=127),
        deployment=deployment,
    )


def _supported(cfg: dict) -> None:
    """What of the family this adapter (and the program) expresses."""
    want = {
        "attention_bias": False, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "norm_topk_prob": True, "hidden_act": "silu",
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "rope_scaling": None, "use_sliding_window": False,
    }
    off = {k: cfg.get(k) for k, v in want.items() if cfg.get(k, v) != v}
    if off:
        raise ValueError(f"the sdar_moe adapter does not express {off}")


def mask_id(cfg: dict) -> int:
    return int(cfg["assumed"]["mask_token_id"])


# ── the weights ──────────────────────────────────────────────────────────


def layer_shapes(cfg: dict) -> dict:
    """Leaf -> (shape, scale, mean) of one layer: matrices ``[in, out]`` at
    fan-in scale (a layer's experts stacked on axis 0), norms 1 +- 0.05."""
    d, dh, f = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"]
    H, G, E = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["num_experts"]
    norm = lambda width: ((width,), 0.05, 1.0)  # noqa: E731
    return {
        "norm_in": norm(d), "wq": ((d, H * dh), d**-0.5, 0.0),
        "wk": ((d, G * dh), d**-0.5, 0.0), "wv": ((d, G * dh), d**-0.5, 0.0),
        "q_norm": norm(dh), "k_norm": norm(dh),
        "wo": ((H * dh, d), (H * dh) ** -0.5, 0.0), "norm_ff": norm(d),
        "router": ((d, E), d**-0.5, 0.0), "w_gate": ((E, d, f), d**-0.5, 0.0),
        "w_up": ((E, d, f), d**-0.5, 0.0), "w_down": ((E, f, d), f**-0.5, 0.0),
    }


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _layer(key, cfg_items, dtype):
    """One layer's leaves from its key, a jitted call a layer (all 4.36e9
    values in one call would hold their float32 draws alive at once)."""
    cfg, out = dict(cfg_items), {}
    for j, (name, (shape, scale, mean)) in enumerate(layer_shapes(cfg).items()):
        leaf = mean + scale * jax.random.normal(
            jax.random.fold_in(key, j), shape, jnp.float32
        )
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


def make_weights(seed: int, cfg: dict, dtype: str) -> dict:
    """The weights, held in ``dtype`` (module docstring); the reference and
    the program take the same layout."""
    key, items = reference.seed_key(seed), _items(cfg)
    return dict(
        _globals(key, items, dtype),
        layers=[
            _layer(jax.random.fold_in(key, 3 + i), items, dtype)
            for i in range(cfg["num_hidden_layers"])
        ],
    )


def make_program_params(seed: int, cfg: dict, dtype: str) -> dict:
    """The same values as the program takes them. A program without this
    family (the parent of the PR that added it) is refused here, before
    8.7 GB are drawn for a node that cannot host them."""
    import importlib.util

    if importlib.util.find_spec("pygrid_tpu.models.sdar_moe") is None:
        raise RuntimeError(
            "the program beside this benchmark has no pygrid_tpu.models."
            "sdar_moe: it cannot host model_type 'sdar_moe'"
        )
    return make_weights(seed, cfg, dtype)


def to_reference(params: dict, cfg: dict) -> dict:
    return params


# ── the mathematics ──────────────────────────────────────────────────────


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """``x`` [T, heads, dh] at ``positions`` [T]: rotate-half."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=x.dtype) / half)
    angle = positions.astype(x.dtype)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _attention(x, lw, cfg, positions, mask):
    T = x.shape[0]
    H, G, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = _rope(_rms((x @ lw["wq"]).reshape(T, H, dh), lw["q_norm"], eps), positions, theta)
    k = _rope(_rms((x @ lw["wk"]).reshape(T, G, dh), lw["k_norm"], eps), positions, theta)
    v = (x @ lw["wv"]).reshape(T, G, dh)
    s = jnp.einsum("qgrd,kgd->grqk", q.reshape(T, G, H // G, dh), k) * dh**-0.5
    s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    a = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1), v)
    return a.reshape(T, H * dh) @ lw["wo"]


def _matrix(x, dtype, int8: bool):
    """A stored matrix in ``dtype``; the control's rounded to int8 with
    one scale per output channel first."""
    if int8:
        x = reference.int8_values(x.astype(jnp.float32), -2)
    return x.astype(dtype)


def _experts(m, lw, cfg, int8: bool):
    """Every expert over every position, one expert at a time (its three
    matrices raised to ``m``'s type there); an expert the router did not
    choose for a position weighs zero there."""
    k = cfg["num_experts_per_tok"]
    r = jax.nn.softmax(m @ lw["router"], -1)
    top, idx = jax.lax.top_k(r, k)
    weight = jnp.zeros_like(r).at[jnp.arange(m.shape[0])[:, None], idx].set(
        top / top.sum(-1, keepdims=True)
    )

    def one(total, e):
        gate, up, down = (_matrix(x, m.dtype, int8) for x in e[:3])
        return total + e[3][:, None] * ((jax.nn.silu(m @ gate) * (m @ up)) @ down), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(m), (lw["w_gate"], lw["w_up"], lw["w_down"], weight.T)
    )
    return out


def _raise(lw: dict, dtype, int8: bool) -> dict:
    """A layer's leaves outside its experts in ``dtype``; the experts'
    stay as they are stored until the loop over them."""
    return {
        name: x if name in EXPERT_MATRICES
        else _matrix(x, dtype, int8 and name in MATRICES)
        for name, x in lw.items()
    }


def hidden(w: dict, tokens, positions, mask, cfg: dict, dtype=jnp.float32):
    """Final hidden states ``[T, hidden_size]`` after the last norm, for
    ONE sequence ``tokens`` [T] at ``positions`` [T] under ``mask`` [T, T].
    A layer's weights are raised to ``dtype`` where the layer uses them."""
    eps, int8 = cfg["rms_norm_eps"], "int8" in w
    w = w.get("int8", w)
    embed = w["embed"]
    if int8:
        embed = reference.int8_values(embed.astype(jnp.float32), -1)
    h = embed[tokens].astype(dtype)
    for lw in w["layers"]:
        lw = _raise(lw, dtype, int8)
        h = h + _attention(_rms(h, lw["norm_in"], eps), lw, cfg, positions, mask)
        h = h + _experts(_rms(h, lw["norm_ff"], eps), lw, cfg, int8)
    return _rms(h, w["norm_f"].astype(dtype), eps)


def _head(w: dict, h, dtype):
    return h @ _matrix(w.get("int8", w)["head"], dtype, "int8" in w)


def block_mask(T: int):
    at = jnp.arange(T) // BLOCK
    return at[None, :] <= at[:, None]


def logits(w: dict, tokens, cfg: dict, dtype=jnp.float32):
    """``[B, T, vocab]``: every position's own logits under the
    block-causal mask (a masked position holds ``mask_token_id``)."""
    T = tokens.shape[-1]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _head(w, hidden(w, row, jnp.arange(T), block_mask(T), cfg, dtype), dtype)
            for row in tokens
        ])


def schedule(masked: int, denoising_steps: int) -> int:
    """How many positions a denoising forward reveals of ``masked``."""
    return min(BLOCK // int(denoising_steps), masked)


@functools.partial(jax.jit, static_argnames=("cfg_json",))
def _sequence_rows(w, tokens, cfg_json):
    return logits(w, tokens[None], json.loads(cfg_json))[0]


def generate(w: dict, cfg: dict, prompt: np.ndarray, n_new: int,
             denoising_steps: int = BLOCK, pad_to: int | None = None) -> dict:
    """What a door answers, by the plain loop over ``logits``: ``tokens``,
    ``reveal_step`` and the last block's dropped tail. ``prompt``: [P].
    Every forward runs the whole sequence at one length (``pad_to``, or
    the request's ``total``): what stands after the current block is seen
    by nothing in or before it."""
    prompt = np.asarray(prompt).reshape(-1)
    P, mid = len(prompt), mask_id(cfg)
    total = -(-(P + n_new) // BLOCK) * BLOCK
    cfg_json = json.dumps(cfg, sort_keys=True)
    seq = np.zeros(max(total, pad_to or 0), np.int64)
    seq[:P] = prompt
    masked = np.arange(len(seq)) >= P
    step = np.full(len(seq), -1)
    for a in range(P // BLOCK * BLOCK, total, BLOCK):
        forward = 0
        while masked[a : a + BLOCK].any():
            state = np.where(masked, mid, seq)
            rows = np.asarray(
                _sequence_rows(w, jnp.asarray(state), cfg_json)[a : a + BLOCK]
            )
            prob = jax.nn.softmax(rows, -1).max(-1)
            conf = np.where(masked[a : a + BLOCK], np.asarray(prob), -1.0)
            take = schedule(int(masked[a : a + BLOCK].sum()), denoising_steps)
            for at in np.argsort(-conf, kind="stable")[:take]:
                seq[a + at], step[a + at] = rows[at].argmax(), forward
                masked[a + at] = False
            forward += 1
    end = P + n_new
    return {
        "tokens": seq[None, P:end].tolist(), "reveal_step": step[None, P:end].tolist(),
        "dropped_tokens": seq[None, end:total].tolist(),
        "dropped_reveal_step": step[None, end:total].tolist(),
    }


@functools.partial(jax.jit, static_argnames=("cfg_json",))
def _replay_rows(w, clean, noised, cfg_json):
    """The noised half's logits ``[T, vocab]`` of the training layout:
    ``clean`` [T] then ``noised`` [T], both at positions ``0 .. T - 1``."""
    cfg = json.loads(cfg_json)
    T = clean.shape[0]
    blk = jnp.arange(T) // BLOCK
    earlier = blk[None, :] < blk[:, None]
    mask = jnp.block([
        [block_mask(T), jnp.zeros((T, T), bool)],
        [earlier, blk[None, :] == blk[:, None]],
    ])
    positions = jnp.concatenate([jnp.arange(T)] * 2)
    with jax.default_matmul_precision("highest"):
        h = hidden(w, jnp.concatenate([clean, noised]), positions, mask, cfg)
        return _head(w, h[T:], jnp.float32)


def served_gaps(w, cfg: dict, request: dict, answer: dict, pad_to: int, control=None):
    """One gap a served token: the reference's best logit at the token's
    own position, in the state of the forward that revealed it, less the
    served token's. ``ValueError`` where the answer's fields do not fit
    the request, or reveal other counts than the schedule."""
    prompt = np.asarray(request["prompt"]).reshape(-1)
    P, n_new = len(prompt), int(request["n_new"])
    steps = int(request["fields"].get("denoising_steps", BLOCK))
    total = -(-(P + n_new) // BLOCK) * BLOCK
    seq = np.concatenate([
        prompt, np.asarray(answer["tokens"]).reshape(-1),
        np.asarray(answer["dropped_tokens"]).reshape(-1),
    ]).astype(np.int64)
    at = np.concatenate([
        np.full(P, -1), np.asarray(answer["reveal_step"]).reshape(-1),
        np.asarray(answer["dropped_reveal_step"]).reshape(-1),
    ]).astype(np.int64)
    if seq.shape != (total,) or at.shape != (total,):
        raise ValueError(f"{seq.shape[0]} tokens and {at.shape[0]} steps for {total} positions")
    if seq.min() < 0 or seq.max() >= cfg["vocab_size"] or at[P:].min() < 0:
        raise ValueError("a token or a reveal_step out of range")
    start = P // BLOCK * BLOCK
    for a in range(start, total, BLOCK):
        left = int((at[a : a + BLOCK] >= 0).sum())
        for forward in range(int(at[a : a + BLOCK].max()) + 1):
            shown = int((at[a : a + BLOCK] == forward).sum())
            if shown != schedule(left, steps):
                raise ValueError(
                    f"block at {a}: forward {forward} revealed {shown} of {left}, "
                    f"the schedule says {schedule(left, steps)}"
                )
            left -= shown
    if total > pad_to:
        raise ValueError(f"{total} positions do not fit the sample's {pad_to}")
    cfg_json = json.dumps(cfg, sort_keys=True)
    clean = np.zeros(pad_to, np.int64)
    clean[:total] = seq
    gaps, cgaps = np.zeros(n_new), np.zeros(n_new)
    clean_d = jnp.asarray(clean)
    below = lambda rows, picks: np.asarray(  # noqa: E731
        rows.max(-1) - jnp.take_along_axis(rows, picks[:, None], 1)[:, 0]
    )
    for forward in range(int(at.max()) + 1):
        # the state every block was in at its forward of this index
        noised = np.where((at >= forward) & (np.arange(total) >= start), mask_id(cfg), seq)
        state = clean.copy()
        state[:total] = noised
        # [pad_to, vocab] stays on the device: only a gap a position leaves it
        rows = _replay_rows(w, clean_d, jnp.asarray(state), cfg_json)
        here = P + np.flatnonzero(at[P : P + n_new] == forward)
        gaps[here - P] = below(rows, clean_d)[here]
        if control is not None:
            crow = _replay_rows(control, clean_d, jnp.asarray(state), cfg_json)
            cgaps[here - P] = below(rows, crow.argmax(-1))[here]
    return gaps, (cgaps if control is not None else None)


# ── the controls ─────────────────────────────────────────────────────────


def leaf_norms(w: dict) -> dict:
    """L2 norm of every leaf, by its path (no cell trains this
    configuration; the contract asks for the function)."""
    flat = jax.tree_util.tree_flatten_with_path(w)[0]
    return reference.leaf_norms(
        {jax.tree_util.keystr(path): leaf for path, leaf in flat}, ()
    )


def control_weights(w: dict) -> dict:
    """The serving control: every matrix, the experts' included, the
    embedding and the head rounded to int8 with one scale per output
    channel (per row for the embedding); norms stay. The SAME arrays in a
    wrapper: ``hidden`` rounds a matrix where it raises it, so the control
    costs no second 8.7 GB."""
    return {"int8": w}


# ── the program ──────────────────────────────────────────────────────────


def sdar_config(cfg: dict):
    from pygrid_tpu.models.sdar_moe import SdarConfig

    _supported(cfg)
    return SdarConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_layers=cfg["num_hidden_layers"], n_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["moe_intermediate_size"],
        max_len=cfg["deployment"]["served_context"], mask_id=mask_id(cfg),
        rope_theta=int(cfg["rope_theta"]),
    )


def hosted(cfg: dict, params) -> tuple:
    """What the node's ``HostedModel`` takes: the bundle's ``model`` and the
    parsed form (``generation_cache``) the first request would otherwise
    make from an uploaded blob."""
    scfg = sdar_config(cfg)
    return {"family": "sdar_moe", "cfg": list(scfg)}, (scfg, params)


def train_loss(cell: dict, interpret: bool):
    raise NotImplementedError(
        "no cell trains the sdar_moe configuration: float32 parameters alone "
        "are 17.4 GB of one chip's 16"
    )


# ── the counts ───────────────────────────────────────────────────────────


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_layer_params(cfg: dict) -> int:
    """A layer outside its experts: the attention's matrices, the norms
    and the router."""
    shapes = layer_shapes(cfg)
    return sum(
        int(np.prod(s)) for name, (s, _, _) in shapes.items()
        if name not in ("w_gate", "w_up", "w_down")
    )


def param_count(cfg: dict) -> int:
    layer = dense_layer_params(cfg) + cfg["num_experts"] * expert_params(cfg)
    return (
        cfg["num_hidden_layers"] * layer
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    )


def forward_flops_per_position(cfg: dict, context: int) -> float:
    """Operations one position of a forward needs: 2 a matmul parameter it
    passes through (the attention's four matrices, the router, its
    ``num_experts_per_tok`` experts, the head) and, in each layer, ``4 x
    heads x head_dim`` per attended position."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = 2 * d * (H + G) * dh + d * cfg["num_experts"]
    experts = cfg["num_experts_per_tok"] * expert_params(cfg)
    per_layer = 2.0 * (attn + experts) + 4.0 * H * dh * context
    return cfg["num_hidden_layers"] * per_layer + 2.0 * d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward for one token of a ``seq_len``-token sequence:
    three times the forward's (no cell trains this configuration; the
    contract asks for the function)."""
    return 3.0 * forward_flops_per_position(cfg, (seq_len + 1) // 2)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return (
        2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
        * cfg["head_dim"] * itemsize
    )


def dense_forward_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes a forward reads whatever the routing: every layer outside its
    experts and the output head (the embedding is gathered by row)."""
    return itemsize * (
        cfg["num_hidden_layers"] * dense_layer_params(cfg)
        + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    )


def expert_ffn_bytes(cfg: dict, touched_pairs: float, itemsize: int = 2) -> float:
    """Bytes the grouped expert kernel has to read: each touched (layer,
    expert) pair's three matrices once."""
    return touched_pairs * expert_params(cfg) * itemsize


def expert_ffn_flops(cfg: dict, positions: float) -> float:
    """Operations of the expert FFN over ``positions`` positions of ONE
    layer: ``num_experts_per_tok`` experts each."""
    return 2.0 * positions * cfg["num_experts_per_tok"] * expert_params(cfg)
