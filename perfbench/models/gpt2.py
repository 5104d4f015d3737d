"""The adapter of ``"model_type": "gpt2"``: everything of the benchmark
that knows the GPT-2 block. ``spec.load_model`` finds it by the
configuration's ``model_type``; the runners ask it and name no key of a
block themselves.

An adapter is one file, ``models/<model_type>.py``, that defines the names
of ``spec.MODEL_CONTRACT``:

- the weights, from ``--seed``: ``make_weights(seed, cfg, dtype)`` (the
  reference's, any pytree holding the values of ``dtype``; how it holds
  them is the adapter's choice), ``make_program_params(seed, cfg, dtype)``
  (the same values as the program takes them), ``to_reference(params,
  cfg)`` (the program's form as the reference's);
- the mathematics: ``logits(w, tokens, cfg, dtype=float32) -> [B, T,
  vocab]`` in plain ``jax.numpy`` at ``highest`` precision, importing
  nothing of the program. ``lib/reference.py`` builds the loss and the SGD
  steps on it;
- the comparison that decides a serving cell's ``correct``:
  ``served_gaps(w, cfg, request, answer, pad_to, control=None) -> (gaps,
  control_gaps | None)``, one gap a served token (the reference's best
  logit less the served token's, in the state that token was chosen from)
  and, with ``control`` weights, the gap of the token the control puts
  first in each of those states. ``request`` is what was sent: ``prompt``
  (``int32[1, P]``), ``n_new`` and ``fields``, the mix's named fields of
  this request; ``answer`` what came back: ``tokens`` (``int64[1, n_new]``,
  shape and range already checked) and every other field the door
  returned. ``pad_to`` is a common length the runner's sample fits in, so
  that one compiled program serves it. A ``KeyError`` or ``ValueError``
  over the answer's fields counts the answer as malformed. A causal
  decoder that makes one token a row a step calls
  ``reference.served_gaps`` on its ``logits`` (below); any other replays
  the states the answer names (which positions were known at the forward
  that revealed each token), in float32 at ``highest`` precision;
- the controls: ``control_weights(w)`` (the serving control's weights: which
  leaves are matrices is the architecture's knowledge) and ``leaf_norms(w)``
  (which leaves are stacked by layer);
- the program, the only two places of ``perfbench/`` that import the
  program's model or a kernel: ``hosted(cfg, params) -> (model,
  generation_cache)`` for the node's ``HostedModel``, and ``train_loss(cell,
  interpret) -> loss_fn`` for ``make_fused_rounds``;
- the counts: ``train_flops_per_token(cfg, seq_len)``, and a later kernel's
  functions of its operations and bytes;
- the rehearsal size: ``tiny(cfg) -> dict`` of overrides for the self-tests.

Besides its ``model_type`` a configuration states ``vocab_size`` (the
traffic draws tokens below it) and, where a cell serves or trains it, the
``deployment`` group the runners read (``weights_dtype`` and ``env``;
``training.params_dtype``).

What ``lib/serving.py`` asks of the engine that ``node.ctx.serving.
engine_for`` builds from ``hosted``: ``warmup(lens)`` compiling every
program that prompts of those lengths can reach; ``_k``, an array to wait
on until the warm-up has run; ``_emit(slot, row, tok)``, which a self-test
wraps; and the ``/telemetry/serving`` row with ``kv_blocks_free``.

This block is the one ``pygrid_tpu.models.transformer`` expresses and the
configuration files describe: learned positions, pre-LayerNorm, unbiased
q/k/v/o projections, biased tanh-GELU MLP, tied output head. Where that
departs from the published model (no attention biases, tanh GELU, the
norm's epsilon) the configuration file says so.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from lib import reference

#: a layer's leaves, in the order of the program's flat parameter list
LAYER_LEAVES = (
    "ln1_s", "ln1_b", "wq", "wk", "wv", "wo",
    "ln2_s", "ln2_b", "w1", "b1", "w2", "b2",
)
MATRICES = ("wq", "wk", "wv", "wo", "w1", "w2")


def tiny(cfg: dict) -> dict:
    return dict(n_layer=2, n_embd=64, n_head=4, n_inner=256, vocab_size=128, n_positions=128)


# ── the weights ──────────────────────────────────────────────────────────


def _stacked(key, cfg: dict, dtype):
    """Every leaf, layers stacked on axis 0, drawn in float32 and rounded
    to ``dtype``: scales as ``transformer.init`` has them, and norms and
    biases off their neutral values so that each takes part."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    L, V, T = cfg["n_layer"], cfg["vocab_size"], cfg["n_positions"]
    shapes = {
        "embed": ((V, d), d**-0.5, 0.0), "pos": ((T, d), d**-0.5, 0.0),
        "ln1_s": ((L, d), 0.05, 1.0), "ln1_b": ((L, d), 0.02, 0.0),
        "wq": ((L, d, d), d**-0.5, 0.0), "wk": ((L, d, d), d**-0.5, 0.0),
        "wv": ((L, d, d), d**-0.5, 0.0), "wo": ((L, d, d), d**-0.5, 0.0),
        "ln2_s": ((L, d), 0.05, 1.0), "ln2_b": ((L, d), 0.02, 0.0),
        "w1": ((L, d, f), d**-0.5, 0.0), "b1": ((L, f), 0.02, 0.0),
        "w2": ((L, f, d), f**-0.5, 0.0), "b2": ((L, d), 0.02, 0.0),
        "lnf_s": ((d,), 0.05, 1.0), "lnf_b": ((d,), 0.02, 0.0),
    }
    out = {}
    for i, (name, (shape, scale, mean)) in enumerate(shapes.items()):
        leaf = mean + scale * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32
        )
        out[name] = leaf.astype(dtype)
    return out


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype", "flat"))
def _make(key, cfg_items, dtype, flat):
    w = _stacked(key, dict(cfg_items), jnp.dtype(dtype))
    if not flat:
        return {k: v.astype(jnp.float32) for k, v in w.items()}
    out = [w["embed"], w["pos"]]
    for layer in range(dict(cfg_items)["n_layer"]):
        out += [w[name][layer] for name in LAYER_LEAVES]
    return out + [w["lnf_s"], w["lnf_b"]]


def _items(cfg: dict):
    keys = ("n_embd", "n_inner", "n_layer", "n_head", "vocab_size", "n_positions")
    return tuple((k, int(cfg[k])) for k in keys)


def make_weights(seed: int, cfg: dict, dtype: str) -> dict:
    """The reference's weights: float32 arrays holding the values of
    ``dtype`` (the type the configuration stores them in), layers stacked.
    One jitted call on the device."""
    return _make(reference.seed_key(seed), _items(cfg), dtype, False)


def make_program_params(seed: int, cfg: dict, dtype: str) -> list:
    """The same values as the program's flat parameter list (``embed, pos,
    12 leaves a layer, ln_f``), in ``dtype``. One jitted call."""
    return _make(reference.seed_key(seed), _items(cfg), dtype, True)


def to_reference(flat, cfg: dict) -> dict:
    """The program's flat list as the reference's stacked dict, float32."""
    n = len(LAYER_LEAVES)
    out = {"embed": flat[0], "pos": flat[1], "lnf_s": flat[-2], "lnf_b": flat[-1]}
    for j, name in enumerate(LAYER_LEAVES):
        out[name] = jnp.stack(
            [flat[2 + layer * n + j] for layer in range(cfg["n_layer"])]
        )
    return {k: jnp.asarray(v, jnp.float32) for k, v in out.items()}


# ── the mathematics ──────────────────────────────────────────────────────


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def hidden(w: dict, tokens, cfg: dict, dtype=jnp.float32):
    """Final hidden states ``[B, T, n_embd]`` after the last norm. ``dtype``
    is float32 for the reference; a control passes a lower one, and every
    operation then runs and rounds in it."""
    B, T = tokens.shape
    H = cfg["n_head"]
    dh = cfg["n_embd"] // H
    eps = cfg["layer_norm_epsilon"]
    c = lambda x: x.astype(dtype)  # noqa: E731
    h = c(w["embed"])[tokens] + c(w["pos"])[:T]
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def block(h, lw):
        x = _ln(h, c(lw["ln1_s"]), c(lw["ln1_b"]), eps)
        q = (x @ c(lw["wq"])).reshape(B, T, H, dh)
        k = (x @ c(lw["wk"])).reshape(B, T, H, dh)
        v = (x @ c(lw["wv"])).reshape(B, T, H, dh)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh**-0.5
        s = jnp.where(causal, s, jnp.finfo(s.dtype).min)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        h = h + a.reshape(B, T, H * dh) @ c(lw["wo"])
        x = _ln(h, c(lw["ln2_s"]), c(lw["ln2_b"]), eps)
        m = jax.nn.gelu(x @ c(lw["w1"]) + c(lw["b1"]), approximate=True)
        return h + m @ c(lw["w2"]) + c(lw["b2"]), None

    h, _ = jax.lax.scan(block, h, {k: w[k] for k in LAYER_LEAVES})
    return _ln(h, c(w["lnf_s"]), c(w["lnf_b"]), eps)


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
    return reference.leaf_norms(w, LAYER_LEAVES)


def control_weights(w: dict) -> dict:
    """The serving control's weights: every matrix and the embedding
    rounded to int8 with one scale per output channel (per row for the
    embedding), held as the float32 values those int8 codes stand for."""
    out = dict(w)
    for name in MATRICES + ("embed",):
        out[name] = reference.int8_values(w[name], -2 if name in MATRICES else -1)
    return out


# ── the program ──────────────────────────────────────────────────────────


def transformer_config(cfg: dict):
    from pygrid_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["n_embd"], n_heads=cfg["n_head"],
        n_layers=cfg["n_layer"], d_ff=cfg["n_inner"], max_len=cfg["n_positions"],
    )


def hosted(cfg: dict, params) -> tuple:
    """What the node's ``HostedModel`` takes: the bundle's ``model`` and the
    parsed form (``generation_cache``) the first request would otherwise
    make from an uploaded blob."""
    tcfg = transformer_config(cfg)
    return {"family": "transformer", "cfg": list(tcfg)}, (tcfg, params)


def train_loss(cell: dict, interpret: bool):
    """``loss_fn(params, X, y)`` as ``make_fused_rounds`` takes it: the
    program's transformer over flash attention and the chunked CE head."""
    from pygrid_tpu.models import transformer
    from pygrid_tpu.parallel.pallas_attention import flash_attention

    return functools.partial(
        transformer.loss_and_acc,
        cfg=transformer_config(cell["config"]),
        attn_fn=functools.partial(flash_attention, interpret=interpret),
        compute_dtype=cell["config"]["deployment"]["training"]["compute_dtype"],
        ce_chunk=cell["traffic"]["ce_chunk"],
    )


# ── the counts ───────────────────────────────────────────────────────────


def matmul_params(cfg: dict) -> int:
    """Parameters that a token passes through a matrix multiplication
    with: the four attention projections and the two MLP matrices of every
    layer, and the tied output head. Embedding lookup, positions, norms and
    biases multiply nothing."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * f) + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward and backward passes require for one token of
    a ``seq_len``-token causal sequence: 6 per matmul parameter (2 forward,
    4 backward), plus attention. A token attends to ``(seq_len + 1) / 2``
    positions on average; QK^T and PV are ``2 * n_embd`` each per attended
    position forward, and twice that backward: ``12 * n_embd`` per attended
    position per layer. Recomputation (the flash backward's, the CE head's)
    is not counted."""
    attended = (seq_len + 1) / 2.0
    attn = cfg["n_layer"] * 12.0 * cfg["n_embd"] * attended
    return 6.0 * matmul_params(cfg) + attn
