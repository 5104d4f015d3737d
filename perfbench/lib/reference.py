"""The plain reference: a GPT-2-style decoder in straightforward
``jax.numpy``, float32, matmuls at ``highest`` precision, full causal
attention, no kernel, no cache, no batching tricks. It imports nothing of
the program and takes nothing the program made: weights come from
``--seed`` through :func:`make_weights`, which is also where the program's
own copy comes from.

The block is the one ``pygrid_tpu.models.transformer`` expresses and the
configuration files describe: learned positions, pre-LayerNorm, unbiased
q/k/v/o projections, biased tanh-GELU MLP, tied output head. Where that
departs from the published model (no attention biases, tanh GELU, the
norm's epsilon) the configuration file says so.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: a layer's leaves, in the order of the program's flat parameter list
LAYER_LEAVES = (
    "ln1_s", "ln1_b", "wq", "wk", "wv", "wo",
    "ln2_s", "ln2_b", "w1", "b1", "w2", "b2",
)
MATRICES = ("wq", "wk", "wv", "wo", "w1", "w2")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed % (2**31 - 1)), seed // (2**31 - 1)
    )


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
    return _make(seed_key(seed), _items(cfg), dtype, False)


def make_program_params(seed: int, cfg: dict, dtype: str) -> list:
    """The same values as the program's flat parameter list (``embed, pos,
    12 leaves a layer, ln_f``), in ``dtype``. One jitted call."""
    return _make(seed_key(seed), _items(cfg), dtype, True)


def stack_program_params(flat, cfg: dict) -> dict:
    """The program's flat list as the reference's stacked dict, float32."""
    n = len(LAYER_LEAVES)
    out = {"embed": flat[0], "pos": flat[1], "lnf_s": flat[-2], "lnf_b": flat[-1]}
    for j, name in enumerate(LAYER_LEAVES):
        out[name] = jnp.stack(
            [flat[2 + layer * n + j] for layer in range(cfg["n_layer"])]
        )
    return {k: jnp.asarray(v, jnp.float32) for k, v in out.items()}


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


def loss(w: dict, X, y, cfg: dict, dtype=jnp.float32):
    """Mean token cross-entropy of targets ``y`` under inputs ``X``."""
    z = logits(w, X, cfg, dtype)
    logp = jax.nn.log_softmax(z, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


def sgd_steps(w: dict, X, y, lr: float, cfg: dict, steps: int, chunk: int,
              dtype=jnp.float32):
    """``steps`` of plain SGD on the one batch ``X, y`` (``[N, T]`` rows, all
    of equal length, so the mean over rows is the mean over tokens), the
    gradient accumulated over blocks of ``chunk`` rows so that it fits.
    Returns the weights after the last step and each step's loss, taken
    before that step's update. A control passes ``dtype``: weights, the
    arithmetic and the update then all round to it."""
    n = X.shape[0]
    assert n % chunk == 0, (n, chunk)
    Xc = X.reshape(n // chunk, chunk, -1)
    yc = y.reshape(n // chunk, chunk, -1)
    grad = jax.value_and_grad(lambda w, a, b: loss(w, a, b, cfg, dtype))

    @jax.jit
    def step(w):
        def one(carry, ab):
            total, acc = carry
            val, g = grad(w, *ab)
            return (total + val, jax.tree.map(jnp.add, acc, g)), None

        zero = jax.tree.map(jnp.zeros_like, w)
        (total, acc), _ = jax.lax.scan(
            one, (jnp.zeros((), jnp.float32), zero), (Xc, yc)
        )
        k = Xc.shape[0]
        new = jax.tree.map(
            lambda p, g: (p - jnp.asarray(lr, p.dtype) * (g / k)).astype(p.dtype),
            w, acc,
        )
        return new, total / k

    w = jax.tree.map(lambda p: p.astype(dtype), w)
    losses = []
    for _ in range(steps):
        w, val = step(w)
        losses.append(float(val))
    return w, losses


def leaf_norms(w: dict) -> dict:
    """L2 norm of each leaf, a stacked leaf giving one norm per layer:
    ``{name: [norms]}`` as numpy float64."""
    out = {}
    for name, leaf in w.items():
        x = jnp.asarray(leaf, jnp.float32)
        axes = tuple(range(1, x.ndim)) if name in LAYER_LEAVES else None
        out[name] = np.atleast_1d(
            np.asarray(jnp.sqrt(jnp.sum(x * x, axis=axes)), np.float64)
        )
    return out


def worst_leaf_gap(program: dict, reference: dict) -> float:
    """The widest gap between a leaf's norm in the program and in the
    reference, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some leaves are all but zero)."""
    ref_all = np.concatenate([reference[k] for k in sorted(reference)])
    floor = float(np.median(ref_all))
    worst = 0.0
    for name in reference:
        scale = np.maximum(reference[name], floor)
        worst = max(worst, float(np.max(np.abs(program[name] - reference[name]) / scale)))
    return worst


def fake_quant_int8(w: dict) -> dict:
    """The serving control's weights: every matrix and the embedding
    rounded to int8 with one scale per output channel (per row for the
    embedding), held as the float32 values those int8 codes stand for."""
    out = dict(w)
    for name in MATRICES + ("embed",):
        x = w[name]
        axis = -2 if name in MATRICES else -1
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        out[name] = jnp.round(x / scale) * scale
    return out


def served_gaps(w: dict, cfg: dict, prompt: np.ndarray, served: np.ndarray,
                pad_to: int, control: dict | None = None):
    """For one served request, the gap at each served position between the
    reference's best logit and the logit of the served token (teacher
    forced on what was served). With ``control`` weights, also the gap of
    the token the control puts first at each of those positions."""
    p_len, n_new = prompt.shape[-1], served.shape[-1]
    seq = np.zeros((1, pad_to), np.int32)
    seq[0, :p_len] = prompt.reshape(-1)
    seq[0, p_len : p_len + n_new - 1] = served.reshape(-1)[:-1]
    # causal: padding after the sequence cannot reach back into it
    rows = _logit_rows(w, jnp.asarray(seq), _items(cfg), cfg["layer_norm_epsilon"])
    rows = np.asarray(rows[0, p_len - 1 : p_len - 1 + n_new])
    best = rows.max(-1)
    gaps = best - rows[np.arange(n_new), served.reshape(-1)]
    if control is None:
        return gaps, None
    crow = _logit_rows(control, jnp.asarray(seq), _items(cfg), cfg["layer_norm_epsilon"])
    picks = np.asarray(crow[0, p_len - 1 : p_len - 1 + n_new]).argmax(-1)
    return gaps, best - rows[np.arange(n_new), picks]


@functools.partial(jax.jit, static_argnames=("cfg_items", "eps"))
def _logit_rows(w, seq, cfg_items, eps):
    cfg = dict(cfg_items, layer_norm_epsilon=eps)
    return logits(w, seq, cfg)
