"""What the plain reference is for any decoder that maps tokens to logits:
the seed's key, the loss, plain SGD, the norms of leaves and the gaps of
served tokens, all built on an architecture's ``logits`` in straightforward
``jax.numpy``, float32, matmuls at ``highest`` precision, no kernel, no
cache, no batching tricks. The block itself, its weights from ``--seed``
and what the program takes of them are the adapter's,
``models/<model_type>.py`` (``spec.load_model``); ``model`` below is that
module. Nothing here imports the program or takes anything it made.

``served_gaps`` here is the comparison of a CAUSAL decoder that makes one
token a row a step: teacher forcing, shifted by one, one state a position.
The runner never calls it; an adapter's own ``served_gaps`` does, where
that is true of its architecture. Where a served token was chosen from
another state (its own position's logits, in a block partly masked at the
forward that revealed it), the adapter replays the states the answer
names instead, in float32 at ``highest`` precision, and returns one gap a
served token.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed % (2**31 - 1)), seed // (2**31 - 1)
    )


def loss(model, w, X, y, cfg: dict, dtype=jnp.float32):
    """Mean token cross-entropy of targets ``y`` under inputs ``X``."""
    z = model.logits(w, X, cfg, dtype)
    logp = jax.nn.log_softmax(z, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


def sgd_steps(model, w, X, y, lr: float, cfg: dict, steps: int, chunk: int,
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
    grad = jax.value_and_grad(lambda w, a, b: loss(model, w, a, b, cfg, dtype))

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


def leaf_norms(w: dict, stacked) -> dict:
    """L2 norm of each leaf, a leaf named in ``stacked`` (layers on axis 0)
    giving one norm per layer: ``{name: [norms]}`` as numpy float64."""
    out = {}
    for name, leaf in w.items():
        x = jnp.asarray(leaf, jnp.float32)
        axes = tuple(range(1, x.ndim)) if name in stacked else None
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


def int8_values(x, axis: int):
    """``x`` rounded to int8 with one scale along ``axis``, held as the
    float32 values those int8 codes stand for (the serving control)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    return jnp.round(x / scale) * scale


def served_gaps(model, w, cfg: dict, prompt: np.ndarray, served: np.ndarray,
                pad_to: int, control=None):
    """For one served request of a causal decoder (``model`` its adapter,
    or the adapter's ``logits`` alone), the gap at each served position
    between the reference's best logit and the logit of the served token
    (teacher forced on what was served). With ``control`` weights, also the
    gap of the token the control puts first at each of those positions."""
    logits = getattr(model, "logits", model)
    p_len, n_new = prompt.shape[-1], served.shape[-1]
    seq = np.zeros((1, pad_to), np.int32)
    seq[0, :p_len] = prompt.reshape(-1)
    seq[0, p_len : p_len + n_new - 1] = served.reshape(-1)[:-1]
    # causal: padding after the sequence cannot reach back into it
    cfg_json = json.dumps(cfg, sort_keys=True)  # hashable, nested groups and all
    rows = _logit_rows(w, jnp.asarray(seq), logits, cfg_json)
    rows = np.asarray(rows[0, p_len - 1 : p_len - 1 + n_new])
    best = rows.max(-1)
    gaps = best - rows[np.arange(n_new), served.reshape(-1)]
    if control is None:
        return gaps, None
    crow = _logit_rows(control, jnp.asarray(seq), logits, cfg_json)
    picks = np.asarray(crow[0, p_len - 1 : p_len - 1 + n_new]).argmax(-1)
    return gaps, best - rows[np.arange(n_new), picks]


@functools.partial(jax.jit, static_argnames=("logits", "cfg_json"))
def _logit_rows(w, seq, logits, cfg_json):
    return logits(w, seq, json.loads(cfg_json))
