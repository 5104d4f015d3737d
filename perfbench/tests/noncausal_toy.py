"""A toy decoder that is NOT causal, for the self-tests alone (it is kept
out of ``models/``: no configuration names it). It fills a block of
positions at a time: every position of the block sees the whole block,
known tokens and still-masked positions alike, and the committed tokens
before it; one position a forward is revealed, the most confident one,
with the token its own position's logits put first. So a served token was
chosen from a state that only the answer can name: which positions of its
block were known at the forward that revealed it.

It defines what ``lib/serving.check_served`` asks of an adapter
(``make_weights``, ``control_weights``, ``served_gaps``) and, in place of a
program, ``generate``, which answers a request as a door would: the tokens
and, beside them, ``reveal_step``. The block's width is the request's to
choose: ``request["fields"]["block"]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lib import reference

MAX_BLOCK = 8


def make_weights(seed: int, cfg: dict, dtype: str) -> dict:
    key, (V, d) = reference.seed_key(seed), (cfg["vocab_size"], cfg["width"])
    shapes = {"embed": (V + 1, d), "pos": (MAX_BLOCK, d), "mix": (d, d)}  # row V: the mask
    return {
        name: jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        .astype(dtype).astype(jnp.float32)
        for i, (name, shape) in enumerate(shapes.items())
    }


def control_weights(w: dict) -> dict:
    return {name: reference.int8_values(x, -1) for name, x in w.items()}


def block_logits(w: dict, before: np.ndarray, block: np.ndarray) -> np.ndarray:
    """``[len(block), V]``: each position of the block from the mean
    embedding of everything committed before it and of the block as it
    stands (``V`` where a position is still masked), and its own place."""
    with jax.default_matmul_precision("highest"):
        seen = jnp.concatenate([w["embed"][before], w["embed"][block]]).mean(0)
        h = jnp.tanh((seen + w["pos"][: len(block)]) @ w["mix"])
        return np.asarray(h @ w["embed"][:-1].T)


def _blocks(n_new: int, width: int):
    if not 1 <= width <= MAX_BLOCK:
        raise ValueError(f"a block of {width} positions")
    return [(a, min(a + width, n_new)) for a in range(0, n_new, width)]


def generate(w: dict, cfg: dict, prompt: np.ndarray, n_new: int, block: int) -> dict:
    """What a door would answer: ``tokens`` and ``reveal_step`` (the
    forward of its block, from 0, at which each token was revealed)."""
    V = cfg["vocab_size"]
    tokens, steps = np.full(n_new, V, np.int64), np.zeros(n_new, np.int64)
    for a, b in _blocks(n_new, block):
        for step in range(b - a):
            rows = block_logits(w, np.concatenate([prompt.reshape(-1), tokens[:a]]), tokens[a:b])
            masked = np.flatnonzero(tokens[a:b] == V)
            at = masked[rows[masked].max(-1).argmax()]
            tokens[a + at], steps[a + at] = rows[at].argmax(), step
    return {"tokens": tokens[None].tolist(), "reveal_step": steps[None].tolist()}


def served_gaps(w: dict, cfg: dict, request: dict, answer: dict, pad_to: int, control=None):
    """Replays the states the answer names: at each forward of each block,
    the positions revealed earlier are known and the rest masked; a token
    revealed at that forward is held against its own position's logits."""
    V, n_new = cfg["vocab_size"], request["n_new"]
    tokens = np.asarray(answer["tokens"], np.int64).reshape(-1)
    steps = np.asarray(answer["reveal_step"]).reshape(-1)  # KeyError: no such field
    if steps.shape != (n_new,):
        raise ValueError(f"reveal_step of {steps.shape} for {n_new} tokens")
    prompt = request["prompt"].reshape(-1)
    gaps, cgaps = np.zeros(n_new), np.zeros(n_new)
    for a, b in _blocks(n_new, int(request["fields"]["block"])):
        for step in np.unique(steps[a:b]):
            state = np.where(steps[a:b] < step, tokens[a:b], V)
            before = np.concatenate([prompt, tokens[:a]])
            rows = block_logits(w, before, state)
            picks = block_logits(control, before, state).argmax(-1) if control else None
            for at in np.flatnonzero(steps[a:b] == step):
                gaps[a + at] = rows[at].max() - rows[at, tokens[a + at]]
                if control:
                    cgaps[a + at] = rows[at].max() - rows[at, picks[at]]
    return gaps, (cgaps if control else None)
