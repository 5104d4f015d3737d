"""Decoder-only transformer — the long-context flagship model family.

The reference's model zoo stops at MNIST MLP/CNN (SURVEY.md §5.7); this adds
the transformer family the TPU framework needs for long-context work. Same
pure-functional convention as :mod:`pygrid_tpu.models.mlp`: ``init`` returns
a flat list of arrays (so the model drops into Plans, FedAvg rounds, and
State serde unchanged), ``make_training_step`` builds the
``(X, y, lr, *params) -> (loss, acc, *new_params)`` plan-traceable step.

The attention implementation is injectable: pass
``attn_fn=partial(ring_attention, mesh=mesh)`` (or ``ulysses_attention``)
from :mod:`pygrid_tpu.parallel.ring_attention` to run the same model
sequence-parallel over a mesh — the model code does not change.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from pygrid_tpu.parallel.ring_attention import attention


class TransformerConfig(NamedTuple):
    vocab: int = 128
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    max_len: int = 256

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


PARAMS_PER_LAYER = 12  # ln1(2) + attn(4) + ln2(2) + mlp(4)
N_GLOBAL = 4  # embed, pos, ln_f scale/bias


def init(key: jax.Array, cfg: TransformerConfig = TransformerConfig()) -> list[jax.Array]:
    """Flat param list: [embed, pos, (12 per layer)*n_layers, ln_f_s, ln_f_b].

    Output projection is tied to the embedding (logits = h @ embed.T).
    """
    d, h, f = cfg.d_model, cfg.n_heads, cfg.d_ff
    keys = iter(jax.random.split(key, 2 + 6 * cfg.n_layers))
    sd = d**-0.5
    params: list[jax.Array] = [
        jax.random.normal(next(keys), (cfg.vocab, d)) * sd,
        jax.random.normal(next(keys), (cfg.max_len, d)) * sd,
    ]
    for _ in range(cfg.n_layers):
        params += [jnp.ones((d,)), jnp.zeros((d,))]  # ln1
        for shape in ((d, d), (d, d), (d, d), (d, d)):  # wq wk wv wo
            params.append(jax.random.normal(next(keys), shape) * sd)
        params += [jnp.ones((d,)), jnp.zeros((d,))]  # ln2
        params += [
            jax.random.normal(next(keys), (d, f)) * sd,
            jnp.zeros((f,)),
            jax.random.normal(next(keys), (f, d)) * f**-0.5,
            jnp.zeros((d,)),
        ]
    params += [jnp.ones((d,)), jnp.zeros((d,))]  # final ln
    return params


def _cast(x: jax.Array, cd) -> jax.Array:
    """The ONE compute-dtype cast policy (None = no cast) — apply,
    features, the loss, and the custom CE head must all narrow operands
    identically or their numerics silently diverge."""
    return x.astype(cd) if cd is not None else x


def _ln(x, scale, bias, eps=1e-6):
    # norm statistics always in f32 — bf16 mean/variance drifts
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def apply(
    params: Sequence[jax.Array],
    tokens: jax.Array,
    cfg: TransformerConfig = TransformerConfig(),
    attn_fn: Callable | None = None,
    remat: bool | str = False,
    compute_dtype: Any | None = None,
) -> jax.Array:
    """Logits [B, L, vocab] for int tokens [B, L]; causal.

    ``remat=True`` wraps each block in ``jax.checkpoint`` — intra-block
    activations (QKV, attention internals, the d_ff MLP) are recomputed in
    the backward pass instead of held in HBM. Per-layer residuals are
    still stored, so memory remains O(layers·L·d) but with a ~12× smaller
    constant — the standard FLOPs-for-memory trade for long context.
    ``remat="dots"`` checkpoints with the ``dots_saveable`` policy
    instead: matmul outputs are kept (they are the FLOPs worth not
    re-paying) and only the cheap elementwise/norm intermediates are
    recomputed — a middle point that holds O(layers·L·(d + d_ff))
    activations but removes almost all recompute FLOPs.

    ``compute_dtype="bfloat16"`` runs the matmul path in bf16 (params
    stay float32; weights/activations cast at use — standard mixed
    precision, feeding the MXU its native dtype) while layer norms and
    the softmax/loss stay float32. On a v5e this roughly doubles
    training throughput at these sizes (bench_fed_transformer)."""
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else None

    def c(x: jax.Array) -> jax.Array:
        return _cast(x, cd)

    h = features(
        params, tokens, cfg, attn_fn, remat=remat,
        compute_dtype=compute_dtype,
    )
    # logits accumulate in f32 regardless of the compute dtype — vocab
    # softmax is where bf16 resolution actually bites
    return jnp.dot(
        c(h), c(params[0]).T, preferred_element_type=jnp.float32
    )


def features(
    params: Sequence[jax.Array],
    tokens: jax.Array,
    cfg: TransformerConfig = TransformerConfig(),
    attn_fn: Callable | None = None,
    remat: bool | str = False,
    compute_dtype: Any | None = None,
) -> jax.Array:
    """Final hidden states [B, L, d] (post ln_f, pre output projection).

    Split out of :func:`apply` so the loss can project to vocab logits
    in token chunks (:func:`loss_and_acc` ``ce_chunk``) without the full
    [B·L, vocab] tensor ever existing."""
    attn_fn = attn_fn or attention
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else None

    def c(x: jax.Array) -> jax.Array:
        return _cast(x, cd)

    embed, pos = params[0], params[1]
    B, L = tokens.shape
    h = c(embed[tokens] + pos[:L])
    idx = 2
    dh = cfg.d_model // cfg.n_heads

    def block(h, layer_params):
        (ln1_s, ln1_b, wq, wk, wv, wo, ln2_s, ln2_b, w1, b1, w2, b2) = (
            layer_params
        )
        with jax.named_scope("attention"):
            x = c(_ln(h, ln1_s, ln1_b))
            q = (x @ c(wq)).reshape(B, L, cfg.n_heads, dh)
            k = (x @ c(wk)).reshape(B, L, cfg.n_heads, dh)
            v = (x @ c(wv)).reshape(B, L, cfg.n_heads, dh)
            a = attn_fn(q, k, v, causal=True).reshape(B, L, cfg.d_model)
            h = h + c(a) @ c(wo)
        with jax.named_scope("mlp"):
            x = c(_ln(h, ln2_s, ln2_b))
            return h + c(jax.nn.gelu(x @ c(w1) + c(b1))) @ c(w2) + c(b2)

    if remat == "dots":
        block_fn = jax.checkpoint(
            block, policy=jax.checkpoint_policies.dots_saveable
        )
    elif remat:
        block_fn = jax.checkpoint(block)
    else:
        block_fn = block
    for _ in range(cfg.n_layers):
        h = block_fn(h, tuple(params[idx : idx + PARAMS_PER_LAYER]))
        idx += PARAMS_PER_LAYER
    return _ln(h, params[idx], params[idx + 1])


def _ce_head(h2, embed, y1, fwd_cd, bwd_cd):
    """Tied-embedding CE head with a narrow-dtype backward (custom VJP).

    Forward: operands cast to ``fwd_cd`` — the model's ``compute_dtype``
    (None = no cast), exactly what the plain ``apply`` path does —
    logits f32-accumulated, f32 log-sum-exp; the forward numerics match
    the plain path. Backward: logits are RECOMPUTED (the f32 [N, vocab]
    tensor is never a saved residual — at the flagship bench shape that
    residual is 537 MB) and ``dlogits = softmax - onehot`` and both
    matmul operands are cast to ``bwd_cd`` (bf16) before the two
    gradient matmuls, so they run as native-dtype MXU passes instead of
    mixed f32 ones. The cast costs bf16 resolution on the logits-
    gradient only — the standard mixed-precision trade the rest of the
    matmul path already makes.

    Returns ``(loss_sum, hit_sum)`` over the N tokens.
    """

    def cf(x):
        return _cast(x, fwd_cd)

    def fwd(h2, embed, y1):
        logits = jnp.dot(
            cf(h2), cf(embed).T, preferred_element_type=jnp.float32
        )
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        logit_y = jnp.take_along_axis(logits, y1[:, None], axis=-1)[:, 0]
        hits = jnp.sum((jnp.argmax(logits, -1) == y1).astype(jnp.float32))
        return (jnp.sum(lse - logit_y), hits), (h2, embed, y1, lse)

    def bwd(res, ct):
        g_loss, _ = ct  # hit_sum is not differentiable
        h2, embed, y1, lse = res
        hb, eb = h2.astype(bwd_cd), embed.astype(bwd_cd)
        logits = jnp.dot(
            cf(h2), cf(embed).T, preferred_element_type=jnp.float32
        )
        p = jnp.exp(logits - lse[:, None])
        onehot = jax.nn.one_hot(y1, embed.shape[0], dtype=jnp.float32)
        dlogits = ((p - onehot) * g_loss).astype(bwd_cd)
        dh = jnp.dot(dlogits, eb, preferred_element_type=jnp.float32)
        dembed = jnp.dot(
            dlogits.T, hb, preferred_element_type=jnp.float32
        )
        import numpy as _np

        dy = _np.zeros(y1.shape, dtype=jax.dtypes.float0)
        return dh.astype(h2.dtype), dembed.astype(embed.dtype), dy

    f = jax.custom_vjp(
        lambda h2, embed, y1: fwd(h2, embed, y1)[0]
    )
    f.defvjp(fwd, bwd)
    with jax.named_scope("ce_head"):
        return f(h2, embed, y1)


def loss_and_acc(
    params: Sequence[jax.Array],
    X: jax.Array,
    y: jax.Array,
    cfg: TransformerConfig = TransformerConfig(),
    attn_fn: Callable | None = None,
    remat: bool | str = False,
    compute_dtype: Any | None = None,
    ce_chunk: int | None = None,
    ce_grad_dtype: Any | None = None,
):
    """Token-level CE (int targets y [B, L]) + accuracy.

    ``ce_chunk``: compute the vocab projection + softmax-CE in chunks of
    that many tokens inside a rematerialized ``lax.scan`` — the
    [B·L, vocab] f32 logits tensor (537 MB at the flagship bench shape)
    never materializes in either direction; each chunk's logits live only
    as a VMEM-sized block and the backward recomputes them. Costs one
    extra vocab-matmul forward pass (~8% of flagship FLOPs) and removes
    several full-tensor HBM sweeps — measured ~25% faster end-to-end at
    the flagship shape. Same f32 softmax math, identical loss to the
    unchunked path (equivalence: tests/unit/test_transformer.py).
    ``B·L`` must divide by ``ce_chunk``."""
    cd = jnp.dtype(compute_dtype) if compute_dtype is not None else None

    def c(x: jax.Array) -> jax.Array:
        return _cast(x, cd)

    embed = params[0]
    if ce_grad_dtype is not None:
        if ce_chunk is not None:
            raise ValueError(
                "ce_chunk and ce_grad_dtype are mutually exclusive — "
                "the narrow-backward head materializes full logits "
                "transiently, which is exactly what ce_chunk avoids; "
                "pick the one whose constraint (HBM vs matmul rate) "
                "binds"
            )
        h = features(
            params, X, cfg, attn_fn, remat=remat,
            compute_dtype=compute_dtype,
        )
        N = h.shape[0] * h.shape[1]
        loss_sum, hit_sum = _ce_head(
            h.reshape(N, cfg.d_model), embed, y.reshape(N),
            cd, jnp.dtype(ce_grad_dtype),
        )
        return loss_sum / N, hit_sum / N
    if ce_chunk is None:
        logits = apply(
            params, X, cfg, attn_fn, remat=remat,
            compute_dtype=compute_dtype,
        )
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return loss, acc

    h = features(
        params, X, cfg, attn_fn, remat=remat, compute_dtype=compute_dtype
    )
    N = h.shape[0] * h.shape[1]
    if N % ce_chunk:
        raise ValueError(
            f"ce_chunk={ce_chunk} must divide the token count {N}"
        )
    hf = h.reshape(N // ce_chunk, ce_chunk, cfg.d_model)
    yf = y.reshape(N // ce_chunk, ce_chunk)

    @jax.checkpoint
    def chunk_stats(h_blk, y_blk):
        # f32 accumulation + f32 softmax math — the chunking changes the
        # memory shape, not the numerics contract
        logits = jnp.dot(
            c(h_blk), c(embed).T, preferred_element_type=jnp.float32
        )
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        logit_y = jnp.take_along_axis(logits, y_blk[:, None], axis=-1)[:, 0]
        hits = (jnp.argmax(logits, -1) == y_blk).astype(jnp.float32)
        return jnp.sum(lse - logit_y), jnp.sum(hits)

    def scan_body(carry, blk):
        loss_sum, hit_sum = carry
        h_blk, y_blk = blk
        dl, dh_ = chunk_stats(h_blk, y_blk)
        return (loss_sum + dl, hit_sum + dh_), None

    with jax.named_scope("ce_head"):
        (loss_sum, hit_sum), _ = jax.lax.scan(
            scan_body, (jnp.float32(0.0), jnp.float32(0.0)), (hf, yf)
        )
    return loss_sum / N, hit_sum / N


def make_training_step(
    cfg: TransformerConfig = TransformerConfig(),
    attn_fn: Callable | None = None,
    remat: bool | str = False,
    compute_dtype: Any | None = None,
    ce_chunk: int | None = None,
    ce_grad_dtype: Any | None = None,
) -> Callable:
    """Plan-traceable SGD step: (X, y, lr, *params) -> (loss, acc, *new).

    ``compute_dtype`` (see :func:`apply`): mixed-precision training —
    float32 master params, bf16 matmul path, f32 gradients (the casts
    are differentiable; grads come back f32 because params are f32).
    ``ce_chunk`` / ``ce_grad_dtype`` (see :func:`loss_and_acc`): chunked
    vocab projection / narrow-dtype CE backward."""

    def training_step(X, y, lr, *params):
        (loss, acc), grads = jax.value_and_grad(
            lambda p: loss_and_acc(
                p, X, y, cfg, attn_fn, remat=remat,
                compute_dtype=compute_dtype, ce_chunk=ce_chunk,
                ce_grad_dtype=ce_grad_dtype,
            ),
            has_aux=True,
        )(list(params))
        new_params = [p - lr * g for p, g in zip(params, grads)]
        return (loss, acc, *new_params)

    return training_step


#: default-config step so the module satisfies the models.REGISTRY contract
#: (init/apply/training_step) like mlp and cnn
training_step = make_training_step()
