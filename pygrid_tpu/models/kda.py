"""The gated delta rule with a decay a channel (Kimi Delta Attention,
arXiv:2510.26692): the linear-attention mixer of
:mod:`pygrid_tpu.models.solar_open2`, in the two forms serving needs.

A head keeps a matrix state ``S`` [d_k, d_v], zero at a prompt's start::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with ``a_t = exp(g_t)`` in (0, 1] a CHANNEL of d_k (``g_t <= 0`` the
log-decay), ``b_t`` in [0, 2] a head, ``k_t`` of unit length. Written as
one rank-one update of the decayed state::

    u_t = v_t - (Diag(a_t) S_{t-1})^T k_t
    S_t = Diag(a_t) S_{t-1} + b_t k_t u_t^T                      (*)

The state is HELD TRANSPOSED, ``[d_v, d_k]``: the decays then run along
the lanes (a row broadcast), and both forms read it as it lies.

:func:`step` is (*) for one token of each of ``w`` rows: a decode step.

:func:`chunked` runs a whole prompt from a zero state ``CHUNK`` positions
at a time. With ``G_i = g_1 + .. + g_i`` inside a chunk that starts from
``S_0``::

    N_ij = b_j sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])   (j < i, else 0)
    B_ij = b_j sum_d q_i[d] k_j[d] exp(G_i[d] - G_j[d])   (j <= i, else 0)
    (I + N) U = V - (K * exp(G)) S_0          the chunk's u_t, all at once
    O = (Q * exp(G)) S_0 + B U
    S_C = Diag(exp(G_C)) S_0 + (b K * exp(G_C - G))^T U

``I + N`` is unit lower triangular. Its inverse is built by doubling: the
inverses of the diagonal blocks of ``s`` rows (the identity at ``s = 1``)
give those of ``2 s`` rows, ``[[A, 0], [C, D]]^-1 = [[A^-1, 0], [-D^-1 C
A^-1, D^-1]]``, all blocks of a level in two matrix products:
``log2(CHUNK)`` levels and no loop over positions. (The finite series
``(I - N)(I + N^2)(I + N^4)..`` is the same inverse on paper and useless in
float32: where the decays are slow ``N`` is dense, its powers grow by
many orders of magnitude before they vanish, and the series cancels
them; the doubling only ever forms blocks of the inverse itself.) These
products keep float32's worth of their operands whatever ``mm_dtype`` is:
at full precision in float32, from three bfloat16 passes where the other
products take one (:func:`_dot_split`).

**The decays stay in log space, float32, and no exponential of a
positive number is ever formed.** ``exp(G_i - G_j)`` does not factor into
``exp(G_i) exp(-G_j)`` safely (a channel that decays fast makes the second
overflow within a few positions), so the chunk is cut into sub-chunks of
``SUB`` positions: between two sub-chunks the exponent is split at the
boundary ``R`` between them, ``(G_i - R) + (R - G_j)``, both parts <= 0,
and the sum over channels is one matrix product; inside a sub-chunk every
pair's ``exp(G_i - G_j)`` is formed itself (``SUB`` elementwise passes)
and the matrix unit only does the summing.

The chunk's work is one function of plain 2-D array operations
(:func:`_chunk`), used twice: as the body of the Pallas kernel
``kda_chunk`` (a grid step a head a chunk, the state resident in VMEM
across a head's chunks, chunks past the prompt's true length skipped) on
a TPU at lane-aligned head sizes, and under ``vmap``/``scan`` through
XLA everywhere else (the CPU, tier-1's tiny widths): the same
mathematics, as :func:`pygrid_tpu.models.moe.grouped_eligible` does it
for the expert kernel. A padded position must arrive with ``g = 0`` and
``b = 0``: it then neither decays the state nor feeds it.

Matrix products take their operands in ``mm_dtype`` (the served weights'
type: bfloat16 on the chip) and accumulate in float32; in float32 they
run at full precision. The state, the decays and every elementwise step
are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions a chunk: one triangular system, one state update
CHUNK = 64
#: positions a sub-chunk: every pair's decay formed itself inside one
SUB = 16

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _dot(mm_dtype):
    """``dot(a, b, dims)``: operands in ``mm_dtype``, float32 out."""
    mm_dtype = jnp.dtype(mm_dtype)
    precision = lax.Precision.HIGHEST if mm_dtype == jnp.float32 else None

    def dot(a, b, dims=_NN):
        return lax.dot_general(
            a.astype(mm_dtype), b.astype(mm_dtype), dims,
            precision=precision, preferred_element_type=jnp.float32,
        )

    return dot


def _dot_split(a, b, dims=_NN):
    """A float32 product from three bfloat16 passes: each operand split
    into the bfloat16 nearest it and what that leaves, the product of the
    two remainders dropped (relative error ~2^-16, against 2^-8 of one
    pass and six passes for full float32)."""
    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    one = lambda x, y: lax.dot_general(  # noqa: E731
        x, y, dims, preferred_element_type=jnp.float32
    )
    return one(a_hi, b_hi) + (one(a_hi, b_lo) + one(a_lo, b_hi))


def _chunk(q, k, kb, v, G, St, dot, exact):
    """One chunk of one head (module docstring). ``q, k, kb, G`` [C, d_k]
    (``kb = b * k``; ``G`` the inclusive sum of the chunk's log-decays),
    ``v`` [C, d_v], ``St`` [d_v, d_k] the state before the chunk, all
    float32. Returns (``O`` [C, d_v], the state after)."""
    C = q.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (SUB, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (SUB, C), 1)
    decay = jnp.exp(G)
    rhs = v - dot(k * decay, St, _NT)
    n_rows, b_rows = [], []
    for lo in range(0, C, SUB):
        Gr, kr, qr = G[lo : lo + SUB], k[lo : lo + SUB], q[lo : lo + SUB]
        # inside the sub-chunk: pair (i, j)'s own exp(G_i - G_j), a pass
        # a column j; the product with kb sums over the channels and the
        # column of Z that is wanted of pass j is column lo + j
        passes = []
        for j in range(SUB):
            e = jnp.exp(jnp.minimum(Gr - Gr[j : j + 1], 0.0))
            passes += [kr * e, qr * e]
        Z = dot(jnp.concatenate(passes, 0), kb, _NT)  # [SUB * 2 SUB, C]
        n_a = jnp.zeros((SUB, C), jnp.float32)
        b_a = jnp.zeros((SUB, C), jnp.float32)
        for j in range(SUB):
            at = col == lo + j
            z = Z[2 * SUB * j : 2 * SUB * (j + 1)]
            n_a = n_a + jnp.where(at, z[:SUB], 0.0)
            b_a = b_a + jnp.where(at, z[SUB:], 0.0)
        inside = col >= lo
        n_a = jnp.where(inside & (col < lo + row), n_a, 0.0)
        b_a = jnp.where(inside & (col <= lo + row), b_a, 0.0)
        if lo:
            # earlier sub-chunks: the exponent split at the boundary
            R = G[lo - 1 : lo]
            e = jnp.exp(Gr - R)
            before = kb * jnp.exp(jnp.minimum(R - G, 0.0))
            cross = dot(jnp.concatenate([kr * e, qr * e], 0), before, _NT)
            n_a = n_a + jnp.where(inside, 0.0, cross[:SUB])
            b_a = b_a + jnp.where(inside, 0.0, cross[SUB:])
        n_rows.append(n_a)
        b_rows.append(b_a)
    N = jnp.concatenate(n_rows, 0)
    B = jnp.concatenate(b_rows, 0)
    # (I + N)^-1 by doubling the inverted diagonal blocks
    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # the blocks of N that join two inverted blocks of s rows
    joins = lambda s: jnp.where(  # noqa: E731
        ((row // s) % 2 == 1) & (col // s == row // s - 1), N, 0.0
    )
    # blocks of one row are their own inverses: pairs need no product
    inverse = (row == col).astype(jnp.float32) - joins(1)
    s = 2
    while s < C:
        inverse = inverse - exact(inverse, exact(joins(s), inverse))
        s *= 2
    U = dot(inverse, rhs)
    O = dot(q * decay, St, _NT) + dot(B, U)
    last = G[C - 1 : C]
    St = St * jnp.exp(last) + dot(U.T, kb * jnp.exp(last - G))
    return O, St


def _exact(mm_dtype):
    """The product the triangular inverse is built with."""
    if jnp.dtype(mm_dtype) == jnp.float32:
        return _dot(jnp.float32)
    return _dot_split


def _kernel(len_ref, q_ref, k_ref, kb_ref, v_ref, g_ref, o_ref, s_ref, *,
            mm_dtype):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    live = c * CHUNK < len_ref[0]

    @pl.when(live)
    def _():
        O, St = _chunk(
            q_ref[...], k_ref[...], kb_ref[...], v_ref[...], g_ref[...],
            s_ref[0], _dot(mm_dtype), _exact(mm_dtype),
        )
        o_ref[...] = O
        s_ref[0] = St

    @pl.when(jnp.logical_not(live))
    def _():
        # all padding: nothing moves the state; the rows must still be
        # written (what lies in the buffer may not be a number)
        o_ref[...] = jnp.zeros_like(o_ref)


def _chunk_call(q, k, kb, v, G, length, heads, mm_dtype, interpret):
    """The Pallas form over ``[P, heads * d]`` arrays, ``P`` a multiple of
    ``CHUNK``: (``O`` [P, heads * d_v], states [heads, d_v, d_k]). (Two
    and four heads a grid step were tried on the chip: 3% and 4% off a
    layer's time, so the products' throughput bounds a step, not their
    waits: PERF.md §6, PR 36.)"""
    P = q.shape[0]
    dk, dv = q.shape[1] // heads, v.shape[1] // heads

    def rows(width):
        return pl.BlockSpec((CHUNK, width), lambda h, c, length: (c, h))

    return pl.pallas_call(
        functools.partial(_kernel, mm_dtype=mm_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, P // CHUNK),
            in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(dk)],
            out_specs=[
                rows(dv),
                # the same block for every chunk of a head: the state
                # stays in VMEM until the head's last chunk
                pl.BlockSpec((1, dv, dk), lambda h, c, length: (h, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((P, heads * dv), jnp.float32),
            jax.ShapeDtypeStruct((heads, dv, dk), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="kda_chunk",
    )(jnp.reshape(length, (1,)).astype(jnp.int32), q, k, kb, v, G)


def chunk_eligible(dk: int, dv: int) -> bool:
    """True where a prompt's chunks take the kernel: on a TPU, with head
    sizes that fill whole 128-lane rows."""
    return jax.default_backend() == "tpu" and dk % 128 == 0 and dv % 128 == 0


def chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    length: jax.Array,
    mm_dtype=jnp.float32,
    kernel: bool | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The recurrence over one sequence from a ZERO state. ``q, k, g``
    [P, H, d_k], ``v`` [P, H, d_v], ``beta`` [P, H], float32; positions at
    and past ``length`` are padding and arrive with ``g = 0``, ``beta =
    0``. Returns (``o`` [P, H, d_v], the state after position ``length -
    1`` [H, d_v, d_k])."""
    P, H, dk = q.shape
    dv = v.shape[-1]
    pad = -P % CHUNK
    Pp = P + pad
    with jax.named_scope("kda.chunk"):
        kb = k * beta[..., None]
        flat = lambda x: jnp.pad(  # noqa: E731
            x.reshape(P, -1), ((0, pad), (0, 0))
        )
        # a chunk's own running sum of log-decays
        G = jnp.cumsum(
            jnp.pad(g, ((0, pad), (0, 0), (0, 0))).reshape(
                Pp // CHUNK, CHUNK, H * dk
            ), axis=1,
        ).reshape(Pp, H * dk)
        q, k, kb, v = flat(q), flat(k), flat(kb), flat(v)
        if chunk_eligible(dk, dv) if kernel is None else kernel:
            o, St = _chunk_call(q, k, kb, v, G, length, H, mm_dtype, interpret)
        else:
            dot, exact = _dot(mm_dtype), _exact(mm_dtype)
            heads = lambda x, d: x.reshape(  # noqa: E731
                Pp // CHUNK, CHUNK, H, d
            ).transpose(0, 2, 1, 3)

            def one(St, xs):
                O, St = jax.vmap(
                    lambda q, k, kb, v, G, St: _chunk(
                        q, k, kb, v, G, St, dot, exact
                    )
                )(*xs, St)
                return St, O

            St, o = lax.scan(
                one, jnp.zeros((H, dv, dk), jnp.float32),
                (heads(q, dk), heads(k, dk), heads(kb, dk), heads(v, dv),
                 heads(G, dk)),
            )
            o = o.transpose(0, 2, 1, 3).reshape(Pp, H * dv)
        return o[:P].reshape(P, H, dv), St


def step(St, q, k, v, g, beta):
    """(*) for one token of each of ``w`` rows. ``St`` [w, H, d_v, d_k]
    float32; ``q, k, g`` [w, H, d_k]; ``v`` [w, H, d_v]; ``beta`` [w, H].
    Returns (``o`` [w, H, d_v], the new state). Plain XLA: the state is
    read, decayed, updated by one outer product and written back."""
    with jax.named_scope("kda.step"):
        St = St * jnp.exp(g)[:, :, None, :]
        u = v - jnp.einsum("whvk,whk->whv", St, k)
        St = St + (beta[..., None] * u)[..., :, None] * k[..., None, :]
        return jnp.einsum("whvk,whk->whv", St, q), St
