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
overflow within a few positions). The pairs ``j < i`` of a chunk are taken
level by level instead, the doubling's own levels: at level ``s`` the
pairs of every aligned block of ``2 s`` rows with ``i`` in its second half
and ``j`` in its first (each pair falls in exactly one level), the
exponent split between the halves, ``exp(G_i - G_j) = exp(g_m + .. + g_i)
exp(g_{j+1} + .. + g_{m-1})`` with ``m`` the second half's first row: both
factors are sums of the block's own non-positive terms, so a level is one
exponential a row and ONE matrix product, ``[k|q * E] (kb * E)^T``, of which
the level's pairs are kept; what it keeps of ``N`` is exactly the ``C`` the
doubling joins two inverted blocks with. Those sums come from a ladder of
block totals (:func:`_ladder`: ``log2(CHUNK)`` shifted additions, in the
kernels, no ``cumsum`` outside them) and never from a difference of two
running sums, so a small exponent keeps its digits however far a chunk
has decayed.

The chunk's work is two functions of plain 2-D array operations, each
used twice. :func:`_solver` is what no state enters (the levels' products,
``B``, the inverse), for the 128 rows of two chunks at once, side by side
on the diagonal of full 128 x 128 tiles; :func:`_advance` is a chunk's
three steps against the state (``U``, ``O``, ``S_C``). On a TPU at
lane-aligned head sizes they are the bodies of two Pallas kernels,
``kda_chunk_solver`` (every grid step on its own) and ``kda_chunk_state``
(the pass over a head's chunks, the state resident in VMEM, chunks past
the prompt's true length skipped by both); under ``vmap``/``scan`` through
XLA everywhere else (the CPU, tier-1's tiny widths): the same mathematics,
as :func:`pygrid_tpu.models.moe.grouped_eligible` does it for the expert
kernel. Both are generators that ``yield`` between their dependent
products, and a grid step advances four of them in turn
(:func:`_lockstep`): the compiler keeps to program order, and a chain of
ten dependent products a group leaves the matrix units idle unless another
group's products are emitted between them. A padded position must arrive
with ``g = 0`` and ``b = 0``: it then neither decays the state nor feeds
it.

Matrix products take their operands in ``mm_dtype`` (the served weights'
type: bfloat16 on the chip) and accumulate in float32; in float32 they
run at full precision. The state, the decays and every elementwise step
are float32.

Measured on a v5e (PERF.md §5 item 0, PR 42; 8,192 positions, 64 heads of
128, a layer): the one kernel this replaces took 19.7 ms, 11.8 of them the
inverse's thirty dependent 64 x 64 passes a chunk and 3.0 the sixteen
passes a sub-chunk that formed every pair's decay itself, and XLA's
``cumsum`` beside it 4.7; the two kernels take 4.5 + 2.6. Emitted a group
after another they take 9.5 + 4.0: the interleaving is worth as much as
the arithmetic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions a chunk: one triangular system, one state update
CHUNK = 64
#: positions the state-free part handles at once: two chunks side by
#: side on the diagonal of one 128 x 128 tile
GROUP = 2 * CHUNK

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


def _exact(mm_dtype):
    """The product the triangular inverse is built with."""
    if jnp.dtype(mm_dtype) == jnp.float32:
        return _dot(jnp.float32)
    return _dot_split


def _ladder(g):
    """The sums of log-decays that the chunks of ``g`` [n CHUNK, d_k] need,
    block size by block size: for ``s = 1, 2, 4, .. CHUNK`` yields ``(s,
    pre, suf, tot)``, a row's sum over its own aligned block of ``s`` rows
    up to and with itself, after itself, and whole. Each is a sum of the
    block's own (non-positive) terms and nothing else: no difference of
    two long sums is ever taken, so a small exponent keeps its digits
    however far the chunk has decayed."""
    at = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    pre, suf, tot, s = g, jnp.zeros_like(g), g, 1
    while True:
        yield s, pre, suf, tot
        if s == CHUNK:
            return
        odd = (at & s) != 0
        # a block of 2 s rows: its first half's total joins the second
        # half's sums from the start, the second's the first's to the end
        up, down = jnp.roll(tot, s, 0), jnp.roll(tot, -s, 0)
        pre = pre + jnp.where(odd, up, 0.0)
        suf = suf + jnp.where(odd, 0.0, down)
        tot = tot + jnp.where(odd, up, down)
        s *= 2


def _lockstep(tasks):
    """The results of generators that do independent work, each advanced
    one stage (to its next ``yield``) in turn: the stages of several heads
    or groups are then emitted side by side, and a scheduler that keeps to
    program order has one's products to run while another's are on
    their way."""
    out, live = [None] * len(tasks), dict(enumerate(tasks))
    while live:
        for i, task in list(live.items()):
            try:
                next(task)
            except StopIteration as stop:
                out[i] = stop.value
                del live[i]
    return out


def _solver(q, k, kb, g, dot, exact):
    """What a chunk needs that no state enters, for the ``GROUP`` rows of
    two chunks at once (module docstring). ``q, k, kb, g`` [GROUP, d_k]
    float32 (``kb = b * k``, ``g`` the log-decays). A generator
    (:func:`_lockstep`) that returns ``[GROUP, 2 CHUNK]``: a row's
    ``CHUNK`` entries of its chunk's ``(I + N)^-1``, then its entries of
    ``B``."""
    R = GROUP
    at = lax.broadcasted_iota(jnp.int32, q.shape, 0)
    row = lax.broadcasted_iota(jnp.int32, (R, R), 0)
    col = lax.broadcasted_iota(jnp.int32, (R, R), 1)
    B, inverse, bit = jnp.zeros((R, R), jnp.float32), None, 0
    for s, pre, suf, _ in _ladder(g):
        if s == CHUNK:
            break
        # pairs (i, j) of one block of 2 s rows, i in its second half and j
        # in its first, split between the halves: exp(G_i - G_j) is i's
        # decay from its half's start times j's to its half's end. One
        # exponent a row, the left factor's where i lies, the right one's
        # where j does
        E = jnp.exp(jnp.where((at & s) != 0, pre, suf))
        Z = dot(jnp.concatenate([k * E, q * E], 0), kb * E, _NT)  # [2 R, R]
        yield
        pair = ((row & s) != 0) & ((row >> bit) - (col >> bit) == 1)
        join = jnp.where(pair, Z[:R], 0.0)
        B = jnp.where(pair, Z[R:], B)
        # the inverted diagonal blocks of s rows give those of 2 s
        if inverse is None:
            inverse = (row == col).astype(jnp.float32) - join
        else:
            joined = exact(join, inverse)
            yield
            inverse = inverse - exact(inverse, joined)
            yield
        bit += 1
    B = jnp.where(row == col, dot(q, kb, _NT), B)
    # both are zero outside the two chunks' own squares: fold them
    fold = lambda m: m + jnp.roll(m, CHUNK, 1)  # noqa: E731
    return jnp.where(col < CHUNK, fold(inverse), fold(B))


def _advance(q, k, kb, v, g, AB, St, dot):
    """One chunk of one head against the state. ``q, k, kb, g`` [CHUNK,
    d_k], ``v`` [CHUNK, d_v], ``AB`` the chunk's rows of :func:`_solver`,
    ``St`` [d_v, d_k] the state before the chunk, all float32. A generator
    (:func:`_lockstep`) that returns (``O`` [CHUNK, d_v], the state
    after)."""
    C = CHUNK
    *_, (_, pre, suf, tot) = _ladder(g)
    decay = jnp.exp(pre)
    read = dot(jnp.concatenate([k * decay, q * decay], 0), St, _NT)
    yield
    rhs = v - read[:C]
    none = jnp.zeros_like(rhs)
    # AB's first half multiplies the upper rows, its second the lower
    U = dot(AB, jnp.concatenate([rhs, none], 0))
    yield
    O = read[C:] + dot(AB, jnp.concatenate([none, U], 0))
    St = St * jnp.exp(tot[:1]) + dot(U.T, kb * jnp.exp(suf))
    return O, St


#: (rows, heads) a grid step of each kernel holds
_SOLVER_BLOCK = (GROUP, 4)
_STATE_BLOCK = (2 * CHUNK, 4)


def _solver_kernel(len_ref, q_ref, k_ref, kb_ref, g_ref, ab_ref, *, heads,
                   mm_dtype):
    rows, dk = q_ref.shape[0], q_ref.shape[1] // heads

    # rows past the prompt are never read: kda_chunk_state skips them
    @pl.when(pl.program_id(1) * rows < len_ref[0])
    def _():
        # every (head, group) is on its own
        places = [
            (slice(lo, lo + GROUP), h)
            for h in range(heads) for lo in range(0, rows, GROUP)
        ]
        groups = _lockstep([
            _solver(
                *(ref[at, h * dk : (h + 1) * dk]
                  for ref in (q_ref, k_ref, kb_ref, g_ref)),
                _dot(mm_dtype), _exact(mm_dtype),
            )
            for at, h in places
        ])
        for (at, h), AB in zip(places, groups):
            ab_ref[at, h * GROUP : (h + 1) * GROUP] = AB


def _state_kernel(len_ref, q_ref, k_ref, kb_ref, v_ref, g_ref, ab_ref, o_ref,
                  s_ref, *, heads, mm_dtype):
    c = pl.program_id(1)
    rows = q_ref.shape[0]
    dk, dv = q_ref.shape[1] // heads, v_ref.shape[1] // heads

    ks = [slice(h * dk, (h + 1) * dk) for h in range(heads)]
    vs = [slice(h * dv, (h + 1) * dv) for h in range(heads)]

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    for lo in range(0, rows, CHUNK):
        live = c * rows + lo < len_ref[0]
        at = slice(lo, lo + CHUNK)

        @pl.when(live)
        def _():
            done = _lockstep([
                _advance(
                    q_ref[at, ks[h]], k_ref[at, ks[h]], kb_ref[at, ks[h]],
                    v_ref[at, vs[h]], g_ref[at, ks[h]],
                    ab_ref[at, h * GROUP : (h + 1) * GROUP], s_ref[h],
                    _dot(mm_dtype),
                )
                for h in range(heads)
            ])
            for h, (O, St) in enumerate(done):
                o_ref[at, vs[h]] = O
                s_ref[h] = St

        @pl.when(jnp.logical_not(live))
        def _():
            # all padding: nothing moves the state; the rows must still
            # be written (what lies in the buffer may not be a number)
            o_ref[at, :] = jnp.zeros((CHUNK, o_ref.shape[1]), o_ref.dtype)


def _chunk_call(q, k, kb, v, g, length, heads, mm_dtype, interpret):
    """The Pallas form over ``[P, heads * d]`` arrays, ``P`` a multiple of
    ``GROUP``: (``O`` [P, heads * d_v], states [heads, d_v, d_k]). What
    ``kda_chunk_solver`` hands ``kda_chunk_state`` is one more array of
    ``q``'s size. (Measured, PERF.md §6, PR 42: four groups or heads a grid
    step in lockstep; two take 30% and 45% longer, eight gain nothing.)"""
    P = q.shape[0]
    dk, dv = q.shape[1] // heads, v.shape[1] // heads
    length = jnp.reshape(length, (1,)).astype(jnp.int32)

    def call(kernel, name, block, widths, out_specs, out_shape, order):
        rows, hb = math.gcd(block[0], P), math.gcd(block[1], heads)
        spec = lambda w: pl.BlockSpec(  # noqa: E731
            (rows, hb * w), lambda h, c, length: (c, h)
        )
        return pl.pallas_call(
            functools.partial(kernel, heads=hb, mm_dtype=mm_dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(heads // hb, P // rows),
                in_specs=[spec(w) for w in widths],
                out_specs=out_specs(spec, hb),
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(dimension_semantics=order),
            interpret=interpret,
            name=name,
        )

    AB = call(
        _solver_kernel, "kda_chunk_solver", _SOLVER_BLOCK, [dk] * 4,
        lambda spec, hb: spec(GROUP),
        jax.ShapeDtypeStruct((P, heads * GROUP), jnp.float32),
        ("parallel", "parallel"),
    )(length, q, k, kb, g)
    return call(
        _state_kernel, "kda_chunk_state", _STATE_BLOCK,
        [dk, dk, dk, dv, dk, GROUP],
        lambda spec, hb: [
            spec(dv),
            # the same block for every chunk of a head: the state stays
            # in VMEM until the head's last chunk
            pl.BlockSpec((hb, dv, dk), lambda h, c, length: (h, 0, 0)),
        ],
        [
            jax.ShapeDtypeStruct((P, heads * dv), jnp.float32),
            jax.ShapeDtypeStruct((heads, dv, dk), jnp.float32),
        ],
        ("parallel", "arbitrary"),
    )(length, q, k, kb, v, g, AB)


def chunk_eligible(dk: int, dv: int) -> bool:
    """True where a prompt's chunks take the kernels: on a TPU, with head
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
    pad = -P % GROUP
    Pp = P + pad
    with jax.named_scope("kda.chunk"):
        kb = k * beta[..., None]
        flat = lambda x: jnp.pad(  # noqa: E731
            x.reshape(P, -1), ((0, pad), (0, 0))
        )
        q, k, kb, v, g = flat(q), flat(k), flat(kb), flat(v), flat(g)
        if chunk_eligible(dk, dv) if kernel is None else kernel:
            o, St = _chunk_call(q, k, kb, v, g, length, H, mm_dtype, interpret)
        else:
            dot, exact = _dot(mm_dtype), _exact(mm_dtype)
            heads = lambda x, rows: x.reshape(  # noqa: E731
                Pp // rows, rows, H, -1
            ).transpose(0, 2, 1, 3)
            AB = jax.vmap(jax.vmap(
                lambda *group: _lockstep([_solver(*group, dot, exact)])[0]
            ))(*(heads(x, GROUP) for x in (q, k, kb, g)))
            AB = AB.transpose(0, 2, 1, 3).reshape(Pp, H * GROUP)

            def one(St, xs):
                O, St = jax.vmap(
                    lambda *head: _lockstep([_advance(*head, dot)])[0]
                )(*xs, St)
                return St, O

            St, o = lax.scan(
                one, jnp.zeros((H, dv, dk), jnp.float32),
                tuple(heads(x, CHUNK) for x in (q, k, kb, v, g, AB)),
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
