"""Pallas TPU flash-attention kernels — the dense-attention hot op.

The XLA path (:func:`pygrid_tpu.parallel.ring_attention.attention`)
materializes the [B,H,Lq,Lk] score tensor in HBM: at L=8K heads=8 that is
2 GB per batch element per pass, and bandwidth — not the MXU — bounds it.
These kernels run the standard flash-attention recurrence (online softmax,
Dao et al.) with the score block resident in VMEM, and read and write the
caller's own ``[B, L, H·D]`` layout (the ``[B, L, H, D]`` the model hands
over, reshaped for free): no transpose on the way in or out.

**What a block holds** follows from ``(L, H, D)`` alone:

- *Lanes.* A head narrower than a 128-lane tile shares it: where ``D``
  divides 128, ``P = 128 // D`` heads lie side by side in one tile (two
  64-wide heads; :func:`_heads_per_tile`) and nothing is padded. Inside
  the kernel the tile's heads are stacked on the ROWS: copy ``p`` of the
  q tile keeps head ``p``'s lanes and zeroes the rest, so one
  ``[P·bq, 128] · [bk, 128]ᵀ`` dot gives every head's own scores, each
  row carries its own softmax statistics, and the second dot's result is
  read back by head (:func:`_stack` / :func:`_unstack`). A 64-deep
  contraction half-fills the MXU whatever the layout; what the old pad
  to 128 lanes cost was bytes and grid steps. Other widths pad each head
  to a multiple of 128 lanes and take one head a tile (``P = 1``).
- *Rows.* Grid ``(B/G, head groups/n, Lq/bq, Lk/bk)``, K innermost
  ("arbitrary") so the output tile and the (m, l) running statistics stay
  in VMEM scratch across the K sweep — HBM sees one read of Q/K/V and one
  write of O, never the L×L scores. Where the whole sequence is one tile
  there is nothing to carry: one grid step takes every head group of
  ``G`` sequences (:func:`_group`: as many as ``_VMEM_STEP`` holds), so
  a short sequence costs one step, not one a head.
- both dots (``q·kᵀ`` and ``p·v``) hit the MXU in f32 accumulation;
  inputs may be bf16 (halved K/V streaming traffic);
- fully-masked causal blocks are skipped via ``pl.when`` on the block
  ids — ~2× fewer FLOPs for causal at no accuracy cost;
- masked lanes are zeroed AFTER the exp (an all-masked block would
  otherwise renormalize to uniform — the classic flash pitfall), and the
  final divide guards l=0 rows (fully padded queries).

Correctness contract: matches the XLA reference to f32 tolerance for any
(Lq, Lk, H, D) — ragged lengths are zero-padded to tile multiples and the
pad keys masked by position (tests run interpret mode on CPU; the TPU
path is exercised by the benchmark's training cells and ``chip_smoke``).

No reference analog: the reference has no attention at all (SURVEY §5.7);
this kernel exists because long-context is first-class here. Consume it
via the transformer's injectable attention
(``transformer.apply(..., attn_fn=flash_attention)``) or call it
directly; ``bench.py bench_attention()`` is the reproducible comparison
against the XLA path.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: defaults from an on-chip sweep (v5e, L=4096 D=128 causal): 128×128
#: blocks ran at 15 TF/s — the per-step dots were too small to feed the
#: MXU; 512×1024 ran 6.9× faster and beats the XLA path ~3× (wall-clock,
#: same computation). The wrapper clamps blocks down for short sequences.
BLOCK_Q = 512
BLOCK_K = 1024
#: lanes of a tile: Mosaic wants a block's minor dim in multiples of it.
#: A narrower head that divides it shares the tile with its neighbours
#: (``_heads_per_tile``); any other width is padded up to a multiple
MIN_D = 128
#: sublanes of the per-row statistics blocks, ``[8, bq]`` f32 rows: head
#: p's lse on row p and, for the backward, its Δ on row P + p
STAT_ROWS = 8
#: bytes one grouped grid step may hold (its double-buffered blocks and
#: its f32 score tiles), inside the 16 MiB of scoped VMEM the compiler
#: grants by default. On the v5e at [4,128,12,64] under a 64-client vmap
#: one sequence a step took 1.84 ms forward + backward, two 1.61, four
#: (forward) and two (backward) 1.56, four and four 1.52
_VMEM_STEP = 12 * 2**20

_NEG = -1e30


class _Tiling(NamedTuple):
    """How the heads of a ``[B, L, H, D]`` operand lie on the lanes."""

    heads: int   # P: heads side by side in one lane group
    width: int   # lanes of a group (128, or D padded up where P == 1)
    groups: int  # lane groups a sequence's heads make
    dim: int     # D


def _heads_per_tile(D: int) -> int:
    """Heads that share one 128-lane tile: ``128 // D`` where ``D``
    divides it and the tile's statistics (lse and Δ of every head) fit
    the ``STAT_ROWS`` of their block; one (padded) otherwise."""
    if D < MIN_D and MIN_D % D == 0 and 2 * (MIN_D // D) <= STAT_ROWS:
        return MIN_D // D
    return 1


def _tiling(H: int, D: int) -> _Tiling:
    P = _heads_per_tile(D)
    width = MIN_D if P > 1 else pl.cdiv(D, MIN_D) * MIN_D
    return _Tiling(P, width, pl.cdiv(H, P), D)


def _pad_to(x: jax.Array, length: int, axis: int) -> jax.Array:
    pad = length - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _crop(x: jax.Array, length: int, axis: int) -> jax.Array:
    if x.shape[axis] == length:
        return x
    return lax.slice_in_dim(x, 0, length, axis=axis)


def _pack(x: jax.Array, t: _Tiling, rows: int) -> jax.Array:
    """``[B, L, H, D]`` → ``[B, rows, groups·width]``: a reshape where the
    heads fill their tiles and L its blocks; otherwise whole heads are
    padded on once (``P > 1``) or each head's lanes (``P == 1``)."""
    B, L = x.shape[:2]
    if t.heads > 1:
        x = _pad_to(x, t.groups * t.heads, 2)
    else:
        x = _pad_to(x, t.width, 3)
    return _pad_to(x.reshape(B, L, t.groups * t.width), rows, 1)


def _unpack(x: jax.Array, t: _Tiling, L: int, H: int) -> jax.Array:
    """Inverse of :func:`_pack`: ``[B, rows, groups·width]`` →
    ``[B, L, H, D]``."""
    x = _crop(x, L, 1)
    if t.heads > 1:
        x = x.reshape(x.shape[0], L, t.groups * t.heads, t.dim)
        return _crop(x, H, 2)
    return _crop(x.reshape(x.shape[0], L, H, t.width), t.dim, 3)


def _group(
    B: int, groups: int, unit_bytes: int, tile_bytes: int
) -> tuple[int, int]:
    """``(G, n)``: the sequences and head groups one grid step of a
    one-tile sequence holds — every group of a sequence before a second
    sequence, each the largest divisor whose double-buffered blocks
    (``unit_bytes`` a sequence a group) fit ``_VMEM_STEP`` beside the
    step's score tiles."""

    def largest(of: int, each: int) -> int:
        return max(
            d for d in range(1, of + 1)
            if of % d == 0
            and (d == 1 or 2 * d * each + tile_bytes <= _VMEM_STEP)
        )

    n = largest(groups, unit_bytes)
    G = largest(B, groups * unit_bytes) if n == groups else 1
    return G, n


def _stack(x: jax.Array, t: _Tiling) -> jax.Array:
    """``[r, 128]`` → ``[P·r, 128]``: copy ``p`` keeps head ``p``'s lanes
    and zeroes the others', so a 128-deep contraction against it is head
    ``p``'s own ``D``-deep one."""
    if t.heads == 1:
        return x
    head = lax.broadcasted_iota(jnp.int32, x.shape, 1) // t.dim
    zero = jnp.zeros_like(x)
    return jnp.concatenate(
        [jnp.where(head == p, x, zero) for p in range(t.heads)], axis=0
    )


def _unstack(y: jax.Array, t: _Tiling) -> jax.Array:
    """``[P·r, 128]`` → ``[r, 128]``: head ``p``'s lanes from copy
    ``p``'s rows (what a dot against the whole tile left elsewhere is
    another head's, and dropped)."""
    if t.heads == 1:
        return y
    r = y.shape[0] // t.heads
    head = lax.broadcasted_iota(jnp.int32, (r, y.shape[1]), 1) // t.dim
    out = y[:r]
    for p in range(1, t.heads):
        out = jnp.where(head == p, y[p * r:(p + 1) * r], out)
    return out


def _minus_stat(x: jax.Array, stat: jax.Array, first: int, t: _Tiling,
                rows: bool) -> jax.Array:
    """``x`` (stacked, ``[P·r, c]``) less each head's statistic: head
    ``p``'s is lane ``first + p`` of ``stat [r, lanes]``, a column, or
    with ``rows`` sublane ``first + p`` of ``stat [sublanes, c]``, a row
    (the dkv kernel's orientation)."""
    r = x.shape[0] // t.heads

    def one(p):
        i = first + p
        return stat[i:i + 1, :] if rows else stat[:, i:i + 1]

    if t.heads == 1:
        return x - one(0)
    return jnp.concatenate(
        [x[p * r:(p + 1) * r] - one(p) for p in range(t.heads)], axis=0
    )


def _each_sequence(G: int, body) -> None:
    """``body(g)`` for each of the ``G`` sequences of a block that holds
    whole sequences (a grid step of its own: the sweep has one). Run
    inside a ``pl.when`` like every phase of a longer sweep: under
    ``shard_map``'s varying-axes check the interpreter types a cond's
    operands alike, and a kernel's top-level ref reads not (jax 0.9)."""

    @pl.when(pl.program_id(3) == 0)
    def _():
        if G == 1:
            body(0)
        else:
            lax.fori_loop(0, G, lambda g, carry: body(g), None)


def _mask(qi, ki, block_q, block_k, lq_true, lk_true, causal, heads=1,
          transposed=False):
    """Validity mask for one (q-block, k-block) score tile with ``heads``
    copies stacked on its rows: pad queries and pad keys contribute
    nothing; causal keeps the lower triangle. ``transposed=True`` lays
    the tile out as [P·bk, bq] (k on sublanes, q on lanes — the dkv
    kernel's orientation); the causal/pad semantics are identical,
    keeping one source of truth for all three kernels."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    q_dim = 1 if transposed else 0

    def positions(first, dim):
        pos = first + lax.broadcasted_iota(jnp.int32, shape, dim)
        return jnp.concatenate([pos] * heads, axis=0) if heads > 1 else pos

    q_pos = positions(qi * block_q, q_dim)
    k_pos = positions(ki * block_k, 1 - q_dim)
    # pad-q rows carry an lse that is no real row's (the forward's own
    # pad rows, or zeros from a re-pad): exp(s - lse) is finite but
    # wrong, so q validity is part of the backward's mask (the forward
    # only needs k validity — its pad-q rows are sliced off — and passes
    # ``lq_true=None``)
    valid = k_pos < lk_true
    if lq_true is not None:
        valid = jnp.logical_and(q_pos < lq_true, valid)
    if causal:
        valid = jnp.logical_and(valid, q_pos >= k_pos)
    return valid


def _dot(a, b, contract, precision):
    return lax.dot_general(
        a, b, (contract, ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )


_NT = ((1,), (1,))  # a·bᵀ: both contract their lanes
_NN = ((1,), (0,))  # a·b


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *carry,
    scale, causal, lk_true, n_k, G, n, t, precision,
):
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    def accumulate(g, j, m_prev, l_prev, acc_prev):
        """One score tile of head group ``j`` of sequence ``g`` into the
        running (m, l, acc), all stacked by head; None: nothing yet."""
        lanes = slice(j * t.width, (j + 1) * t.width)
        v = v_ref[g, :, lanes]
        s = _dot(
            _stack(q_ref[g, :, lanes], t), k_ref[g, :, lanes], _NT, precision
        ) * scale  # [P·BQ, BK]
        valid = _mask(
            qi, ki, block_q, block_k, None, lk_true, causal, t.heads
        )
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.max(s, axis=1, keepdims=True)
        if m_prev is not None:
            m_new = jnp.maximum(m_prev, m_new)
        # zero masked lanes AFTER exp: if every lane were masked,
        # exp(s - m_new) = exp(0) = 1 would fake a uniform distribution
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = jnp.sum(p, axis=1, keepdims=True)
        acc = _dot(p.astype(v.dtype), v, _NN, precision)
        if m_prev is not None:
            alpha = jnp.exp(m_prev - m_new)  # [P·BQ, 1]
            l_new = l_prev * alpha + l_new
            acc = acc_prev * alpha + acc
        return m_new, l_new, acc

    def write(g, j, m, l, acc, cols=None):
        """Head group ``j``'s output; returns the sequence's lse columns
        ``[bq, n·8]`` with the group's own filled in."""
        lanes = slice(j * t.width, (j + 1) * t.width)
        l = jnp.maximum(l, 1e-30)
        o_ref[g, :, lanes] = _unstack(acc / l, t).astype(o_ref.dtype)
        lse = m + jnp.log(l)
        shape = (block_q, n * STAT_ROWS)
        lane = lax.broadcasted_iota(jnp.int32, shape, 1)
        if cols is None:
            cols = jnp.zeros(shape, jnp.float32)
        for p in range(t.heads):
            cols = jnp.where(
                lane == j * STAT_ROWS + p,
                lse[p * block_q:(p + 1) * block_q], cols,
            )
        return cols

    def write_lse(g, cols):
        # log-sum-exp per query row — the residual the backward pass
        # needs to re-derive P = exp(s - lse) blockwise without ever
        # materializing the full score tensor. Written as ROWS, head p of
        # group j on sublane 8·j + p of an [n·8, bq] tile (one transpose
        # a sequence): lanes full, so the array is dense in HBM (a
        # [bq, 8] column block is tile-padded 16×, and XLA's passes over
        # it cost more than the kernels' own reads)
        lse_ref[g] = cols.T

    if n_k == 1:  # the whole sequence is this tile: nothing to carry

        def sequence(g):
            cols = None
            for j in range(n):
                cols = write(
                    g, j, *accumulate(g, j, None, None, None), cols
                )
            write_lse(g, cols)

        _each_sequence(G, sequence)
        return

    acc, m_scr, l_scr = carry

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)

    # causal: a block whose earliest key is past the latest query is all
    # masked — skip its dots entirely (upper-triangle block pruning)
    live = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _accumulate():
        m_new, l_new, acc_new = accumulate(
            0, 0, m_scr[:][:, :1], l_scr[:][:, :1], acc[:]  # lanes: replicas
        )
        acc[:] = acc_new
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _normalize():
        write_lse(0, write(0, 0, m_scr[:][:, :1], l_scr[:][:, :1], acc[:]))


def _struct(shape, dtype, vma):
    """out_shape struct carrying the inputs' varying mesh axes: under
    shard_map the outputs inherit the inputs' vma, and check_vma rejects
    a pallas_call whose out_shape doesn't declare it."""
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _blocks(Lq, Lk, block_q, block_k):
    """Blocks clamped to short sequences (instead of padding to a full
    one), and the padded lengths they tile."""
    block_q = min(block_q, pl.cdiv(Lq, 128) * 128)
    block_k = min(block_k, pl.cdiv(Lk, 128) * 128)
    return (
        block_q, block_k,
        pl.cdiv(Lq, block_q) * block_q, pl.cdiv(Lk, block_k) * block_k,
    )


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
)


def _fwd_impl(
    q, k, v, causal, scale, interpret, block_q, block_k, precision
):
    """Run the kernel; returns (out [B,Lq,H,D], lse [B,groups·8,Lqp] f32:
    head p of group j on row 8·j + p, the padded length on the lanes)."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    t = _tiling(H, D)
    block_q, block_k, Lqp, Lkp = _blocks(Lq, Lk, block_q, block_k)
    qf = _pack(q, t, Lqp)
    kf, vf = _pack(k, t, Lkp), _pack(v, t, Lkp)
    n_q, n_k = Lqp // block_q, Lkp // block_k
    G, n = (1, 1)
    if n_q == n_k == 1:
        G, n = _group(
            B, t.groups,
            2 * (block_q + block_k) * t.width * q.dtype.itemsize,
            4 * t.heads * block_q * block_k * 4,  # s, p, mask, positions
        )
    W = n * t.width

    q_spec = pl.BlockSpec(
        (G, block_q, W), lambda b, h, qi, ki: (b, qi, h),
        memory_space=pltpu.VMEM,
    )
    kv_spec = pl.BlockSpec(
        (G, block_k, W), lambda b, h, qi, ki: (b, ki, h),
        memory_space=pltpu.VMEM,
    )
    lse_spec = pl.BlockSpec(
        (G, n * STAT_ROWS, block_q), lambda b, h, qi, ki: (b, h, qi),
        memory_space=pltpu.VMEM,
    )

    struct = partial(_struct, vma=jax.typeof(qf).vma)
    rows = t.heads * block_q
    out, lse = pl.pallas_call(
        partial(
            _flash_kernel,
            scale=scale, causal=causal, lk_true=Lk, n_k=n_k,
            G=G, n=n, t=t, precision=precision,
        ),
        grid=(B // G, t.groups // n, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            struct(qf.shape, q.dtype),
            struct((B, t.groups * STAT_ROWS, Lqp), jnp.float32),
        ],
        scratch_shapes=[] if n_k == 1 else [
            pltpu.VMEM((rows, t.width), jnp.float32),
            pltpu.VMEM((rows, MIN_D), jnp.float32),
            pltpu.VMEM((rows, MIN_D), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return _unpack(out, t, Lq, H), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(
    q, k, v, causal, scale, interpret, block_q, block_k, precision,
    bwd_block_q, bwd_block_k,
):
    out, _ = _fwd_impl(
        q, k, v, causal, scale, interpret, block_q, block_k, precision
    )
    return out


def _flash_fwd(
    q, k, v, causal, scale, interpret, block_q, block_k, precision,
    bwd_block_q, bwd_block_k,
):
    out, lse = _fwd_impl(
        q, k, v, causal, scale, interpret, block_q, block_k, precision
    )
    return out, (q, k, v, out, lse)


#: backward block size defaults — (bq, bk) f32 score/probability
#: intermediates appear 4× per step, so 512×512 (4 MB of VMEM
#: intermediates) instead of the forward's 512×1024; both kernels clamp
#: down for short sequences. Deliberately independent of the forward's
#: block args (the backward's VMEM budget — 2 grad accumulators + 4 f32
#: tiles — is its own problem); override per call via
#: ``flash_attention(..., bwd_block_q=..., bwd_block_k=...)``, which is
#: jit-cache-keyed like every other static arg.
BWD_BLOCK_Q = 512
BWD_BLOCK_K = 512


def _bwd_dkv_kernel(
    q_ref, do_ref, stat_ref, k_ref, v_ref, dk_ref, dv_ref, *carry,
    scale, causal, lq_true, lk_true, n_q, G, n, t, precision,
):
    """dk/dv pass: grid (B/G, groups/n, Lk/bk, Lq/bq), q innermost — the
    dk/dv accumulators stay in VMEM scratch across the whole q sweep.

    Everything is computed in the TRANSPOSED orientation (scores as
    [P·bk, bq], k-rows on sublanes): dv = Pᵀ·dO and dk = dSᵀ·Q contract
    the q axis, which in the row-major orientation is the sublane dim of
    both operands — a layout Mosaic must transpose before the MXU pass.
    With k on sublanes all four dots are lane-contracting or canonical
    matmuls and no relayout is ever emitted. The per-q-row statistics
    arrive as [8, bq] ROWS a head group (head p's lse on sublane p, its
    Δ on sublane P + p) for the same reason.
    """
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    def tile(g, j):
        """(dk, dv) of one (k-block, q-block) pair, stacked by head."""
        lanes = slice(j * t.width, (j + 1) * t.width)
        q = q_ref[g, :, lanes]
        do = do_ref[g, :, lanes]
        stat = stat_ref[g, j * STAT_ROWS:(j + 1) * STAT_ROWS, :]  # [8, bq]
        # sᵀ = K·Qᵀ  [P·bk, bq]
        s_t = _dot(_stack(k_ref[g, :, lanes], t), q, _NT, precision) * scale
        valid_t = _mask(
            qi, ki, block_q, block_k, lq_true, lk_true, causal, t.heads,
            transposed=True,
        )
        # exp(s - lse) ≤ 1 on live lanes (lse ≥ every s in its row); the
        # minimum clamp keeps dead lanes from overflowing before the select
        p_t = jnp.where(
            valid_t,
            jnp.exp(jnp.minimum(_minus_stat(s_t, stat, 0, t, True), 0.0)),
            0.0,
        )
        # dv = Pᵀ·dO  — canonical [P·bk, bq]·[bq, 128]
        dv = _dot(p_t.astype(do.dtype), do, _NN, precision)
        # dpᵀ = V·dOᵀ, dsᵀ = Pᵀ ∘ (dpᵀ − Δ)·scale, dk = dSᵀ·Q
        dp_t = _dot(_stack(v_ref[g, :, lanes], t), do, _NT, precision)
        ds_t = p_t * _minus_stat(dp_t, stat, t.heads, t, True) * scale
        return _dot(ds_t.astype(q.dtype), q, _NN, precision), dv

    def write(g, j, dk, dv):
        lanes = slice(j * t.width, (j + 1) * t.width)
        dk_ref[g, :, lanes] = _unstack(dk, t).astype(dk_ref.dtype)
        dv_ref[g, :, lanes] = _unstack(dv, t).astype(dv_ref.dtype)

    if n_q == 1:  # the whole sequence is this tile: nothing to carry

        def sequence(g):
            for j in range(n):
                write(g, j, *tile(g, j))

        _each_sequence(G, sequence)
        return

    dk_acc, dv_acc = carry

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # causal: a (q,k) block pair strictly above the diagonal has no live
    # lane — skip all four dots (the upper-triangle pruning the XLA scan
    # could not express; ~2× fewer MXU FLOPs on causal backward)
    live = (
        (qi * block_q + block_q - 1 >= ki * block_k) if causal else True
    )

    @pl.when(live)
    def _accumulate():
        dk, dv = tile(0, 0)
        dk_acc[:] += dk
        dv_acc[:] += dv

    @pl.when(qi == n_q - 1)
    def _write():
        write(0, 0, dk_acc[:], dv_acc[:])


def _bwd_dq_kernel(
    q_ref, do_ref, stat_ref, k_ref, v_ref, dq_ref, *carry,
    scale, causal, lq_true, lk_true, n_k, G, n, t, precision,
):
    """dq pass: grid (B/G, groups/n, Lq/bq, Lk/bk), k innermost — the dq
    accumulator stays in VMEM scratch across the whole k sweep. The
    statistics arrive as the dkv kernel's rows and are turned here, once
    a sequence, into the [bq, n·8] columns this orientation reads: of
    group j, head p's lse on lane 8·j + p and its Δ on lane 8·j + P + p."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    def tile(g, j, stat):
        """dq of one (q-block, k-block) pair, stacked by head; ``stat``
        the sequence's statistics as columns, group j's from lane 8·j."""
        lanes = slice(j * t.width, (j + 1) * t.width)
        k = k_ref[g, :, lanes]
        v = v_ref[g, :, lanes]
        s = _dot(_stack(q_ref[g, :, lanes], t), k, _NT, precision) * scale
        valid = _mask(
            qi, ki, block_q, block_k, lq_true, lk_true, causal, t.heads
        )
        p = jnp.where(
            valid,
            jnp.exp(
                jnp.minimum(
                    _minus_stat(s, stat, j * STAT_ROWS, t, False), 0.0
                )
            ),
            0.0,
        )
        dp = _dot(_stack(do_ref[g, :, lanes], t), v, _NT, precision)
        ds = (
            p * _minus_stat(dp, stat, j * STAT_ROWS + t.heads, t, False)
            * scale
        )
        # dq = ds·k
        return _dot(ds.astype(k.dtype), k, _NN, precision)

    def write(g, j, dq):
        lanes = slice(j * t.width, (j + 1) * t.width)
        dq_ref[g, :, lanes] = _unstack(dq, t).astype(dq_ref.dtype)

    def columns(g):
        # one transpose a sequence, not one a head group: [n·8, bq] rows
        # → [bq, n·8] columns
        return stat_ref[g].T

    if n_k == 1:  # the whole sequence is this tile: nothing to carry

        def sequence(g):
            stat = columns(g)
            for j in range(n):
                write(g, j, tile(g, j, stat))

        _each_sequence(G, sequence)
        return

    (dq_acc,) = carry

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = (
        (qi * block_q + block_q - 1 >= ki * block_k) if causal else True
    )

    @pl.when(live)
    def _accumulate():
        dq_acc[:] += tile(0, 0, columns(0))

    @pl.when(ki == n_k - 1)
    def _write():
        write(0, 0, dq_acc[:])


def _delta(dof: jax.Array, of: jax.Array, t: _Tiling) -> jax.Array:
    """Δ_i = rowsum(dO ∘ O), the softmax-jacobian diagonal term, of every
    head from the packed operands: ``[B, L, groups·width]`` →
    ``[B, groups, P, L]`` f32. One XLA pass, not worth a kernel: the f32
    products against a 0/1 matrix that sums each head's own lanes, at
    ``HIGHEST`` precision (f32 passes) — on the chip half the time of a
    reduction over ``D``, which relays the 64-wide heads out first."""
    B, L, lanes = dof.shape
    lane = np.arange(lanes)
    # a lane's head; the pad lanes of a lone padded head (zeros) are its own
    head = lane // t.width * t.heads + np.minimum(
        lane % t.width // t.dim, t.heads - 1
    )
    own = (head[:, None] == np.arange(t.groups * t.heads)).astype(np.float32)
    delta = jnp.einsum(
        "blk,kh->blh",
        dof.astype(jnp.float32) * of.astype(jnp.float32), own,
        precision=lax.Precision.HIGHEST,
    )
    return delta.reshape(B, L, t.groups, t.heads).transpose(0, 2, 3, 1)


def _flash_bwd(
    causal, scale, interpret, block_q, block_k, precision,
    bwd_block_q, bwd_block_k, residuals, do,
):
    """Flash backward (Dao et al. §3.1) as two Pallas kernels off the
    forward's saved per-row log-sum-exp: a dk/dv pass (q innermost) and a
    dq pass (k innermost), each with its gradient tile resident in VMEM
    f32 scratch and bf16 operands feeding every MXU dot — the streams are
    never up-cast to f32 in HBM, and are read in the caller's layout like
    the forward's. Causal block pairs strictly above the diagonal skip
    all four dots (the pruning the forward does, which the previous
    plain-XLA ``lax.scan`` backward could not express — it cost ~2× extra
    MXU work and a full f32 re-materialization of q/k/v/dO).
    Memory stays O(L·block) in both passes and compile time O(1) in L.
    """
    q, k, v, o, lse = residuals
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    t = _tiling(H, D)
    bq, bk, Lqp, Lkp = _blocks(Lq, Lk, bwd_block_q, bwd_block_k)
    n_q, n_k = Lqp // bq, Lkp // bk
    qf, dof = _pack(q, t, Lqp), _pack(do, t, Lqp)
    kf, vf = _pack(k, t, Lkp), _pack(v, t, Lkp)
    # per-q-row statistics as the forward wrote its lse, [8, Lqp] rows a
    # head group, with Δ beside it: what the transposed dkv kernel reads
    # as it is, and the dq kernel turns into columns a sequence at a time
    # (the forward's blocks may have padded L to another length than these)
    lse = _pad_to(_crop(lse, min(lse.shape[2], Lqp), 2), Lqp, 2).reshape(
        B, t.groups, STAT_ROWS, Lqp
    )
    spare = jnp.zeros(
        (B, t.groups, STAT_ROWS - 2 * t.heads, Lqp), jnp.float32
    )
    stat = jnp.concatenate(
        [lse[:, :, :t.heads], _delta(dof, _pack(o, t, Lqp), t), spare],
        axis=2,
    ).reshape(B, t.groups * STAT_ROWS, Lqp)

    G, n = (1, 1)
    if n_q == n_k == 1:
        G, n = _group(
            B, t.groups, 3 * (bq + bk) * t.width * q.dtype.itemsize,
            6 * t.heads * bq * bk * 4,  # s, p, dp, ds, mask, positions
        )
    W = n * t.width
    grid = (B // G, t.groups // n)
    struct = partial(_struct, vma=jax.typeof(qf).vma)

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    def scratch(rows, count, sweep):
        """f32 accumulators, stacked by head; none for a one-step sweep."""
        if sweep == 1:
            return []
        return [
            pltpu.VMEM((t.heads * rows, t.width), jnp.float32)
        ] * count

    common = dict(
        scale=scale, causal=causal, lq_true=Lq, lk_true=Lk, G=G, n=n, t=t,
        precision=precision,
    )
    dkv_q = spec((G, bq, W), lambda b, h, ki, qi: (b, qi, h))
    dkv_kv = spec((G, bk, W), lambda b, h, ki, qi: (b, ki, h))
    dk, dv = pl.pallas_call(
        partial(_bwd_dkv_kernel, n_q=n_q, **common),
        grid=(*grid, n_k, n_q),
        in_specs=[
            dkv_q,  # q
            dkv_q,  # do
            spec(
                (G, n * STAT_ROWS, bq), lambda b, h, ki, qi: (b, h, qi)
            ),  # lse, Δ
            dkv_kv,  # k
            dkv_kv,  # v
        ],
        out_specs=[dkv_kv, dkv_kv],
        out_shape=[struct(kf.shape, k.dtype), struct(vf.shape, v.dtype)],
        scratch_shapes=scratch(bk, 2, n_q),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, dof, stat, kf, vf)

    dq_q = spec((G, bq, W), lambda b, h, qi, ki: (b, qi, h))
    dq_kv = spec((G, bk, W), lambda b, h, qi, ki: (b, ki, h))
    dq = pl.pallas_call(
        partial(_bwd_dq_kernel, n_k=n_k, **common),
        grid=(*grid, n_q, n_k),
        in_specs=[
            dq_q,  # q
            dq_q,  # do
            spec(
                (G, n * STAT_ROWS, bq), lambda b, h, qi, ki: (b, h, qi)
            ),  # lse, Δ
            dq_kv,  # k
            dq_kv,  # v
        ],
        out_specs=dq_q,
        out_shape=struct(qf.shape, q.dtype),
        scratch_shapes=scratch(bq, 1, n_k),
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, dof, stat, kf, vf)

    return _unpack(dq, t, Lq, H), _unpack(dk, t, Lk, H), _unpack(dv, t, Lk, H)


_flash.defvjp(_flash_fwd, _flash_bwd)


@partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "interpret", "block_q", "block_k", "precision",
        "bwd_block_q", "bwd_block_k",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: float | None = None,
    interpret: bool = False,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    precision: lax.Precision | None = None,
    bwd_block_q: int = BWD_BLOCK_Q,
    bwd_block_k: int = BWD_BLOCK_K,
) -> jax.Array:
    """Fused attention, [B, L, H, D] (the layout `attention` uses).

    Any (Lq, Lk, H, D). The kernels read that layout as it is: heads
    that divide a 128-lane tile share it (two 64-wide heads a tile, no
    pad, no transpose), and a sequence that fits one block takes one
    grid step for all its heads, several sequences a step while VMEM
    holds them; what the blocks hold follows from the shapes alone (see
    the module docstring). Ragged lengths are zero-padded to block
    multiples and pad keys masked by position; an odd head count gets
    whole heads of padding, any other width each head's lanes.
    ``causal`` requires Lq == Lk (self-attention alignment).
    ``interpret=True`` runs the kernel on CPU for tests.

    Differentiable: the forward kernel saves each query row's
    log-sum-exp, and a custom VJP runs the flash backward as two Pallas
    kernels (dk/dv and dq, ``bwd_block_q``/``bwd_block_k`` tiles) —
    O(L·block) memory in both directions, so long-context TRAINING fits
    where the XLA path cannot even materialize the scores.

    ``precision`` reaches both MXU dots: the default (None) feeds the MXU
    bf16 operands with f32 accumulation — the standard TPU trade, and
    what f32 inputs get from plain XLA too; pass
    ``lax.Precision.HIGHEST`` for full-f32 operand passes when attention
    scores must match a float32 reference bit-closely.
    """
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    if causal and Lq != Lk:
        raise ValueError("causal flash_attention requires Lq == Lk")
    scale_ = scale if scale is not None else D**-0.5
    return _flash(
        q, k, v, causal, scale_, interpret, block_q, block_k, precision,
        bwd_block_q, bwd_block_k,
    )
