"""Pallas TPU flash-attention kernel — the dense-attention hot op.

The XLA path (:func:`pygrid_tpu.parallel.ring_attention.attention`)
materializes the [B,H,Lq,Lk] score tensor in HBM: at L=8K heads=8 that is
2 GB per batch element per pass, and bandwidth — not the MXU — bounds it.
This kernel runs the standard flash-attention recurrence (online softmax,
Dao et al.) with the score block resident in VMEM:

- grid ``(B·H, Lq/BLOCK_Q, Lk/BLOCK_K)``, K innermost ("arbitrary") so
  the output tile and the (m, l) running statistics stay in VMEM scratch
  across the whole K sweep — HBM sees one read of Q/K/V and one write of
  O, never the L×L scores;
- both dots (``q·kᵀ`` and ``p·v``) hit the MXU in f32 accumulation;
  inputs may be bf16 (halved K/V streaming traffic);
- fully-masked causal blocks are skipped via ``pl.when`` on the block
  ids — ~2× fewer FLOPs for causal at no accuracy cost;
- masked lanes are zeroed AFTER the exp (an all-masked block would
  otherwise renormalize to uniform — the classic flash pitfall), and the
  final divide guards l=0 rows (fully padded queries).

Correctness contract: matches the XLA reference to f32 tolerance for any
(Lq, Lk, D) — ragged lengths are zero-padded to tile multiples and the
pad keys masked by position (tests run interpret mode on CPU; the TPU
path is exercised by bench/e2e).

No reference analog: the reference has no attention at all (SURVEY §5.7);
this kernel exists because long-context is first-class here. Consume it
via the transformer's injectable attention
(``transformer.apply(..., attn_fn=flash_attention)``) or call it
directly; ``bench.py bench_attention()`` is the reproducible comparison
against the XLA path.
"""

from __future__ import annotations

from functools import partial

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
#: head-dim tile floor: Mosaic wants the minor dim in 128-lane multiples
MIN_D = 128

_NEG = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
    *, scale, causal, lk_true, n_k, block_q, block_k, precision,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

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
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        ) * scale  # [BQ, BK]

        k_pos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = k_pos < lk_true  # pad keys contribute nothing
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG)

        m_prev = m_scr[:][:, :1]  # [BQ, 1] (lanes are replicas)
        l_prev = l_scr[:][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # zero masked lanes AFTER exp: if every lane were masked,
        # exp(s - m_new) = exp(0) = 1 would fake a uniform distribution
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)  # [BQ, 1]
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[:] = acc[:] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _normalize():
        l_final = l_scr[:][:, :1]
        o_ref[0] = (
            acc[:] / jnp.maximum(l_final, 1e-30)
        ).astype(o_ref.dtype)
        # log-sum-exp per query row — the residual the backward pass
        # needs to re-derive P = exp(s - lse) blockwise without ever
        # materializing the full score tensor. 8 lanes per row, not a
        # full 128-lane broadcast: Mosaic's block rule needs the minor
        # dim ÷128 OR equal to the array's — 8 satisfies the latter at
        # 1/16th the HBM write traffic
        lse = m_scr[:][:, :1] + jnp.log(jnp.maximum(l_final, 1e-30))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _struct(shape, dtype, vma):
    """out_shape struct carrying the inputs' varying mesh axes: under
    shard_map the outputs inherit the inputs' vma, and check_vma rejects
    a pallas_call whose out_shape doesn't declare it."""
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _pad_to(x: jax.Array, length: int, axis: int) -> jax.Array:
    pad = length - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fwd_impl(
    q, k, v, causal, scale, interpret, block_q, block_k, precision
):
    """Run the kernel; returns (out [B,Lq,H,D], lse [B·H,Lq] f32)."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]

    # [B, L, H, D] → [B·H, L, D]
    def to_bhld(x):
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2], x.shape[1], D)

    qf, kf, vf = to_bhld(q), to_bhld(k), to_bhld(v)
    # short sequences shrink the blocks instead of padding to a full one
    block_q = min(block_q, pl.cdiv(Lq, 128) * 128)
    block_k = min(block_k, pl.cdiv(Lk, 128) * 128)
    Lqp = pl.cdiv(Lq, block_q) * block_q
    Lkp = pl.cdiv(Lk, block_k) * block_k
    Dp = pl.cdiv(D, MIN_D) * MIN_D
    qf = _pad_to(_pad_to(qf, Lqp, 1), Dp, 2)
    kf = _pad_to(_pad_to(kf, Lkp, 1), Dp, 2)
    vf = _pad_to(_pad_to(vf, Lkp, 1), Dp, 2)
    n_k = Lkp // block_k

    q_spec = pl.BlockSpec(
        (1, block_q, Dp), lambda bh, qi, ki: (bh, qi, 0),
        memory_space=pltpu.VMEM,
    )
    kv_spec = pl.BlockSpec(
        (1, block_k, Dp), lambda bh, qi, ki: (bh, ki, 0),
        memory_space=pltpu.VMEM,
    )
    o_spec = pl.BlockSpec(
        (1, block_q, Dp), lambda bh, qi, ki: (bh, qi, 0),
        memory_space=pltpu.VMEM,
    )
    lse_spec = pl.BlockSpec(
        (1, block_q, 8), lambda bh, qi, ki: (bh, qi, 0),
        memory_space=pltpu.VMEM,
    )

    vma = jax.typeof(qf).vma
    struct = partial(_struct, vma=vma)

    out, lse = pl.pallas_call(
        partial(
            _flash_kernel,
            scale=scale, causal=causal, lk_true=Lk, n_k=n_k,
            block_q=block_q, block_k=block_k, precision=precision,
        ),
        grid=(B * H, Lqp // block_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[o_spec, lse_spec],
        out_shape=[
            struct((B * H, Lqp, Dp), q.dtype),
            struct((B * H, Lqp, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dp), jnp.float32),
            pltpu.VMEM((block_q, MIN_D), jnp.float32),
            pltpu.VMEM((block_q, MIN_D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    # [B·H, Lqp, Dp] → [B, Lq, H, D]
    out = out[:, :Lq, :D].reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
    return out, lse[:, :Lq, 0]


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


def _mask(qi, ki, block_q, block_k, lq_true, lk_true, causal,
          transposed=False):
    """Validity mask for one (q-block, k-block) score tile: pad queries
    and pad keys contribute nothing; causal keeps the lower triangle.
    ``transposed=True`` lays the tile out as [bk, bq] (k on sublanes, q
    on lanes — the dkv kernel's orientation); the causal/pad semantics
    are identical, keeping one source of truth for both kernels."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    q_dim = 1 if transposed else 0
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, shape, q_dim)
    k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    # pad-q rows carry lse=0 from the re-pad: exp(s-0) is finite but
    # wrong, so q validity must be part of the mask (the forward only
    # needed k validity — its pad-q rows were sliced off)
    valid = jnp.logical_and(q_pos < lq_true, k_pos < lk_true)
    if causal:
        valid = jnp.logical_and(valid, q_pos >= k_pos)
    return valid


def _bwd_dkv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale, causal, lq_true, lk_true, n_q, block_q, block_k, precision,
):
    """dk/dv pass: grid (B·H, Lk/bk, Lq/bq), q innermost — the dk/dv
    accumulators stay in VMEM scratch across the whole q sweep.

    Everything is computed in the TRANSPOSED orientation (scores as
    [bk, bq], k-rows on sublanes): dv = Pᵀ·dO and dk = dSᵀ·Q contract
    the q axis, which in the row-major orientation is the sublane dim of
    both operands — a layout Mosaic must transpose before the MXU pass.
    With k on sublanes all four dots are lane-contracting or canonical
    matmuls and no relayout is ever emitted. The per-q-row statistics
    arrive as [8, bq] ROWS (lse/Δ broadcast over 8 sublanes) for the
    same reason.
    """
    ki = pl.program_id(1)
    qi = pl.program_id(2)

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
        q = q_ref[0]
        do = do_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        lse = lse_ref[0][:1, :]      # [1, bq] f32 row
        delta = delta_ref[0][:1, :]  # [1, bq] f32 row
        # sᵀ = K·Qᵀ  [bk, bq]
        s_t = lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        ) * scale
        valid_t = _mask(
            qi, ki, block_q, block_k, lq_true, lk_true, causal,
            transposed=True,
        )
        # exp(s - lse) ≤ 1 on live lanes (lse ≥ every s in its row); the
        # minimum clamp keeps dead lanes from overflowing before the select
        p_t = jnp.where(
            valid_t, jnp.exp(jnp.minimum(s_t - lse, 0.0)), 0.0
        )
        # dv += Pᵀ·dO  — canonical [bk, bq]·[bq, Dp]
        dv_acc[:] += lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )
        # dpᵀ = V·dOᵀ, dsᵀ = Pᵀ ∘ (dpᵀ − Δ)·scale, dk += dSᵀ·Q
        dp_t = lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )
        ds_t = p_t * (dp_t - delta) * scale
        dk_acc[:] += lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )

    @pl.when(qi == n_q - 1)
    def _write():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref, dq_acc,
    *, scale, causal, lq_true, lk_true, n_k, block_q, block_k, precision,
):
    """dq pass: grid (B·H, Lq/bq, Lk/bk), k innermost — the dq
    accumulator stays in VMEM scratch across the whole k sweep."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = (
        (qi * block_q + block_q - 1 >= ki * block_k) if causal else True
    )

    @pl.when(live)
    def _accumulate():
        q = q_ref[0]
        do = do_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        ) * scale
        valid = _mask(qi, ki, block_q, block_k, lq_true, lk_true, causal)
        p = jnp.where(valid, jnp.exp(jnp.minimum(s - lse, 0.0)), 0.0)
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )
        ds = p * (dp - delta) * scale
        # dq += ds·k
        dq_acc[:] += lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )

    @pl.when(ki == n_k - 1)
    def _write():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(
    causal, scale, interpret, block_q, block_k, precision,
    bwd_block_q, bwd_block_k, residuals, do,
):
    """Flash backward (Dao et al. §3.1) as two Pallas kernels off the
    forward's saved per-row log-sum-exp: a dk/dv pass (q innermost) and a
    dq pass (k innermost), each with its gradient tile resident in VMEM
    f32 scratch and bf16 operands feeding every MXU dot — the streams are
    never up-cast to f32 in HBM. Causal block pairs strictly above the
    diagonal skip all four dots (the pruning the forward does, which the
    previous plain-XLA ``lax.scan`` backward could not express — it cost
    ~2× extra MXU work and a full f32 re-materialization of q/k/v/dO).
    Memory stays O(L·block) in both passes and compile time O(1) in L.
    """
    q, k, v, o, lse = residuals
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    in_dtypes = (q.dtype, k.dtype, v.dtype)

    def to_bhld(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    qf, kf, vf, dof = map(to_bhld, (q, k, v, do))
    # Δ_i = rowsum(dO ∘ O) — the softmax-jacobian diagonal term (one
    # fused XLA pass; not worth a kernel)
    delta = jnp.sum(
        dof.astype(jnp.float32) * to_bhld(o).astype(jnp.float32),
        axis=-1,
    )  # [BH, Lq]

    bq = min(bwd_block_q, pl.cdiv(Lq, 128) * 128)
    bk = min(bwd_block_k, pl.cdiv(Lk, 128) * 128)
    Lqp = pl.cdiv(Lq, bq) * bq
    Lkp = pl.cdiv(Lk, bk) * bk
    Dp = pl.cdiv(D, MIN_D) * MIN_D
    n_q = Lqp // bq
    n_k = Lkp // bk
    qf = _pad_to(_pad_to(qf, Lqp, 1), Dp, 2)
    dof = _pad_to(_pad_to(dof, Lqp, 1), Dp, 2)
    kf = _pad_to(_pad_to(kf, Lkp, 1), Dp, 2)
    vf = _pad_to(_pad_to(vf, Lkp, 1), Dp, 2)
    # per-q-row statistics in both orientations (the forward's Mosaic
    # block-rule trick): [Lqp, 8] columns for the dq kernel, [8, Lqp]
    # rows for the transposed dkv kernel — each reads with no relayout
    lse8 = _pad_to(
        jnp.broadcast_to(lse[:, :, None], (B * H, Lq, 8)), Lqp, 1
    )
    delta8 = _pad_to(
        jnp.broadcast_to(delta[:, :, None], (B * H, Lq, 8)), Lqp, 1
    )
    lse_t8 = _pad_to(
        jnp.broadcast_to(lse[:, None, :], (B * H, 8, Lq)), Lqp, 2
    )
    delta_t8 = _pad_to(
        jnp.broadcast_to(delta[:, None, :], (B * H, 8, Lq)), Lqp, 2
    )

    vma = jax.typeof(qf).vma
    struct = partial(_struct, vma=vma)

    def kv_specs(index):
        return [
            pl.BlockSpec((1, bk, Dp), index, memory_space=pltpu.VMEM)
            for _ in range(2)
        ]

    dkv_q_index = lambda bh, ki, qi: (bh, qi, 0)  # noqa: E731
    dkv_stat_index = lambda bh, ki, qi: (bh, 0, qi)  # noqa: E731
    dk, dv = pl.pallas_call(
        partial(
            _bwd_dkv_kernel,
            scale=scale, causal=causal, lq_true=Lq, lk_true=Lk, n_q=n_q,
            block_q=bq, block_k=bk, precision=precision,
        ),
        grid=(B * H, n_k, n_q),
        in_specs=[
            pl.BlockSpec(
                (1, bq, Dp), dkv_q_index, memory_space=pltpu.VMEM
            ),  # q
            pl.BlockSpec(
                (1, bq, Dp), dkv_q_index, memory_space=pltpu.VMEM
            ),  # do
            pl.BlockSpec(
                (1, 8, bq), dkv_stat_index, memory_space=pltpu.VMEM
            ),  # lseᵀ
            pl.BlockSpec(
                (1, 8, bq), dkv_stat_index, memory_space=pltpu.VMEM
            ),  # Δᵀ
        ] + kv_specs(lambda bh, ki, qi: (bh, ki, 0)),
        out_specs=[
            pl.BlockSpec(
                (1, bk, Dp), lambda bh, ki, qi: (bh, ki, 0),
                memory_space=pltpu.VMEM,
            )
            for _ in range(2)
        ],
        out_shape=[
            struct((B * H, Lkp, Dp), k.dtype),
            struct((B * H, Lkp, Dp), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, Dp), jnp.float32),
            pltpu.VMEM((bk, Dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, dof, lse_t8, delta_t8, kf, vf)

    dq_q_index = lambda bh, qi, ki: (bh, qi, 0)  # noqa: E731
    dq = pl.pallas_call(
        partial(
            _bwd_dq_kernel,
            scale=scale, causal=causal, lq_true=Lq, lk_true=Lk, n_k=n_k,
            block_q=bq, block_k=bk, precision=precision,
        ),
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec(
                (1, bq, Dp), dq_q_index, memory_space=pltpu.VMEM
            ),  # q
            pl.BlockSpec(
                (1, bq, Dp), dq_q_index, memory_space=pltpu.VMEM
            ),  # do
            pl.BlockSpec(
                (1, bq, 8), dq_q_index, memory_space=pltpu.VMEM
            ),  # lse
            pl.BlockSpec(
                (1, bq, 8), dq_q_index, memory_space=pltpu.VMEM
            ),  # Δ
        ] + kv_specs(lambda bh, qi, ki: (bh, ki, 0)),
        out_specs=pl.BlockSpec(
            (1, bq, Dp), lambda bh, qi, ki: (bh, qi, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=struct((B * H, Lqp, Dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, Dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, dof, lse8, delta8, kf, vf)

    def back(x, L_true, dtype):
        return (
            x[:, :L_true, :D]
            .reshape(B, H, L_true, D)
            .transpose(0, 2, 1, 3)
            .astype(dtype)
        )

    return (
        back(dq, Lq, in_dtypes[0]),
        back(dk, Lk, in_dtypes[1]),
        back(dv, Lk, in_dtypes[2]),
    )


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

    Any (Lq, Lk, D): inputs are zero-padded to tile multiples and pad
    keys masked by position. ``causal`` requires Lq == Lk (self-attention
    alignment). ``interpret=True`` runs the kernel on CPU for tests.

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
