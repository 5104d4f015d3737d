"""Long-context attention parallelism: ring attention + Ulysses all-to-all.

The reference has no attention anywhere (SURVEY.md §5.7 — its largest model
is an MNIST MLP), but a TPU-native framework must scale context as a
first-class capability. Two standard sequence-parallel schemes, both built on
``shard_map`` over a ``"seq"`` mesh axis so the collectives ride ICI:

- **Ring attention** (:func:`ring_attention`): Q stays put; K/V blocks rotate
  around the ring via ``lax.ppermute`` while each device accumulates its
  queries' attention with a numerically-stable online softmax (flash-style
  running max/sum). Memory per device is O(L/P · L/P) per step instead of
  O(L²); the P permute steps overlap compute with ICI transfers.
- **Ulysses / all-to-all sequence parallelism** (:func:`ulysses_attention`):
  ``lax.all_to_all`` re-shards [seq-sharded, all heads] → [full seq,
  head-sharded], runs dense attention per local head group, and re-shards
  back. Cheaper collectives when heads ≥ devices; exact by construction.

Both are exact (not approximations) — tests compare against
:func:`attention` on a virtual 8-device mesh.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG = -1e30  # finite "-inf": keeps fully-masked blocks NaN-free in exp()


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Plain full attention, [B, L, H, D] — the single-device reference.

    Scores and softmax always accumulate in float32 (matching the flash
    kernel's ``preferred_element_type``): with bf16 inputs a bf16
    softmax denominator drifts as L grows. Output returns at the input
    dtype."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = (
        jnp.einsum(
            "bqhd,bkhd->bhqk", q, k,
            preferred_element_type=jnp.float32,
        )
        * scale
    )
    if causal:
        L, Lk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(L)[:, None] >= jnp.arange(Lk)[None, :]
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _block_accumulate(q, k_blk, v_blk, o, l, m, scale, q_pos, k_pos, causal):
    """Online-softmax accumulation of one K/V block into (o, l, m).

    o: [B,H,Lq,D] running (unnormalised) output, l: [B,H,Lq] running softmax
    denominator, m: [B,H,Lq] running max. Standard flash-attention
    recurrence; scores and the running statistics accumulate in float32
    regardless of input dtype (same contract as the dense reference and
    the Pallas kernel — a bf16 denominator drifts as L grows).
    """
    s = (
        jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_blk,
            preferred_element_type=jnp.float32,
        )
        * scale
    )
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32,
    )
    return o_new, l_new, m_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Exact attention with Q/K/V sharded over ``axis`` on their length dim.

    Global shapes [B, L, H, D]; L must divide by the mesh axis size. Each of
    the P ring steps attends local queries to the currently-held K/V block,
    then rotates K/V one hop (``ppermute``) so block t on device i is the one
    originally owned by device (i - t) mod P — which makes the causal
    block-position arithmetic local and static-shape-friendly.
    """
    p_sz = mesh.shape[axis]
    scale_ = scale if scale is not None else q.shape[-1] ** -0.5
    perm = [(i, (i + 1) % p_sz) for i in range(p_sz)]

    def inner(q, k, v):
        B, Lq, H, D = q.shape
        Lk = k.shape[1]
        my = lax.axis_index(axis)
        q_pos = my * Lq + jnp.arange(Lq)

        def accumulate(t, k_blk, v_blk, o, l, m):
            kv_idx = (my - t) % p_sz
            k_pos = kv_idx * Lk + jnp.arange(Lk)
            if not causal:
                return _block_accumulate(
                    q, k_blk, v_blk, o, l, m, scale_, q_pos, k_pos, causal
                )
            # fully-masked blocks (kv block strictly after the q block)
            # contribute nothing — skip their einsum/exp work entirely;
            # the conditional HLO runs only the taken branch per device
            return lax.cond(
                kv_idx <= my,
                lambda: _block_accumulate(
                    q, k_blk, v_blk, o, l, m, scale_, q_pos, k_pos, causal
                ),
                lambda: (o, l, m),
            )

        def body(t, carry):
            k_blk, v_blk, o, l, m = carry
            o, l, m = accumulate(t, k_blk, v_blk, o, l, m)
            k_blk = lax.ppermute(k_blk, axis, perm)
            v_blk = lax.ppermute(v_blk, axis, perm)
            return k_blk, v_blk, o, l, m

        # fresh accumulators are replication-typed; mark them device-varying
        # so the fori_loop carry matches the ppermute-varying K/V blocks
        # running stats in f32 regardless of q.dtype (see _block_accumulate)
        o = lax.pcast(
            jnp.zeros((B, H, Lq, D), jnp.float32), axis, to="varying"
        )
        l = lax.pcast(jnp.zeros((B, H, Lq), jnp.float32), axis, to="varying")
        m = lax.pcast(
            jnp.full((B, H, Lq), _NEG, jnp.float32), axis, to="varying"
        )
        # p_sz-1 rotate steps in the loop; the last block needs no ppermute
        k, v, o, l, m = lax.fori_loop(0, p_sz - 1, body, (k, v, o, l, m))
        o, l, m = accumulate(p_sz - 1, k, v, o, l, m)
        out = jnp.einsum("bhqd->bqhd", o / l[..., None])
        return out.astype(q.dtype)

    spec = P(None, axis, None, None)
    return shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = False,
    scale: float | None = None,
    attn_fn=None,
) -> jax.Array:
    """Exact attention via head↔sequence all-to-all re-sharding.

    Global [B, L, H, D] sharded on L; requires H % mesh.shape[axis] == 0.
    ``all_to_all`` turns the local [B, L/P, H, D] into [B, L, H/P, D] (full
    sequence, local head group), dense attention runs per head group, and a
    second ``all_to_all`` restores sequence sharding.

    ``attn_fn`` swaps the per-head-group dense attention — pass
    :func:`pygrid_tpu.parallel.pallas_attention.flash_attention` to run the
    Pallas kernel inside the all-to-all scheme (full sequence per device,
    so the O(L²)→O(L) memory win applies where it matters most).
    """
    p_sz = mesh.shape[axis]
    if q.shape[2] % p_sz != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by mesh axis "
            f"{axis!r} ({p_sz}); use ring_attention instead"
        )
    attn = attn_fn or attention

    def inner(q, k, v):
        a2a = partial(
            lax.all_to_all, axis_name=axis, split_axis=2, concat_axis=1,
            tiled=True,
        )
        out = attn(a2a(q), a2a(k), a2a(v), causal=causal, scale=scale)
        return lax.all_to_all(
            out, axis_name=axis, split_axis=1, concat_axis=2, tiled=True
        )

    spec = P(None, axis, None, None)
    # injected kernels (pallas interpret mode especially) trip jax's strict
    # varying-axes checker inside shard_map — a jax-side limitation its own
    # error message says to work around this way; the default dense path
    # keeps full checking
    sm_kwargs = {} if attn_fn is None else {"check_vma": False}
    return shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        **sm_kwargs,
    )(q, k, v)
