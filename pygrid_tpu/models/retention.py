"""Power retention of degree 2 with a scalar forget gate a head (Manifest
AI, "Scaling Context Requires Rethinking Attention", arXiv:2507.04239): the
mixer of :mod:`pygrid_tpu.models.brumby`, in the two forms serving needs.

For one K/V head and each of the ``R`` query heads that share it, with
``g_t`` in (0, 1] the gate and ``c`` the scale::

    a_tj = g_{j+1} .. g_t (c q_t . k_j)^2                 (j <= t; a_tt has no gate)
    y_t  = sum_j a_tj v_j / (sum_j a_tj + EPS)

A square of a dot product is a dot product of squares' worth of monomials:
with ``phi`` below, ``phi(x) . phi(y) = (x . y)^2``, so the sums over ``j``
are carried as a state, zero at a prompt's start::

    S_t = g_t S_{t-1} + v_t phi(c^1/2 k_t)^T       [d_v, F d_k]
    z_t = g_t z_{t-1} + phi(c^1/2 k_t)             [F, d_k]
    y_t = S_t phi(c^1/2 q_t) / (phi(c^1/2 q_t) . z_t + EPS)

**The feature map is lane rotations.** :func:`phi` of ``x`` [d_k] has ``F =
d_k / 2 + 1`` rows of ``d_k``: row ``d`` holds ``x_a x_{a+d mod d_k}`` for
every ``a``, times 1 in rows 0 (the squares) and ``d_k / 2``, times ``sqrt
2`` between. Rotations ``d`` and ``d_k - d`` pair the same entries, so rows
``1 .. d_k/2 - 1`` hold every pair at a distance other than ``d_k / 2``
once (weight ``sqrt 2``: it is two of the square's terms) and row ``d_k /
2`` holds each of its pairs twice (weight 1 each): 8,320 numbers at ``d_k``
128 where the mathematics needs 8,256 monomials, 0.8% more, for rows that
are whole lane rows and a map that needs no gather. The state keeps ``d_v``
on the sublanes and ``(d, a)`` on the lanes, so the rank-one update is a
row broadcast and the read-out a product contracting the lanes of both
(``q k^T``'s shape).

:func:`step` is one position of each of ``w`` rows: a decode step.
:func:`chunked` runs a sequence ``CHUNK`` positions at a time from a carried
state: within a chunk the quadratic form under its decay mask (the FIRST
form above, exact whatever the length: no switch between forms), across
chunks ``S`` and ``z``. Both are exact at every length. The decays stay in
log space, float32, summed within a chunk; ``exp`` is only ever taken of a
sum that is not positive.

On a TPU at head sizes of 128 they are two Pallas kernels that work on the
cache where it lies (``input_output_aliases``: a slot's state is read once
and written once): ``retention_step`` (a grid step a row and head: decay,
rank-one update and the ``R`` query heads' read-out in the one pass over
the head's 4.3 MB) and ``retention_chunk`` (a grid step a head and chunk,
the head's state resident in VMEM over its chunks; chunks that hold
nothing but a bucket's padding are skipped). Everywhere else (the CPU,
tier-1's tiny widths) the same mathematics through XLA on a slice of the
cache. A padded position must arrive with ``k = 0`` and ``log_g = 0``: it
then neither decays the state nor feeds it.

The chunked form's matrix products take their operands in ``mm_dtype`` (the
served weights' type: bfloat16 on the chip) and accumulate in float32; in
float32 they run at full precision. A step's read-out is float32 at full
precision whatever the weights' type: it is one small product a lane row
beside 8.5 MB of state traffic a head, and the kernel takes the same 2.2 ms a
layer of 20 rows either way (my chip run, PR 44). The state, the decays, the
feature map and every elementwise step are float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pygrid_tpu.models.kda import _NN, _NT, _dot

#: added to the normaliser (the configuration has no key for it)
EPS = 1e-5
#: positions a chunk of the chunked form
CHUNK = 128
#: VMEM the kernels ask for: a head's state in and out, double buffered,
#: is 17 MB of it
_VMEM_BYTES = 96 * 1024 * 1024


def rows(dk: int) -> int:
    """``F``: rows of ``d_k`` the feature map has."""
    return dk // 2 + 1


def _weight(d: int, dk: int) -> float:
    return 1.0 if d in (0, dk // 2) else 2.0**0.5


def _row(x: jax.Array, d: int) -> jax.Array:
    """Row ``d`` of the feature map of ``x`` [..., d_k]: ``x_a x_{a+d}``."""
    dk = x.shape[-1]
    return x * x if d == 0 else x * jnp.roll(x, dk - d, -1) * _weight(d, dk)


def phi(x: jax.Array) -> jax.Array:
    """The feature map of ``x`` [..., d_k] (even ``d_k``): [..., F, d_k]
    with ``phi(x) . phi(y) = (x . y)^2`` summed over both axes."""
    return jnp.stack([_row(x, d) for d in range(rows(x.shape[-1]))], axis=-2)


def state_shapes(layers: int, slots: int, heads: int, dk: int, dv: int):
    """The cache's two state arrays, float32: ``S`` [layers, slots, heads,
    d_v, F d_k] and ``z`` [layers, slots, heads, F, d_k]."""
    F = rows(dk)
    return (layers, slots, heads, dv, F * dk), (layers, slots, heads, F, dk)


def eligible(dk: int, dv: int) -> bool:
    """True where the two forms take the kernels: on a TPU, with heads that
    fill whole 128-lane rows."""
    return jax.default_backend() == "tpu" and dk == 128 and dv % 128 == 0


def chunks_run(length, padded: int, dk: int, dv: int, kernel=None):
    """(chunks that hold a sequence's own ``length`` positions, chunks
    :func:`chunked` runs over its ``padded`` positions): the kernel skips
    a chunk that is all padding, the scan through XLA runs every one."""
    held = -(-length // CHUNK)
    if eligible(dk, dv) if kernel is None else kernel:
        return held, held
    return held, jnp.full_like(held, -(-padded // CHUNK))


def _scaled(q, k, scale):
    root = float(scale) ** 0.5
    return q.astype(jnp.float32) * root, k.astype(jnp.float32) * root


# ── one position a row ───────────────────────────────────────────────────


def _step_math(S, z, q, k, v, log_g, dot):
    """``S`` [w, G, d_v, F d_k], ``z`` [w, G, F, d_k], ``q`` [w, G, R,
    d_k], ``k`` [w, G, d_k], ``v`` [w, G, d_v], ``log_g`` [w, G]."""
    w, G, F, dk = z.shape
    g = jnp.exp(log_g)
    pk, pq = phi(k), phi(q).reshape(w, G, -1, F * dk)
    z = g[..., None, None] * z + pk
    S = g[..., None, None] * S + v[..., :, None] * pk.reshape(w, G, 1, F * dk)
    num = jax.vmap(jax.vmap(lambda a, b: dot(a, b, _NT)))(pq, S)
    den = jnp.einsum("wgrf,wgf->wgr", pq, z.reshape(w, G, F * dk))
    return num / (den[..., None] + EPS), S, z


def _step_kernel(dec_ref, q_ref, k_ref, v_ref, s_ref, z_ref, y_ref, so_ref,
                 zo_ref, *, heads):
    dot = _dot(jnp.float32)
    dk, dv = q_ref.shape[-1], v_ref.shape[-1]
    dec = dec_ref[pl.program_id(0) * heads + pl.program_id(1)]
    q, k = q_ref[0, 0], k_ref[0, 0]  # [R8, d_k], [1, d_k]
    # v down the sublanes: every lane of row i holds v_i
    vcol = jnp.broadcast_to(v_ref[0, 0], (dk, dv)).T
    num = jnp.zeros((q.shape[0], dv), jnp.float32)
    den = jnp.zeros(q.shape, jnp.float32)
    for d in range(rows(dk)):
        at = slice(d * dk, (d + 1) * dk)
        kd, qd = _row(k, d), _row(q, d)
        zd = dec * z_ref[0, 0, 0, d : d + 1, :] + kd
        zo_ref[0, 0, 0, d : d + 1, :] = zd
        den = den + qd * zd
        s = dec * s_ref[0, 0, 0, :, at] + vcol * kd
        so_ref[0, 0, 0, :, at] = s
        num = num + dot(qd, s, _NT)
    y_ref[0, 0] = num / (jnp.sum(den, axis=1, keepdims=True) + EPS)


def _step_call(S, z, layer, q, k, v, log_g, interpret):
    """``retention_step`` over the first ``w`` rows of layer ``layer`` of
    the cache's state, in place."""
    w, G, R, dk = q.shape
    dv = v.shape[-1]
    F = rows(dk)
    R8 = -(-R // 8) * 8
    q = jnp.pad(q, ((0, 0), (0, 0), (0, R8 - R), (0, 0)))
    row = lambda i, g, dec: (i, g, 0, 0)  # noqa: E731
    here = lambda i, g, dec: (layer, i, g, 0, 0)  # noqa: E731
    y, S, z = pl.pallas_call(
        functools.partial(_step_kernel, heads=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(w, G),
            in_specs=[
                pl.BlockSpec((1, 1, R8, dk), row),
                pl.BlockSpec((1, 1, 1, dk), row),
                pl.BlockSpec((1, 1, 1, dv), row),
                pl.BlockSpec((1, 1, 1, dv, F * dk), here),
                pl.BlockSpec((1, 1, 1, F, dk), here),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, R8, dv), row),
                pl.BlockSpec((1, 1, 1, dv, F * dk), here),
                pl.BlockSpec((1, 1, 1, F, dk), here),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((w, G, R8, dv), jnp.float32),
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
        ],
        # operands count the scalar prefetch: S is the fifth, z the sixth
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
        name="retention_step",
    )(
        jnp.exp(log_g).reshape(w * G), q, k[:, :, None], v[:, :, None], S, z,
    )
    return y[:, :, :R], S, z


def step(
    S: jax.Array,
    z: jax.Array,
    layer: int,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    log_g: jax.Array,
    scale: float,
    kernel: bool | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One position of each of the first ``w`` slots, through layer
    ``layer`` of the cache's state (:func:`state_shapes`). ``q`` [w, G, R,
    d_k], ``k`` [w, G, d_k], ``v`` [w, G, d_v], ``log_g`` [w, G] float32.
    Returns (``y`` [w, G, R, d_v], ``S``, ``z``) with those rows' state
    advanced; every other row and layer is as it was."""
    w, dk, dv = q.shape[0], q.shape[-1], v.shape[-1]
    q, k = _scaled(q, k, scale)
    v, log_g = v.astype(jnp.float32), log_g.astype(jnp.float32)
    with jax.named_scope("retention.step"):
        if eligible(dk, dv) if kernel is None else kernel:
            return _step_call(S, z, layer, q, k, v, log_g, interpret)
        y, Sw, zw = _step_math(
            S[layer, :w], z[layer, :w], q, k, v, log_g, _dot(jnp.float32)
        )
        return y, S.at[layer, :w].set(Sw), z.at[layer, :w].set(zw)


# ── a sequence, a chunk at a time ────────────────────────────────────────


def _chunk_math(S, z, q, k, v, cum, dot):
    """One chunk of every head against the carried state. ``S`` [G, d_v, F
    d_k], ``z`` [G, F, d_k], ``q`` [C, G, R, d_k], ``k`` [C, G, d_k], ``v``
    [C, G, d_v], ``cum`` [C, G] the log-decays summed from the chunk's
    first position to each. Returns (``y`` [C, G, R, d_v], ``S``, ``z``)."""
    C, G, R, dk = q.shape
    F = rows(dk)
    t = jnp.arange(C)
    gap = cum.T[:, :, None] - cum.T[:, None, :]  # [G, t, j]
    decay = jnp.where(t[:, None] >= t[None, :], jnp.exp(jnp.minimum(gap, 0.0)), 0.0)
    qg, kg, vg = (x.swapaxes(0, 1) for x in (q, k, v))  # heads first
    qr = qg.transpose(0, 2, 1, 3).reshape(G, R * C, dk)
    score = jax.vmap(lambda a, b: dot(a, b, _NT))(qr, kg).reshape(G, R, C, C)
    a = score * score * decay[:, None]
    num = jax.vmap(lambda a, b: dot(a, b, _NN))(a.reshape(G, R * C, C), vg)
    den = a.sum(-1).reshape(G, R * C)
    # what came before the chunk, through the state
    pq = phi(qr).reshape(G, R * C, F * dk)
    ahead = jnp.tile(jnp.exp(cum.T), (1, R))  # [G, R C]
    num = num + ahead[..., None] * jax.vmap(lambda a, b: dot(a, b, _NT))(pq, S)
    den = den + ahead * jnp.einsum("gtf,gf->gt", pq, z.reshape(G, F * dk))
    y = (num / (den[..., None] + EPS)).reshape(G, R, C, -1).transpose(2, 0, 1, 3)
    # the chunk into the state
    total = cum[-1]  # [G]
    left = jnp.exp(total[None] - cum).T  # [G, C]
    pk = phi(kg).reshape(G, C, F * dk)
    S = jnp.exp(total)[:, None, None] * S + jax.vmap(
        lambda a, b: dot(a.T, b, _NN)
    )(vg * left[..., None], pk)
    z = jnp.exp(total)[:, None, None] * z + jnp.einsum(
        "gc,gcf->gf", left, pk
    ).reshape(G, F, dk)
    return y, S, z


def _chunk_kernel(meta_ref, q_ref, k_ref, v_ref, cum_ref, s_ref, z_ref, y_ref,
                  so_ref, zo_ref, pq_ref, pk_ref, sm_ref, *, R, mm_dtype):
    dot = _dot(mm_dtype)
    c = pl.program_id(1)
    C, dk = k_ref.shape
    dv = v_ref.shape[1]
    F = rows(dk)
    fresh = meta_ref[2] == 0

    @pl.when(c == 0)
    def _():
        so_ref[...] = jnp.where(fresh, 0.0, s_ref[...])
        zo_ref[...] = jnp.where(fresh, 0.0, z_ref[...])

    live = c * C < meta_ref[1]

    @pl.when(live)
    def _():
        k, v = k_ref[...], v_ref[...]
        cum_j = jnp.broadcast_to(cum_ref[0], (C, C))  # a row's entry j
        cum_t = cum_j.T
        t = lax.broadcasted_iota(jnp.int32, (C, C), 0)
        j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
        decay = jnp.where(t >= j, jnp.exp(jnp.minimum(cum_t - cum_j, 0.0)), 0.0)
        total = cum_ref[0][:, C - 1 :]  # [1, 1]
        ahead = jnp.exp(cum_t[:, :1])  # [C, 1]
        left = jnp.exp(total - cum_t[:, :1])
        through = jnp.exp(total)
        # the state as the chunk finds it, in the products' type, once
        sm_ref[...] = so_ref[0, 0, 0].astype(sm_ref.dtype)
        fed_z = []  # what the chunk adds to each row of z
        for d in range(F):
            kd = _row(k, d)
            pk_ref[:, d * dk : (d + 1) * dk] = kd.astype(pk_ref.dtype)
            fed_z.append(jnp.sum(kd * left, axis=0, keepdims=True))
        for r in range(R):
            q = q_ref[:, r * dk : (r + 1) * dk]
            score = dot(q, k, _NT)
            a = score * score * decay
            zden = jnp.zeros((C, dk), jnp.float32)
            for d in range(F):
                qd = _row(q, d)
                zden = zden + qd * zo_ref[0, 0, 0, d : d + 1, :]
                pq_ref[:, d * dk : (d + 1) * dk] = qd.astype(pq_ref.dtype)
            num = dot(a, v) + ahead * dot(pq_ref[...], sm_ref[...], _NT)
            den = (
                jnp.sum(a, axis=1, keepdims=True)
                + ahead * jnp.sum(zden, axis=1, keepdims=True)
            )
            y_ref[:, r * dv : (r + 1) * dv] = num / (den + EPS)
        # the chunk into the state, a band of lanes at a time
        fed = (v * left).T  # [d_v, C]
        for lo in range(0, F * dk, 5 * dk):
            at = slice(lo, min(lo + 5 * dk, F * dk))
            so_ref[0, 0, 0, :, at] = (
                through * so_ref[0, 0, 0, :, at] + dot(fed, pk_ref[:, at])
            )
        for d in range(F):
            zo_ref[0, 0, 0, d : d + 1, :] = (
                through * zo_ref[0, 0, 0, d : d + 1, :] + fed_z[d]
            )

    @pl.when(jnp.logical_not(live))
    def _():
        # all padding: nothing moves the state; the rows must still be
        # written (what lies in the buffer may not be a number)
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


def _chunk_call(S, z, layer, slot, q, k, v, cum, length, start, mm_dtype,
                interpret):
    """``retention_chunk`` over one slot of layer ``layer`` of the cache's
    state, in place. ``q`` [P, G R d_k], ``k`` [P, G d_k], ``v`` [P, G
    d_v], ``cum`` [G, 1, P], ``P`` a multiple of ``CHUNK``."""
    P, G = k.shape[0], cum.shape[0]
    dv, F, dk = S.shape[3], z.shape[3], z.shape[4]
    R = q.shape[1] // (G * dk)
    C = CHUNK
    meta = jnp.stack([slot, length, start]).astype(jnp.int32)
    seq = lambda width: pl.BlockSpec(  # noqa: E731
        (C, width), lambda g, c, meta: (c, g)
    )
    # a head's state: the same block for every chunk, resident in VMEM
    here = lambda g, c, meta: (layer, meta[0], g, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_chunk_kernel, R=R, mm_dtype=mm_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, P // C),
            in_specs=[
                seq(R * dk), seq(dk), seq(dv),
                pl.BlockSpec((1, 1, C), lambda g, c, meta: (g, 0, c)),
                pl.BlockSpec((1, 1, 1, dv, F * dk), here),
                pl.BlockSpec((1, 1, 1, F, dk), here),
            ],
            out_specs=[
                seq(R * dv),
                pl.BlockSpec((1, 1, 1, dv, F * dk), here),
                pl.BlockSpec((1, 1, 1, F, dk), here),
            ],
            scratch_shapes=[
                pltpu.VMEM((C, F * dk), mm_dtype),
                pltpu.VMEM((C, F * dk), mm_dtype),
                pltpu.VMEM((dv, F * dk), mm_dtype),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((P, G * R * dv), jnp.float32),
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
        ],
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
        name="retention_chunk",
    )(meta, q, k, v, cum, S, z)


def chunked(
    S: jax.Array,
    z: jax.Array,
    layer: int,
    slot: jax.Array,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    log_g: jax.Array,
    length: jax.Array,
    start: jax.Array,
    scale: float,
    mm_dtype=jnp.float32,
    kernel: bool | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One sequence through slot ``slot`` of layer ``layer`` of the cache's
    state: from a zero state where ``start`` is 0, from the state the slot
    holds (a prompt's earlier piece) otherwise. ``q`` [P, G, R, d_k], ``k``
    [P, G, d_k], ``v`` [P, G, d_v], ``log_g`` [P, G] float32; positions at
    and past ``length`` are padding and arrive with ``k = 0`` and ``log_g =
    0``. Returns (``y`` [P, G, R, d_v], ``S``, ``z``) with that slot's
    state as of position ``length - 1``; every other slot and layer is as
    it was."""
    P, G, R, dk = q.shape
    dv = v.shape[-1]
    C = CHUNK
    pad = -P % C
    n = (P + pad) // C
    q, k = _scaled(q, k, scale)
    grow = lambda x: jnp.pad(  # noqa: E731
        x.astype(jnp.float32), ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    )
    q, k, v, log_g = grow(q), grow(k), grow(v), grow(log_g)
    with jax.named_scope("retention.chunk"):
        # float32 sums of the log-decays, within a chunk
        cum = jnp.cumsum(log_g.reshape(n, C, G), axis=1)
        if eligible(dk, dv) if kernel is None else kernel:
            y, S, z = _chunk_call(
                S, z, layer, slot, q.reshape(P + pad, -1),
                k.reshape(P + pad, -1), v.reshape(P + pad, -1),
                cum.reshape(n * C, G).T[:, None], length, start, mm_dtype,
                interpret,
            )
            return y.reshape(P + pad, G, R, dv)[:P], S, z
        dot = _dot(mm_dtype)
        fresh = start == 0
        at = (layer, slot, 0, 0, 0)
        S0 = lax.dynamic_slice(S, at, (1, 1) + S.shape[2:])[0, 0]
        z0 = lax.dynamic_slice(z, at, (1, 1) + z.shape[2:])[0, 0]

        def one(carry, xs):
            y, Sc, zc = _chunk_math(*carry, *xs, dot)
            return (Sc, zc), y

        chunks = lambda x: x.reshape(n, C, *x.shape[1:])  # noqa: E731
        (Sn, zn), y = lax.scan(
            one,
            (jnp.where(fresh, 0.0, S0), jnp.where(fresh, 0.0, z0)),
            (chunks(q), chunks(k), chunks(v), cum),
        )
        return (
            y.reshape(P + pad, G, R, dv)[:P],
            lax.dynamic_update_slice(S, Sn[None, None], at),
            lax.dynamic_update_slice(z, zn[None, None], at),
        )
