"""Mixture-of-experts FFN with expert parallelism.

The reference has no MoE (SURVEY.md §2.5 lists expert parallelism as
absent); this provides the TPU-native expert-parallel layer the framework
needs for sparse scaling. GShard-style top-1 routing with capacity:

- every shard routes its local tokens (gate softmax → argmax expert,
  position-in-expert via cumsum, tokens beyond capacity dropped);
- dispatch is two ``lax.all_to_all``s over the ``"expert"`` mesh axis:
  token buckets travel to the devices owning their expert, the expert FFN
  runs batched per device, results travel back and are combined with the
  gate weights. The all_to_alls ride ICI — no host gather ever sees the
  token stream.

With enough capacity (no drops) the expert-parallel output equals the
dense compute-every-expert reference bit-for-bit up to float
reassociation — that is what the tests pin.

The second half of the module is the SERVED expert layer
(:func:`routed_experts`): a top-k softmax router without drops or
capacity and a grouped gated-SiLU FFN that reads each touched expert's
weights once — what :mod:`pygrid_tpu.models.sdar_moe` runs in every
layer of every forward. The training-only top-1 layer above is untouched
by it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P


def init(
    key: jax.Array, d_model: int, d_ff: int, n_experts: int
) -> list[jax.Array]:
    """[gate Wg, expert W1, b1, W2, b2] with experts stacked on axis 0."""
    kg, k1, k2 = jax.random.split(key, 3)
    scale1 = 1.0 / math.sqrt(d_model)
    scale2 = 1.0 / math.sqrt(d_ff)
    return [
        jax.random.normal(kg, (d_model, n_experts)) * scale1,
        jax.random.normal(k1, (n_experts, d_model, d_ff)) * scale1,
        jnp.zeros((n_experts, d_ff)),
        jax.random.normal(k2, (n_experts, d_ff, d_model)) * scale2,
        jnp.zeros((n_experts, d_model)),
    ]


def _expert_ffn(w1, b1, w2, b2, h):
    return jax.nn.gelu(h @ w1 + b1) @ w2 + b2


def _route(x: jax.Array, wg: jax.Array, capacity: int):
    """Top-1 routing: dispatch one-hot [t, E, C] + combine weights."""
    n_experts = wg.shape[1]
    gates = jax.nn.softmax(x @ wg, axis=-1)  # [t, E]
    expert_idx = jnp.argmax(gates, axis=-1)  # [t]
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=x.dtype)  # [t, E]
    # arrival order within each expert's bucket
    pos = (
        jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0), expert_idx[:, None], axis=1
        )[:, 0]
        - 1
    ).astype(jnp.int32)
    keep = (pos < capacity).astype(x.dtype)
    dispatch = (
        onehot[:, :, None]
        * jax.nn.one_hot(pos, capacity, dtype=x.dtype)[:, None, :]
        * keep[:, None, None]
    )  # [t, E, C]
    gate_val = jnp.take_along_axis(gates, expert_idx[:, None], axis=1)[:, 0]
    combine = dispatch * gate_val[:, None, None]
    return dispatch, combine


def apply_dense(params: list, x: jax.Array) -> jax.Array:
    """Single-device reference: every expert computes every token, the
    top-1 gate selects (exact — no capacity drops)."""
    wg, w1, b1, w2, b2 = params
    gates = jax.nn.softmax(x @ wg, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)
    all_out = jax.vmap(
        lambda w1e, b1e, w2e, b2e: _expert_ffn(w1e, b1e, w2e, b2e, x)
    )(w1, b1, w2, b2)  # [E, t, d]
    sel = jnp.take_along_axis(
        all_out, expert_idx[None, :, None], axis=0
    )[0]  # [t, d]
    gate_val = jnp.take_along_axis(gates, expert_idx[:, None], axis=1)
    return sel * gate_val


def param_specs(n_leading: int = 5, axis: str = "expert"):
    """Shardings for ``init``'s param list: gate replicated, experts
    sharded on their stacking axis."""
    return [P()] + [P(axis), P(axis), P(axis), P(axis)][: n_leading - 1]


def apply_expert_parallel(
    params: list,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "expert",
    capacity_factor: float = 2.0,
) -> jax.Array:
    """Expert-parallel MoE: tokens sharded on [B] over ``axis``, experts
    sharded on their stacking axis; two all_to_alls move the buckets."""
    p_sz = mesh.shape[axis]
    wg = params[0]
    n_experts = wg.shape[1]
    if n_experts % p_sz:
        raise ValueError(
            f"experts ({n_experts}) must divide over mesh axis ({p_sz})"
        )
    if x.shape[0] % p_sz:
        raise ValueError(f"tokens ({x.shape[0]}) must shard over {p_sz}")
    t_local = x.shape[0] // p_sz
    capacity = max(1, int(math.ceil(t_local * capacity_factor / n_experts)))

    def inner(wg, w1, b1, w2, b2, x):
        dispatch, combine = _route(x, wg, capacity)  # [t, E, C]
        buckets = jnp.einsum("tec,td->ecd", dispatch, x)  # [E, C, d]
        # buckets for expert e hop to e's owner; capacity axis concatenates
        expert_in = lax.all_to_all(
            buckets, axis, split_axis=0, concat_axis=1, tiled=True
        )  # [E/P, P*C, d]
        expert_out = jax.vmap(_expert_ffn)(w1, b1, w2, b2, expert_in)
        back = lax.all_to_all(
            expert_out, axis, split_axis=1, concat_axis=0, tiled=True
        )  # [E, C, d]
        return jnp.einsum("tec,ecd->td", combine, back)

    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
    )(*params, x)


# ── the served layer: top-k routing, no drops, grouped experts ───────────
#
# ``T`` tokens each choose ``k`` of ``E`` experts: ``N = T·k`` assignments.
# They are sorted by expert into a row layout in which every expert's
# rows start on a tile boundary (``ROW_TILE`` rows), so one grid step of
# the kernel is one tile of one expert: ``N/ROW_TILE + E`` tiles at most,
# whatever the routing. Consecutive tiles of one expert find its weights
# already in VMEM, tiles past the last live one do nothing and move
# nothing, and an expert that received no row is never read: a forward's
# expert traffic is the touched experts' weights, once each.

#: rows of one grid step: two bfloat16 sublane tiles, so that the ~16
#: rows an expert receives at 64 slots x 4 positions x 8 of 128 fit one
#: step (a second step would push the expert's weights through the MXU
#: again)
ROW_TILE = 32

#: VMEM the grouped kernel asks for: one expert's three matrices, double
#: buffered (2 x 9.4 MB at 2048 x 768 in bfloat16), and the row tiles
VMEM_LIMIT = 48 * 1024 * 1024


def route_topk(x: jax.Array, w_router: jax.Array, k: int):
    """``softmax(x W_r)`` in float32 at full precision, the ``k`` largest
    and their probabilities renormalised to sum to one: (``idx`` [T, k]
    int32, ``p`` [T, k] float32). The product is 2048 x 128 a token:
    float32 costs nothing and keeps the choice the reference's but at
    true ties."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(
            x.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        probs, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        return idx.astype(jnp.int32), probs / probs.sum(-1, keepdims=True)


def grouped_layout(expert_ids: jax.Array, n_experts: int, tile: int):
    """Where each of ``N`` assignments goes in the tile-aligned layout.
    Returns ``dest`` [N] (its row), ``tile_expert`` [tiles] (the expert
    whose weights a tile multiplies; a dead tile repeats the last live
    one's, so it fetches nothing), ``n_live`` (tiles that hold rows) and
    ``sizes`` [E] (rows an expert received)."""
    n = expert_ids.shape[0]
    n_tiles = -(-n // tile) + n_experts
    sizes = jnp.zeros((n_experts,), jnp.int32).at[expert_ids].add(1)
    tiles = -(-sizes // tile)
    tile_end = jnp.cumsum(tiles)
    n_live = tile_end[-1]
    order = jnp.argsort(expert_ids, stable=True)
    sorted_ids = expert_ids[order]
    rank = jnp.arange(n, dtype=jnp.int32) - (jnp.cumsum(sizes) - sizes)[sorted_ids]
    dest_sorted = (tile_end - tiles)[sorted_ids] * tile + rank
    dest = jnp.zeros((n,), jnp.int32).at[order].set(dest_sorted)
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_expert = jnp.searchsorted(
        tile_end, jnp.minimum(t, n_live - 1), side="right"
    ).astype(jnp.int32)
    return dest, tile_expert, n_live.astype(jnp.int32), sizes


def _grouped_kernel(te_ref, live_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        prec = (
            lax.Precision.HIGHEST if wg_ref.dtype == jnp.float32 else None
        )
        x = x_ref[...]
        gate = jnp.dot(
            x, wg_ref[0], preferred_element_type=jnp.float32, precision=prec
        )
        up = jnp.dot(
            x, wu_ref[0], preferred_element_type=jnp.float32, precision=prec
        )
        hidden = (jax.nn.silu(gate) * up).astype(wd_ref.dtype)
        o_ref[...] = jnp.dot(
            hidden, wd_ref[0], preferred_element_type=jnp.float32,
            precision=prec,
        )


def grouped_expert_ffn(
    x_rows: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    tile_expert: jax.Array,
    n_live: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """``(silu(x W_gate,e) * x W_up,e) W_down,e`` for every row of the
    tile-aligned layout (:func:`grouped_layout`), float32 out. ``x_rows``
    [tiles·ROW_TILE, d] in the weights' dtype; the weights ``[E, d, f]``,
    ``[E, d, f]``, ``[E, f, d]`` stay in HBM and one expert's three
    matrices are in VMEM at a time. Rows of dead tiles are left
    unwritten."""
    n_rows, d = x_rows.shape
    _, _, f = w_gate.shape
    n_tiles = n_rows // ROW_TILE

    def rows(t, te, live):
        return (jnp.minimum(t, live[0] - 1), 0)

    def expert(t, te, live):
        return (te[t], 0, 0)

    return pl.pallas_call(
        _grouped_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((ROW_TILE, d), rows),
                pl.BlockSpec((1, d, f), expert),
                pl.BlockSpec((1, d, f), expert),
                pl.BlockSpec((1, f, d), expert),
            ],
            out_specs=pl.BlockSpec((ROW_TILE, d), rows),
        ),
        out_shape=jax.ShapeDtypeStruct((n_rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="grouped_expert_ffn",
    )(tile_expert, jnp.reshape(n_live, (1,)), x_rows, w_gate, w_up, w_down)


def grouped_eligible(w_gate: jax.Array) -> bool:
    """True where the expert FFN takes the kernel: on a TPU, with widths
    that fill whole 128-lane rows. Everywhere else (the CPU, tier-1's
    tiny widths) the same sorted rows go through ``lax.ragged_dot``."""
    _, d, f = w_gate.shape
    return jax.default_backend() == "tpu" and d % 128 == 0 and f % 128 == 0


def routed_experts(
    x: jax.Array,
    w_router: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    k: int,
    kernel: bool | None = None,
    interpret: bool = False,
):
    """The served expert layer over ``x`` [T, d] (float32, normed):
    ``sum_{e in top-k} p_e · W_down,e (silu(W_gate,e x) * W_up,e x)`` with
    ``p`` the router's softmax renormalised over the ``k`` chosen; no
    drops, no capacity, no shared expert. Returns (``y`` [T, d] float32,
    the number of experts that received a row, int32: what the forward
    had to read of this layer's experts)."""
    n_experts = w_gate.shape[0]
    idx, p = route_topk(x, w_router, k)
    with jax.named_scope("moe.experts"):
        ids = idx.reshape(-1)
        token = jnp.arange(ids.shape[0], dtype=jnp.int32) // k
        xw = x.astype(w_gate.dtype)
        if grouped_eligible(w_gate) if kernel is None else kernel:
            dest, tile_expert, n_live, sizes = grouped_layout(
                ids, n_experts, ROW_TILE
            )
            x_rows = jnp.zeros(
                (tile_expert.shape[0] * ROW_TILE, x.shape[1]), xw.dtype
            ).at[dest].set(xw[token])
            y = grouped_expert_ffn(
                x_rows, w_gate, w_up, w_down, tile_expert, n_live,
                interpret=interpret,
            )[dest]
        else:
            sizes = jnp.zeros((n_experts,), jnp.int32).at[ids].add(1)
            order = jnp.argsort(ids, stable=True)
            xs = xw[token[order]]
            dot = lambda a, w: lax.ragged_dot(  # noqa: E731
                a, w, sizes, preferred_element_type=jnp.float32,
                precision=(
                    lax.Precision.HIGHEST if w.dtype == jnp.float32 else None
                ),
            )
            hidden = jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up)
            ys = dot(hidden.astype(w_down.dtype), w_down)
            y = jnp.zeros_like(ys).at[order].set(ys)
        y = (y.reshape(-1, k, y.shape[-1]) * p[..., None]).sum(1)
        return y, jnp.sum(sizes > 0).astype(jnp.int32)
