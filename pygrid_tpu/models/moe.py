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
(:func:`routed_experts`): a top-k router without drops or capacity
(softmax over the experts, or a sigmoid an expert with a selection bias:
:func:`route_topk`) and a grouped gated-SiLU FFN that reads each touched
expert's weights once — what :mod:`pygrid_tpu.models.sdar_moe` and
:mod:`pygrid_tpu.models.solar_open2` run in every layer of every forward
and :mod:`pygrid_tpu.models.lfm2_moe` in every layer behind its dense
one.
A chip may HOLD a share of a layer's experts (``held``): the router still
scores all of them, the held ones compute their part of the result, and
what the absent ones would add is left out (no exchange, nothing standing
in for the other chips). A shared expert beside the routed ones is the
caller's dense FFN (:func:`shared_expert`). The training-only top-1 layer
above is untouched by it.
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

#: rows of one grid step where an expert receives a hundred rows and
#: more (a long prompt's prefill): a whole MXU pass of rows for each load
#: of the expert's weights
ROW_TILE_WIDE = 128

#: VMEM the grouped kernel asks for at the least: one expert's three
#: matrices, double buffered (2 x 9.4 MB at 2048 x 768 in bfloat16), and
#: the row tiles. A wider expert asks for what its own matrices need
#: (:func:`_vmem_limit`)
VMEM_LIMIT = 48 * 1024 * 1024


def _vmem_limit(w_gate: jax.Array, w_down: jax.Array, tile: int) -> int:
    """VMEM for one grid step: the expert's three matrices twice (the
    next expert's arrive while this one multiplies), the row tile in and
    out twice, and the tile's float32 hidden activations."""
    _, d, f = w_gate.shape
    expert = (2 * w_gate[0].size + w_down[0].size) * w_gate.dtype.itemsize
    rows = tile * d * (w_gate.dtype.itemsize + 4)
    need = 2 * expert + 2 * rows + 4 * tile * f * 4 + (4 << 20)
    return max(VMEM_LIMIT, need)


#: under the sum a sigmoid router's chosen scores are normalised by (the
#: LFM2-MoE family's published code)
SIGMOID_TOPK_EPS = 1e-6


def route_topk(
    x: jax.Array,
    w_router: jax.Array,
    k: int,
    sigmoid: bool = False,
    bias: jax.Array | None = None,
):
    """The router's scores over all its experts in float32 at full
    precision, the ``k`` chosen and their weights: (``idx`` [T, k] int32,
    ``p`` [T, k] float32). The product is 2048 x 128 a token: float32
    costs nothing and keeps the choice the reference's but at true ties.

    By default the scores are ``softmax(x W_r)``, the ``k`` largest are
    chosen and their probabilities renormalised to sum to one. ``sigmoid``
    scores each expert alone, ``sigmoid(x W_r)``; the chosen are then the
    ``k`` largest of ``score + bias`` (``bias`` [E]: a per-expert
    selection bias that balances the load and weighs nothing) and their
    weights the scores WITHOUT the bias over ``(their sum +
    SIGMOID_TOPK_EPS)``."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(
            x.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        if not sigmoid:
            probs, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
            return idx.astype(jnp.int32), probs / probs.sum(-1, keepdims=True)
        scores = jax.nn.sigmoid(logits)
        biased = scores if bias is None else scores + bias.astype(jnp.float32)
        _, idx = lax.top_k(biased, k)
        p = jnp.take_along_axis(scores, idx, axis=-1)
        return idx.astype(jnp.int32), p / (p.sum(-1, keepdims=True) + SIGMOID_TOPK_EPS)


def grouped_layout(expert_ids: jax.Array, n_experts: int, tile: int):
    """Where each of ``N`` assignments goes in the tile-aligned layout.
    Returns ``dest`` [N] (its row), ``tile_expert`` [tiles] (the expert
    whose weights a tile multiplies; a dead tile repeats the last live
    one's, so it fetches nothing), ``n_live`` (tiles that hold rows) and
    ``sizes`` [E] (rows an expert received). An assignment whose id is
    ``n_experts`` fell on an expert that is not held here: it takes no
    row (its ``dest`` is past the layout's end) and counts in no size.
    The layout has room for all ``N`` it is given landing on one expert
    or spread over all; tiles past ``n_live`` cost a grid step and move
    nothing."""
    n = expert_ids.shape[0]
    n_tiles = -(-n // tile) + n_experts
    sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[expert_ids].add(1)[
        :n_experts
    ]
    tiles = -(-sizes // tile)
    tile_end = jnp.cumsum(tiles)
    n_live = tile_end[-1]
    order = jnp.argsort(expert_ids, stable=True)
    sorted_ids = expert_ids[order]
    here = jnp.minimum(sorted_ids, n_experts - 1)
    rank = jnp.arange(n, dtype=jnp.int32) - (jnp.cumsum(sizes) - sizes)[here]
    dest_sorted = jnp.where(
        sorted_ids < n_experts,
        (tile_end - tiles)[here] * tile + rank,
        n_tiles * tile,
    )
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


def _row_block(t, n_live):
    """The block of rows grid step ``t`` works on: its own while it is
    live; a dead tile stays on the last live one (nothing is fetched or
    written back); and where no assignment fell on a held expert there is
    no live tile and every step stays on the first (an index of -1 is a
    DMA outside the array: the chip halts)."""
    return jnp.maximum(jnp.minimum(t, n_live - 1), 0)


def grouped_expert_ffn(
    x_rows: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    tile_expert: jax.Array,
    n_live: jax.Array,
    interpret: bool = False,
    tile: int = ROW_TILE,
) -> jax.Array:
    """``(silu(x W_gate,e) * x W_up,e) W_down,e`` for every row of the
    tile-aligned layout (:func:`grouped_layout`), float32 out. ``x_rows``
    [tiles·tile, d] in the weights' dtype; the weights ``[E, d, f]``,
    ``[E, d, f]``, ``[E, f, d]`` stay in HBM and one expert's three
    matrices are in VMEM at a time. Rows of dead tiles are left
    unwritten."""
    n_rows, d = x_rows.shape
    _, _, f = w_gate.shape
    n_tiles = n_rows // tile

    def rows(t, te, live):
        return (_row_block(t, live[0]), 0)

    def expert(t, te, live):
        return (te[t], 0, 0)

    return pl.pallas_call(
        _grouped_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((tile, d), rows),
                pl.BlockSpec((1, d, f), expert),
                pl.BlockSpec((1, d, f), expert),
                pl.BlockSpec((1, f, d), expert),
            ],
            out_specs=pl.BlockSpec((tile, d), rows),
        ),
        out_shape=jax.ShapeDtypeStruct((n_rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(w_gate, w_down, tile),
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


def shared_expert(
    x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array
) -> jax.Array:
    """The expert every token passes through, beside the routed ones: a
    dense gated SiLU FFN over ``x`` [T, d] in the weights' dtype,
    accumulated in float32."""
    with jax.named_scope("moe.shared"):
        mm = lambda a, w: jnp.dot(  # noqa: E731
            a.astype(w.dtype), w, preferred_element_type=jnp.float32,
            precision=(
                lax.Precision.HIGHEST if w.dtype == jnp.float32 else None
            ),
        )
        return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def _grouped_rows(xw, token, ids, w_gate, w_up, w_down, tile, interpret):
    """Each assignment's expert output ``[N, d]`` float32 through the tile
    layout and the kernel (an assignment to no held expert reads whatever
    the buffer held: the caller masks it)."""
    dest, tile_expert, n_live, _ = grouped_layout(ids, w_gate.shape[0], tile)
    x_rows = jnp.zeros(
        (tile_expert.shape[0] * tile, xw.shape[1]), xw.dtype
    ).at[dest].set(xw[token], mode="drop")
    return grouped_expert_ffn(
        x_rows, w_gate, w_up, w_down, tile_expert, n_live,
        interpret=interpret, tile=tile,
    )[dest]


def _weighed(rows, p):
    """A token's ``k`` assignments' rows ``[T·k, d]``, each times its
    probability, summed: ``[T, d]``."""
    return (rows.reshape(*p.shape, rows.shape[-1]) * p[..., None]).sum(1)


def _held_rounds(xw, ids, here, p, k, w_gate, w_up, w_down, share, tile, interpret):
    """``sum_j p_j expert_j(x)`` over the assignments that fell on a held
    expert, ``[T, d]`` float32, where the chip holds ``share`` of the
    router's experts. The tile layout is sized for TWICE the even share
    of the ``N`` assignments, not for all of them landing here (which
    the router may do, and a layout sized for it moves eight times the
    rows it has to): the held assignments are taken in rounds of that
    many, in their own order, until none is left. One round under even
    routing; as many as it takes under any other, so nothing is ever
    dropped."""
    N, T = ids.shape[0], xw.shape[0]
    cap = min(N, tile * -(-int(2 * N * share) // tile))
    rounds = -(-N // cap)
    order = jnp.argsort(~here, stable=True)  # the held ones first
    order = jnp.concatenate(
        [order, jnp.zeros((rounds * cap - N,), order.dtype)]
    ).astype(jnp.int32)
    n_held = jnp.sum(here).astype(jnp.int32)
    p = p.reshape(-1)

    def a_round(r, y):
        mine = lax.dynamic_slice(order, (r * cap,), (cap,))
        real = r * cap + jnp.arange(cap, dtype=jnp.int32) < n_held
        rows = _grouped_rows(
            xw, mine // k, jnp.where(real, ids[mine], w_gate.shape[0]),
            w_gate, w_up, w_down, tile, interpret,
        )
        rows = jnp.where(real[:, None], rows, 0.0) * p[mine][:, None]
        return y.at[mine // k].add(rows, indices_are_sorted=True)

    return lax.fori_loop(
        0, -(-n_held // cap), a_round, jnp.zeros((T, xw.shape[1]), jnp.float32)
    )


def routed_experts(
    x: jax.Array,
    w_router: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    k: int,
    kernel: bool | None = None,
    interpret: bool = False,
    held: tuple[int, int] | None = None,
    live: jax.Array | None = None,
    sigmoid: bool = False,
    bias: jax.Array | None = None,
):
    """The served expert layer over ``x`` [T, d] (float32, normed):
    ``sum_{e in top-k} p_e · W_down,e (silu(W_gate,e x) * W_up,e x)`` with
    ``p`` the router's weights of the ``k`` chosen (:func:`route_topk`:
    a softmax renormalised over them, or with ``sigmoid`` each expert's
    own score, chosen with ``bias`` and weighed without it); no drops, no
    capacity.

    ``held = (first, count)`` says which of the router's experts the
    weights given are: ``w_gate[i]`` is expert ``first + i``. The router
    scores all of its outputs and the ``k`` chosen are renormalised as
    ever; an assignment to an expert that is not held takes no row of
    the tile layout, reads no weight and adds nothing (what the chips
    that hold it would add is left out). ``None``: every expert is here.

    ``live`` [T] bool says which rows of ``x`` are somebody's (a block
    step carries positions that are nobody's beside the others): the
    assignments of a row that is not take no row of the layout either,
    read no weight, and its ``y`` is zero. ``None``: every row is.

    Returns (``y`` [T, d] float32; the number of held experts that
    received a row, int32: what the forward had to read of this layer's
    experts; the number of assignments that fell on a held expert,
    int32: all ``T·k`` of them where every expert is here)."""
    n_experts = w_gate.shape[0]
    idx, p = route_topk(x, w_router, k, sigmoid, bias)
    with jax.named_scope("moe.experts"):
        ids = idx.reshape(-1)
        if held is not None:
            local = ids - held[0]
            here = (local >= 0) & (local < n_experts)
            ids = jnp.where(here, local, n_experts)
            p = jnp.where(here.reshape(p.shape), p, 0.0)
        if live is not None:
            ids = jnp.where(jnp.repeat(live, k), ids, n_experts)
            p = jnp.where(live[:, None], p, 0.0)
        sizes = jnp.zeros((n_experts + 1,), jnp.int32).at[ids].add(1)[
            :n_experts
        ]
        token = jnp.arange(ids.shape[0], dtype=jnp.int32) // k
        xw = x.astype(w_gate.dtype)
        if grouped_eligible(w_gate) if kernel is None else kernel:
            # a tile of rows a step: wide where the router's even share
            # gives an expert a wide tile's worth (a long prompt), two
            # sublane tiles where it gives it a handful (a decode step)
            wide = ids.shape[0] // w_router.shape[1] >= ROW_TILE_WIDE
            tile = ROW_TILE_WIDE if wide else ROW_TILE
            if held is not None:
                y = _held_rounds(
                    xw, ids, here, p, k, w_gate, w_up, w_down,
                    n_experts / w_router.shape[1], tile, interpret,
                )
            else:
                rows = _grouped_rows(
                    xw, token, ids, w_gate, w_up, w_down, tile, interpret
                )
                if live is not None:
                    # past the layout's end: whatever the buffer held
                    rows = jnp.where((ids < n_experts)[:, None], rows, 0.0)
                y = _weighed(rows, p)
        else:
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
            if held is not None or live is not None:
                # a row past the experts' groups is nobody's
                y = jnp.where((ids < n_experts)[:, None], y, 0.0)
            y = _weighed(y, p)
        return (
            y, jnp.sum(sizes > 0).astype(jnp.int32),
            jnp.sum(sizes).astype(jnp.int32),
        )
