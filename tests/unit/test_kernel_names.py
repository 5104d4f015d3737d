"""The flash kernels lower for the chip at the fed cells' real shapes, under
names a trace reduction can find after a refactor.

Compile-only, for a TPU v5e that is described and not attached
(on-chip-measurement §2.3): nothing runs, so this says nothing of results
or times. The topology is described inside a module-scoped fixture, never
at import, and every such test lives in this one file: one xdist worker
gets the file and loads the TPU's library alone.
"""

from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp

from pygrid_tpu.parallel.pallas_attention import flash_attention

#: [B, L, H, D] of one client's attention in the benchmark's two training
#: cells (Cerebras-GPT-111M: 12 heads of 64; perfbench/traffic/fed-*.json)
CELL_SHAPES = {
    "fed-silo-docs": (2, 2048, 12, 64),
    "fed-device-short": (4, 128, 12, 64),
}


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as err:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {err}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _loss(q, k, v):
    out = flash_attention(q, k, v, causal=True)
    return jnp.sum(out.astype(jnp.float32))


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_flash_kernels_lower_for_the_v5e_under_their_names(
    cell, one_chip, no_compile_cache
):
    arg = jax.ShapeDtypeStruct(
        CELL_SHAPES[cell], jnp.bfloat16, sharding=one_chip
    )
    forward = jax.jit(_loss).lower(arg, arg, arg).compile().as_text()
    assert "tpu_custom_call" in forward
    assert "flash_fwd" in forward
    assert "flash_bwd" not in forward
    both = (
        jax.jit(jax.grad(_loss, argnums=(0, 1, 2)))
        .lower(arg, arg, arg).compile().as_text()
    )
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert name in both, name


def test_ring_matmul_lowers_for_the_v5e_under_its_name(
    one_chip, no_compile_cache
):
    from pygrid_tpu.smpc.pallas_kernels import pallas_ring_matmul
    from pygrid_tpu.smpc.ring import Ring64

    word = jax.ShapeDtypeStruct((256, 256), jnp.uint32, sharding=one_chip)
    text = (
        jax.jit(lambda a, b: pallas_ring_matmul(a, b))
        .lower(Ring64(word, word), Ring64(word, word)).compile().as_text()
    )
    assert "tpu_custom_call" in text and "ring_matmul" in text


#: the serving cells' pool and tables (Cerebras-GPT-1.3B served in bf16:
#: 24 layers, 768 blocks of 64 tokens, 16 heads of 128, 32 pages a slot)
POOL = (24, 768, 64, 16, 128)
MAX_PAGES = 32


@pytest.mark.parametrize("q_dtype", ["float32", "bfloat16"])
def test_paged_decode_attention_lowers_for_the_v5e_under_its_name(
    q_dtype, one_chip, no_compile_cache
):
    from pygrid_tpu.serving.paged_attention import paged_decode_attention

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg(POOL, jnp.bfloat16)
    text = (
        jax.jit(lambda *a: paged_decode_attention(*a))
        .lower(
            arg((16, 16, 128), q_dtype), pool, pool, arg((), jnp.int32),
            arg((16, MAX_PAGES), jnp.int32), arg((16,), jnp.int32),
        ).compile().as_text()
    )
    assert "tpu_custom_call" in text and "paged_decode_attention" in text


def test_decode_step_at_the_cells_width_holds_nothing_pool_sized(
    one_chip, no_compile_cache, monkeypatch
):
    """The width-16 step program, traced as it is on a TPU: one kernel a
    layer, and no value of one layer's pool (the gather's operand), of
    all the tables' pages (the gather's result) or a copy of the pool."""
    import re

    from pygrid_tpu.models import transformer as T
    from pygrid_tpu.serving.programs import ProgramSet

    # what ``paged_attention.eligible`` and the step's ``interpret`` read
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = T.TransformerConfig(
        vocab=50257, d_model=2048, n_heads=16, n_layers=24, d_ff=8192,
        max_len=2048,
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    shapes = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))
    params = [arg(p.shape, jnp.bfloat16) for p in shapes]
    pool, w = arg(POOL, jnp.bfloat16), 16
    step = ProgramSet(cfg).paged_decode(w)
    while not hasattr(step, "lower"):  # the profiler's wrapper
        step = step.__wrapped__
    text = step.lower(
        params, pool, pool, arg((16,), jnp.int32), arg((16,), jnp.int32),
        arg((16, MAX_PAGES), jnp.int32),
        arg((w,), jnp.float32), arg((w, 2), jnp.uint32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == cfg.n_layers
    assert "paged_decode_attention" in text
    one_layer, tables = "768,64,16,128]", f"{w * MAX_PAGES},64,16,128]"
    assert not re.search(rf"= \w+\[({re.escape(one_layer)}|{re.escape(tables)})", text)
    assert not re.search(r"= \w+\[24,768,64,16,128\][^ ]* copy\(", text)


def test_grouped_expert_ffn_lowers_for_the_v5e_under_its_name(
    one_chip, no_compile_cache
):
    """The served expert layer at the block-diffusion cell's widths (64
    rows x 4 positions, 8 of 128 experts of 2048 x 768, bfloat16): one
    kernel, one expert's three matrices in VMEM at a time, under the name
    the roofline reader sums (``perfbench/metrics/expert_ffn_roofline_pct.
    blockdiff.py``)."""
    from pygrid_tpu.models import moe

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = (
        jax.jit(lambda *a: moe.routed_experts(*a, 8, kernel=True))
        .lower(
            arg((256, 2048), jnp.float32), arg((2048, 128)),
            arg((128, 2048, 768)), arg((128, 2048, 768)), arg((128, 768, 2048)),
        ).compile()
    )
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "grouped_expert_ffn" in text
    # 256 x 8 assignments in tiles of 32 rows, and a tile more an expert:
    # the rows the kernel writes, whatever the routing
    assert f"f32[{(256 * 8 // 32 + 128) * 32},2048]" in text
    # nothing of the size of a layer's experts is copied or converted
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 1024 * 1024


def test_block_step_gathers_its_pages_from_the_pool_where_it_lies(
    one_chip, no_compile_cache, monkeypatch
):
    """The block-diffusion family's width-64 step at the cell's K/V
    shapes (4 K/V heads of 128, 1,024 blocks of 64; two layers and few
    experts, to keep the compile short): each layer's two gathers read
    the pool itself. Indexing the layer out first (``pool[layer][table]``)
    made XLA copy that layer, 67 MB, before every gather (PERF.md §6, PR
    34); a reshape of the pool made it copy the whole pool."""
    import re

    from pygrid_tpu.models import sdar_moe
    from pygrid_tpu.serving.programs import ProgramSet

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = sdar_moe.SdarConfig(
        vocab=1024, d_model=512, n_heads=32, n_kv_heads=4, head_dim=128,
        n_layers=2, n_experts=8, top_k=2, d_expert=128, max_len=1024,
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda p: arg(p.shape, jnp.bfloat16),
        jax.eval_shape(lambda: sdar_moe.init(jax.random.PRNGKey(0), cfg)),
    )
    w, blocks, pages = 64, 1024, 16
    pool = arg((cfg.n_layers, blocks, 64, 4, 128), jnp.bfloat16)
    step = ProgramSet(cfg).paged_block_step(w)
    while not hasattr(step, "lower"):  # the profiler's wrapper
        step = step.__wrapped__
    text = step.lower(
        params, pool, pool, arg((w,), jnp.int32),
        (arg((w, 4), jnp.int32), arg((w, 4), jnp.bool_)),
        arg((w, pages), jnp.int32), arg((w, 4), jnp.int32),
        arg((w,), jnp.int32), arg((w,), jnp.bool_),
    ).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    gathers = re.findall(
        r"= bf16\[1024,64,4,128\]\S* fusion\((%[\w.-]+),[^\n]*attn\.block/gather", entry
    )
    assert len(gathers) == 2 * cfg.n_layers
    # every gather's operand is the pool as the scatter before it left it
    for operand in gathers:
        assert re.search(
            rf"{re.escape(operand)} = bf16\[{cfg.n_layers},1024,64,4,128\]", entry
        ), operand
    # and nothing else of a layer's size (the gathers' results are: 64
    # rows x 16 pages), or of the pool's, is made on the way
    assert len(re.findall(r"= bf16\[1024,64,4,128\]", entry)) == len(gathers)
    assert not re.search(r"= bf16\[\d+,64,4,128\]\S* copy\(", entry)
    # the row's block kept on the device (PR 39) is two [64, 4] arrays
    # written where they lie: both pools and both of them are aliased to
    # the program's results, none copied
    aliased = re.search(r"input_output_alias=\{([^\n]*)\}, entry", text).group(1)
    assert aliased.count("may-alias") + aliased.count("must-alias") == 5


def test_every_kernel_of_the_chunked_delta_rule_is_named_kda_chunk(
    one_chip, no_compile_cache
):
    """``kda.chunked`` alone at the cell's widths (64 heads of 128, a
    bucket of 2,048, bfloat16 products): whatever Pallas kernels it
    reaches, each carries the ``kda_chunk`` prefix that
    ``perfbench/metrics/kda_chunk_roofline_pct.longdoc.py`` sums by, and
    the log-decays' running sums are taken inside them (no ``cumsum`` or
    ``reduce-window`` of XLA's beside the kernels)."""
    import re

    from pygrid_tpu.models import kda

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    P, H, d = 2048, 64, 128
    text = jax.jit(
        lambda q, k, v, g, beta, length: kda.chunked(
            q, k, v, g, beta, length, mm_dtype=jnp.bfloat16, kernel=True
        )
    ).lower(
        arg((P, H, d)), arg((P, H, d)), arg((P, H, d)), arg((P, H, d)),
        arg((P, H)), arg((), jnp.int32),
    ).compile().as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    assert len(calls) == 2, len(calls)
    assert all("kda_chunk" in c for c in calls)
    assert sum("kda_chunk_solver" in c for c in calls) == 1
    assert sum("kda_chunk_state" in c for c in calls) == 1
    assert "reduce-window" not in text and "cumsum" not in text


def test_longdoc_prefill_holds_its_kernels_and_no_square_of_scores(
    one_chip, no_compile_cache, monkeypatch
):
    """The delta-rule family's prefill at the published widths (64 heads
    of 128 on 8 K/V heads, 40 of 320 experts of 4096 x 1280, bfloat16; a
    bucket of 2,048 and 512 blocks, to keep the compile short): three
    delta-rule layers of two kernels each (``kda_chunk_solver``,
    ``kda_chunk_state``: every Pallas kernel that ``kda.chunked`` reaches
    carries the ``kda_chunk`` prefix), one flash forward,
    ``grouped_expert_ffn`` in every layer, under the names the roofline
    readers sum
    (``perfbench/metrics/kda_chunk_roofline_pct.longdoc.py``,
    ``expert_ffn_roofline_pct.longdoc.py``); no ``[heads, P, P]`` float32
    scores, and the K/V pool is written in place, never copied whole."""
    import re

    from pygrid_tpu.models import solar_open2
    from pygrid_tpu.serving.programs import ProgramSet

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = solar_open2.SolarConfig(
        vocab=24576, d_model=4096, n_heads=64, n_kv_heads=8, head_dim=128,
        n_layers=4, n_experts=320, top_k=8, d_expert=1280, held_first=0,
        held_count=40, max_len=8448, kda_rank=128,
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda p: arg(p.shape, jnp.bfloat16),
        jax.eval_shape(lambda: solar_open2.init(jax.random.PRNGKey(0), cfg)),
    )
    P, slots, blocks, pages = 2048, 64, 512, 132
    pool = arg((1, blocks, 64, 8, 128), jnp.bfloat16)
    prefill = ProgramSet(cfg, cache_dtype=jnp.bfloat16).paged_prefill(P)
    while not hasattr(prefill, "lower"):  # the profiler's wrapper
        prefill = prefill.__wrapped__
    compiled = prefill.lower(
        params, pool, pool, arg((slots,), jnp.int32),
        arg((3, slots, 64, 128, 128), jnp.float32),
        arg((3, 3, slots, 3 * 8192), jnp.bfloat16), arg((slots,), jnp.int32),
        arg((slots, pages), jnp.int32), arg((), jnp.int32), arg((P,), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32), arg((), jnp.float32),
        arg((2,), jnp.uint32),
    ).compile()
    text = compiled.as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    named = lambda name: sum(f"{name}" in c for c in calls)  # noqa: E731
    assert len(calls) == 11, len(calls)
    assert named("kda_chunk_solver") == 3 and named("kda_chunk_state") == 3
    assert named("kda_chunk") == 6 and named("grouped_expert_ffn") == 4
    assert named("flash_fwd") == 1
    # the scores of 64 heads over the bucket, whole: 1 GB here, 17 GB at 8,192
    assert not re.search(rf"f32\[64,{P},{P}\]|f32\[8,8,{P},{P}\]", text)
    # the state is one float32 tensor a layer and slot, updated where it lies
    assert "f32[3,64,64,128,128]" in text
    assert not re.search(r"= bf16\[1,512,64,8,128\][^ ]* copy\(", text)
    # what a prefill of 2,048 keeps beside its arguments: under 2 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024**3


#: the power-retention cell's widths (Brumby-14B-Base: 40 query heads on 8
#: K/V heads of 128, 20 slots; perfbench/configs/brumby-14b-base.json)
RETENTION = dict(slots=20, heads=8, group=5, dk=128, dv=128)


@pytest.mark.parametrize("form", ["step", "chunk"])
def test_retention_kernels_lower_for_the_v5e_under_their_names(
    form, one_chip, no_compile_cache
):
    """Both forms at the cell's widths, on two layers of a 20-slot state,
    donated: one kernel each, under the names the roofline readers sum
    (``perfbench/metrics/retention_*_roofline_pct.longreason.py``), the
    state aliased (what the program holds beside its arguments is a
    fraction of one layer's 0.69 GB)."""
    from pygrid_tpu.models import retention

    slots, G, R, dk, dv = RETENTION.values()

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    S, z = (arg(s) for s in retention.state_shapes(2, slots, G, dk, dv))
    if form == "step":
        fn = lambda S, z, q, k, v, g: retention.step(  # noqa: E731
            S, z, 1, q, k, v, g, dk**-0.5, kernel=True
        )
        rest = (
            arg((slots, G, R, dk)), arg((slots, G, dk)), arg((slots, G, dv)),
            arg((slots, G)),
        )
    else:
        P = 1024
        fn = lambda S, z, slot, q, k, v, g, n: retention.chunked(  # noqa: E731
            S, z, 1, slot, q, k, v, g, n, jnp.int32(0), dk**-0.5,
            mm_dtype=jnp.bfloat16, kernel=True,
        )
        rest = (
            arg((), jnp.int32), arg((P, G, R, dk)), arg((P, G, dk)),
            arg((P, G, dv)), arg((P, G)), arg((), jnp.int32),
        )
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(S, z, *rest).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"retention_{form}" in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * slots * G * dv * 65 * dk * 4
    assert memory.temp_size_in_bytes < 200e6


def test_longreason_step_holds_eight_kernels_and_no_second_state(
    one_chip, no_compile_cache, monkeypatch
):
    """The power-retention family's decode step at the published widths,
    two layers of the cell's eight (to keep the compile short), width 20:
    a ``retention_step`` a layer, the 1.4 GB state a parameter that is
    aliased to its output and produced by nothing else, and no K/V array
    with an element in it."""
    import re

    from pygrid_tpu.models import brumby, retention
    from pygrid_tpu.serving.programs import ProgramSet

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers, slots = 2, 20
    cfg = brumby.BrumbyConfig(
        vocab=151936, d_model=5120, n_heads=40, n_kv_heads=8, head_dim=128,
        n_layers=layers, d_ff=17408, max_len=5120,
    )

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda p: arg(p.shape, jnp.bfloat16),
        jax.eval_shape(lambda: brumby.init(jax.random.PRNGKey(0), cfg)),
    )
    S, z = (arg(s) for s in retention.state_shapes(layers, slots, 8, 128, 128))
    empty = arg((0, 1, 64, 8, 128), jnp.bfloat16)
    step = ProgramSet(cfg, cache_dtype=jnp.bfloat16).paged_decode(slots)
    while not hasattr(step, "lower"):  # the profiler's wrapper
        step = step.__wrapped__
    compiled = step.lower(
        params, empty, empty, arg((slots,), jnp.int32), S, z,
        arg((slots,), jnp.int32), arg((slots, 1), jnp.int32),
        arg((slots,), jnp.float32), arg((slots, 2), jnp.uint32),
    ).compile()
    text = compiled.as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    assert len(calls) == layers and all("retention_step" in c for c in calls)
    made = re.findall(rf"= f32\[{layers},{slots},8,128,8320\][^ ]* (\w+)\(", text)
    assert set(made) <= {"parameter", "custom-call", "get-tuple-element"}, made
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= layers * slots * 8 * 129 * 8320 * 4
    assert memory.temp_size_in_bytes < 200e6


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_agent_programs_hold_their_kernels_and_no_copy_of_the_pool(
    program, one_chip, no_compile_cache, monkeypatch
):
    """The short-convolution family's programs at the published widths (32
    query heads on 8 K/V heads of 64, 64 experts of 2048 x 1536, a dense
    MLP of 11,776, bfloat16), three layers of the cell's nine (the dense
    conv layer, an attention layer, a conv layer; to keep the compile
    short): ``grouped_expert_ffn`` in the two expert layers and not in the
    dense one, one flash forward a prompt under the name it trains under,
    no ``[heads, P, P]`` scores, and the pool, whose heads of 64 lie side
    by side on 512 lanes, written and read where it lies (by the wide
    decode kernel in the step): with the heads on an axis of their own
    XLA copied all of it before every write and gather."""
    import re

    from pygrid_tpu.models import lfm2_moe
    from pygrid_tpu.serving.programs import ProgramSet

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = lfm2_moe.Lfm2Config(
        vocab=65536, d_model=2048, n_heads=32, n_kv_heads=8, n_layers=3,
        attn_layers=0b010, n_dense=1, d_ff=11776, n_experts=64, top_k=4,
        d_expert=1536, d_conv=3, max_len=4608,
    )
    assert lfm2_moe.flash_eligible(cfg)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda p: arg(p.shape, jnp.bfloat16),
        jax.eval_shape(lambda: lfm2_moe.init(jax.random.PRNGKey(0), cfg)),
    )
    P, slots, blocks, pages = 2048, 64, 513, 72
    pool = arg((1, blocks, 64, 512), jnp.bfloat16)
    cache = (
        pool, pool, arg((slots,), jnp.int32),
        arg((2, 2, slots, 2048), jnp.bfloat16),
    )
    programs = ProgramSet(cfg, cache_dtype=jnp.bfloat16)
    fn = programs.paged_decode(slots) if program == "step" else programs.paged_prefill(P)
    while not hasattr(fn, "lower"):  # the profiler's wrapper
        fn = fn.__wrapped__
    last, table = arg((slots,), jnp.int32), arg((slots, pages), jnp.int32)
    if program == "step":
        rest = (arg((slots,), jnp.float32), arg((slots, 2), jnp.uint32))
    else:
        rest = (
            arg((), jnp.int32), arg((P,), jnp.int32), arg((), jnp.int32),
            arg((), jnp.int32), arg((), jnp.float32), arg((2,), jnp.uint32),
        )
    compiled = fn.lower(params, *cache, last, table, *rest).compile()
    text = compiled.as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    named = lambda name: sum(name in c for c in calls)  # noqa: E731
    assert named("grouped_expert_ffn") == 2
    assert named("flash_fwd") == (program == "prefill")
    # the step's attention layer reads its rows' live pages in place:
    # one kernel, and no gathered table beside the pool
    assert named("paged_decode_attention_wide") == (program == "step")
    assert len(calls) == 3
    assert not re.search(rf"\[({slots * pages}|{slots},{pages}),64,512\]", text)
    assert not re.search(r"= bf16\[1,513,64,512\][^ ]* copy\(", text)
    assert not re.search(rf"f32\[32,{P},{P}\]|f32\[8,4,{P},{P}\]", text)
    # the taps are one tensor, updated where it lies
    assert "bf16[2,2,64,2048]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1024**3


def test_wide_decode_attention_at_the_agent_cells_shape_gathers_no_table(
    one_chip, no_compile_cache
):
    """A step's attention as ``lfm2_moe.paged_decode_step`` runs it in
    ``agent-saturate`` (64 slots, 32 query heads on 8 K/V heads of 64 laid
    wide on 512 lanes, tables of 72 pages of 64, the bfloat16 pool of 1 +
    64 x 72 blocks): one kernel under the name a trace reduction prints,
    and no ``[4608, 64, 512]`` of gathered pages anywhere."""
    import re

    from pygrid_tpu.models import lfm2_moe
    from pygrid_tpu.serving import paged_attention

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w, H, G, dh, block, pages = 64, 32, 8, 64, 64, 72
    pool = arg((2, 1 + w * pages, block, G * dh), jnp.bfloat16)
    assert paged_attention.wave_pages(block, G * dh, 2, pages) == 8

    def attend(q, k_pool, v_pool, layer, table, lengths):
        return lfm2_moe._narrow(
            paged_attention.paged_decode_attention_wide(
                lfm2_moe._widen(q, G), k_pool, v_pool, layer, table, lengths,
                scale=dh**-0.5,
            ),
            G,
        )

    compiled = jax.jit(attend).lower(
        arg((w, H, dh), jnp.float32), pool, pool, arg((), jnp.int32),
        arg((w, pages), jnp.int32), arg((w,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*", text)
    assert len(calls) == 1 and "paged_decode_attention_wide" in calls[0]
    assert not re.search(r"\[(4608|64,72),64,512\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 1024**2
