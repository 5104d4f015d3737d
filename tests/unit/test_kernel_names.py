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
        params, pool, pool, arg((16,), jnp.int32),
        arg((16, MAX_PAGES), jnp.int32), arg((w,), jnp.int32),
        arg((w,), jnp.float32), arg((w, 2), jnp.uint32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == cfg.n_layers
    assert "paged_decode_attention" in text
    one_layer, tables = "768,64,16,128]", f"{w * MAX_PAGES},64,16,128]"
    assert not re.search(rf"= \w+\[({re.escape(one_layer)}|{re.escape(tables)})", text)
    assert not re.search(r"= \w+\[24,768,64,16,128\][^ ]* copy\(", text)
