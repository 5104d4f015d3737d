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
