"""Hybrid-mesh construction + multi-host feed helpers (single-host CPU
stands in: the 8 virtual devices all report process_index 0, so host
splits are driven through the num_hosts override)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pygrid_tpu.parallel.distributed import (
    data_sharding,
    host_array,
    hybrid_mesh,
    local_batch_slice,
)


def test_single_host_mesh_shape():
    mesh = hybrid_mesh(ici_axes=("model",))
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] == 1 and mesh.shape["model"] == 8


def test_simulated_multihost_split():
    """4 "hosts" × 2 chips: the outer axis carries hosts, inner carries the
    per-host ICI group."""
    mesh = hybrid_mesh(
        ici_axes=("model",), ici_shape=(2,), num_hosts=4
    )
    assert mesh.devices.shape == (4, 2)
    # each inner row holds distinct devices, no duplicates overall
    ids = [d.id for d in mesh.devices.ravel()]
    assert sorted(ids) == sorted(range(8))


def test_mesh_rejects_bad_split():
    with pytest.raises(ValueError):
        hybrid_mesh(ici_axes=("model",), ici_shape=(3,), num_hosts=4)
    with pytest.raises(ValueError):
        hybrid_mesh(ici_axes=("model",), num_hosts=3)


def test_local_batch_slice():
    mesh = hybrid_mesh(ici_axes=("model",), ici_shape=(2,), num_hosts=4)
    sl = local_batch_slice(32, mesh)
    assert sl == slice(0, 8)  # single real process → host 0's rows
    with pytest.raises(ValueError):
        local_batch_slice(30, mesh)


def test_data_sharding_psum_over_dcn_axis():
    """A psum over the DCN axis aggregates host-sharded data — the FedAvg
    cross-host aggregation path."""
    mesh = hybrid_mesh(
        dcn_axis="hosts", ici_axes=("clients",), ici_shape=(2,), num_hosts=4
    )
    x = jnp.arange(8.0).reshape(4, 2)

    def agg(x):
        return jax.lax.psum(x, "hosts")

    out = jax.shard_map(
        agg, mesh=mesh, in_specs=P("hosts", "clients"),
        out_specs=P(None, "clients"),
    )(x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x.sum(axis=0))[None, :]
    )


def test_host_array_roundtrip():
    mesh = hybrid_mesh(ici_axes=("model",))
    local = np.arange(16.0).reshape(4, 4)
    arr = host_array(local, mesh, P("data"))
    np.testing.assert_allclose(np.asarray(arr), local)
    assert arr.sharding.is_equivalent_to(data_sharding(mesh), 2)
