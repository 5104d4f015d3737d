"""pygrid_tpu — a TPU-native privacy-preserving ML grid framework.

A from-scratch rebuild of the capabilities of OpenMined PyGrid (reference:
/root/reference) plus the PySyft-0.2.9 execution surface it consumes, designed
TPU-first: Plans are traced/exported XLA programs, simulated FL clients and
SMPC parties are vmapped batches of HBM-resident state on a `jax.sharding.Mesh`,
and FedAvg aggregation is a `psum` over ICI instead of a Python reduce loop.

Top-level layout (see SURVEY.md for the reference layer map this covers):

- ``serde``      wire serialization (msgpack-based, typed registry)
- ``plans``      Plan/State/PlaceHolder — traced, exported, portable programs
- ``runtime``    virtual party runtime (object store, pointers, message router)
- ``smpc``       fixed-precision ring-2^64 additive secret sharing, Beaver matmul,
                 the Pallas uint64 ring-matmul kernel
- ``parallel``   mesh construction, FedAvg collectives, shard_map helpers,
                 the Pallas flash-attention kernel
- ``models``     model families (MLP, CNN, transformer) + KV-cache decode
- ``serving``    continuous-batching generation engine (paged KV, fused decode)
- ``storage``    sqlite-backed Warehouse + object persistence
- ``federated``  model-centric FL coordination (cycles, controllers, managers)
- ``node``       the Node app (aiohttp HTTP + WS server)
- ``network``    the Network app (grid directory, routing, monitoring)
- ``client``     client SDK (model-centric / data-centric / FL worker clients)
- ``users``      RBAC (users, roles, groups, JWT auth)
"""

__version__ = "0.1.0"

from pygrid_tpu.utils import codes, exceptions  # noqa: F401
