"""Benchmark: FedAvg on TPU — kernel plane AND protocol plane.

Needs an accelerator: without one ``main()`` exits non-zero naming the
platform JAX found, and a section that raises ends the run with a
non-zero exit — nothing here is measured on, or falls back to, the host
CPU under a device metric's name. (The cell table and runner that
replace this script are ROADMAP S0.)

Two measurements against the reference's north-star workload
(SURVEY.md §3.3 steps 3-7; last driver captures ``BENCH_r04.json`` /
``BENCH_r05.json``):

1. **Kernel**: rounds/sec with 1024 simulated clients (MNIST MLP), the
   whole multi-round simulation fused on device via ``lax.scan``
   (`make_scanned_rounds`). Reported with MFU against the chip's bf16 peak.
2. **Protocol**: N real ``FLClient``s over WebSockets against a live node —
   authenticate → cycle-request → get-model → get-plan → report, with the
   node running real serde, sqlite state, CycleManager readiness logic and
   stacked-mean aggregation per cycle. Reports full-cycle completions/sec
   and diff-ingest throughput. (The reference's equivalent path is
   cycle_manager.py:151-323 driven by socket workers.)

Baseline proxy: the same per-client step on torch CPU eager (the reference's
execution plane is torch-CPU eager driven per-worker; conservative in our
disfavor — it ignores the reference's own serde/socket overhead).

Prints exactly ONE JSON line on stdout, stamped with the platform,
device kind and device count it ran on.
"""

from __future__ import annotations

import base64
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

K = 1024          # simulated clients per round (kernel plane)
BATCH = 64
SIZES = (784, 392, 10)
LR = 0.1
#: marginal-timing horizon: long enough that per-call dispatch/fetch
#: noise is orders below the chained device work being measured. A
#: 10-round spread once mis-ranked the two kernel paths outright (see
#: bench_tpu).
TIMED_ROUNDS = 190

def _env_num(name: str, default, cast):
    """Env knob with a defensive parse: a malformed value (``45s``,
    ``3.0`` for an int, a negative) must degrade to the default, not
    crash the bench before its one JSON line is printed."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        value = cast(raw)
    except ValueError:
        print(
            f"ignoring malformed {name}={raw!r}; using {default}",
            file=sys.stderr,
        )
        return default
    if value > 0:
        return value
    print(
        f"ignoring out-of-range {name}={raw!r}; using {default}",
        file=sys.stderr,
    )
    return default


PROTO_WORKERS = _env_num("PYGRID_BENCH_WORKERS", 64, int)
PROTO_CYCLES = _env_num("PYGRID_BENCH_CYCLES", 2, int)
PROTO_DEADLINE = _env_num("PYGRID_BENCH_DEADLINE", 240.0, float)
#: bf16 peak TFLOP/s of one chip, keyed by ``device_kind`` as JAX reports
#: it. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
PEAK_TFLOPS_BY_KIND = {"TPU v5 lite": 197.0}


def peak_tflops() -> float:
    """The bf16 peak of the device this process runs on. A device that
    is not in the table is an error, not a default: an MFU against the
    wrong peak is a wrong number under a right-looking name."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_TFLOPS_BY_KIND:
        raise RuntimeError(
            f"no peak FLOP/s recorded for device_kind {kind!r} — add it "
            "to bench.PEAK_TFLOPS_BY_KIND with its source"
        )
    return PEAK_TFLOPS_BY_KIND[kind]


def _flops_per_round() -> float:
    """Training FLOPs of one FedAvg round: fwd (2·B·Σ d_in·d_out) + bwd
    (≈2× fwd) per client, K clients."""
    dots = SIZES[0] * SIZES[1] + SIZES[1] * SIZES[2]
    return 6.0 * K * BATCH * dots


def bench_tpu() -> dict:
    """FedAvg kernel-plane numbers for the four round builders.

    All are the same algorithm (identities tested in
    ``test_fedavg_sim.py`` / ``test_fedavg_fused.py``):

    - *per-client (fused)*: the general per-client path rebuilt from the
      model's loss with the final-step aggregation reassociated —
      ``grad_q of the mean loss at p_k + q`` — so every layer's weight
      grad is ONE folded matmul (``fedavg_fused.make_fused_rounds``).
      Per-client semantics, folded-path MFU; the headline per-client
      number.
    - *per-client (opaque)*: vmapped opaque ``training_step`` — the path
      any black-box plan or stateful client optimizer rides; batched
      64-row weight-grad matmuls bound it to ~35% MFU.
    - *folded* (``fold_clients=True``): K·B samples fold into one batch
      before the first matmul.
    - *ls4*: the fused builder at ``local_steps=4`` with a bf16 delta
      carry — real multi-step FL, where the [K, |params|] per-client
      carry is algorithmically required and the round is bandwidth-bound
      (the [K, |params|] carry read+write per local step is its floor).
    """
    import jax
    import jax.numpy as jnp

    from pygrid_tpu.models import mlp
    from pygrid_tpu.parallel import make_fused_rounds, make_scanned_rounds

    print(f"device: {jax.devices()[0]}", file=sys.stderr)
    params = mlp.init(jax.random.PRNGKey(0), SIZES)
    client_X = jax.random.normal(jax.random.PRNGKey(1), (K, BATCH, SIZES[0]))
    labels = jax.random.randint(jax.random.PRNGKey(2), (K, BATCH), 0, SIZES[-1])
    client_y = jax.nn.one_hot(labels, SIZES[-1])
    lr = jnp.float32(LR)

    # single-pass bf16 MXU dots with f32 accumulation — measured ~5% over
    # the platform default at these sizes, accuracy-neutral for FedAvg
    def scanned(n: int, fold: bool):
        return make_scanned_rounds(
            mlp.training_step,
            n_rounds=n,
            local_steps=1,
            matmul_precision="BF16_BF16_F32",
            fold_clients=fold,
        )

    def fused(n: int, local_steps: int = 1, carry_dtype=None):
        return make_fused_rounds(
            mlp.loss_and_acc,
            n_rounds=n,
            local_steps=local_steps,
            matmul_precision="BF16_BF16_F32",
            carry_dtype=carry_dtype,
        )

    # Marginal timing: (chain(N+M) - chain(N)) / M cancels the per-call
    # dispatch + result-fetch overhead, whose variance buried ~1 ms/round
    # of signal when the spread was 10 rounds (round 3 mis-measured the
    # folded path that way). A ~190-round spread puts that noise orders
    # below the signal; min-over-trials kills the one-sided host-load
    # tail.
    small_n, large_n = 10, 10 + TIMED_ROUNDS

    def measure(builder) -> float:
        fns = {n: builder(n) for n in (small_n, large_n)}
        for n, fn in fns.items():  # compile both programs
            out = fn(params, client_X, client_y, lr)
            _ = float(out[1][-1])  # host fetch: waits for the chain

        def run(n: int) -> float:
            t0 = time.perf_counter()
            final, losses, accs = fns[n](params, client_X, client_y, lr)
            _ = float(losses[-1])  # single fetch forces the whole chain
            return time.perf_counter() - t0

        t_small = min(run(small_n) for _ in range(6))
        t_large = min(run(large_n) for _ in range(6))
        return (t_large - t_small) / TIMED_ROUNDS  # marginal timing

    dt_fused = measure(lambda n: fused(n))
    dt_opaque = measure(lambda n: scanned(n, fold=False))
    dt_folded = measure(lambda n: scanned(n, fold=True))
    dt_ls4 = measure(
        lambda n: fused(n, local_steps=4, carry_dtype=jnp.bfloat16)
    )
    peak = peak_tflops() * 1e12
    mfu_fused = _flops_per_round() / dt_fused / peak
    mfu_opaque = _flops_per_round() / dt_opaque / peak
    mfu_fold = _flops_per_round() / dt_folded / peak
    mfu_ls4 = 4 * _flops_per_round() / dt_ls4 / peak
    print(
        f"tpu: per-client[fused] {dt_fused*1e3:.2f} ms/round @ {K} clients "
        f"({K/dt_fused:,.0f} client-updates/sec, MFU {mfu_fused*100:.1f}%) | "
        f"opaque {dt_opaque*1e3:.2f} ms (MFU {mfu_opaque*100:.1f}%) | "
        f"folded {dt_folded*1e3:.2f} ms (MFU {mfu_fold*100:.1f}%) | "
        f"ls4[bf16 carry] {dt_ls4*1e3:.2f} ms (MFU {mfu_ls4*100:.1f}%) "
        f"of {peak_tflops():.0f} TF bf16",
        file=sys.stderr,
    )
    return {
        "per_client_rps": 1.0 / dt_fused,
        "per_client_mfu": mfu_fused,
        "opaque_rps": 1.0 / dt_opaque,
        "opaque_mfu": mfu_opaque,
        "folded_rps": 1.0 / dt_folded,
        "folded_mfu": mfu_fold,
        "ls4_rps": 1.0 / dt_ls4,
        "ls4_mfu": mfu_ls4,
    }


def bench_cpu_torch_baseline() -> float:
    """Per-client torch-CPU eager step (reference execution plane proxy).
    Returns equivalent rounds/sec for K clients done sequentially."""
    import torch

    torch.set_num_threads(1)  # the reference pins torch to 1 thread
    w1 = torch.randn(SIZES[0], SIZES[1]) * 0.05
    b1 = torch.zeros(SIZES[1])
    w2 = torch.randn(SIZES[1], SIZES[2]) * 0.05
    b2 = torch.zeros(SIZES[2])
    for p in (w1, b1, w2, b2):
        p.requires_grad_(True)
    X = torch.randn(BATCH, SIZES[0])
    y = torch.randint(0, SIZES[-1], (BATCH,))

    def client_step():
        h = torch.relu(X @ w1 + b1)
        logits = h @ w2 + b2
        loss = torch.nn.functional.cross_entropy(logits, y)
        grads = torch.autograd.grad(loss, (w1, b1, w2, b2))
        with torch.no_grad():
            for p, g in zip((w1, b1, w2, b2), grads):
                p -= LR * g

    client_step()  # warm
    n = 50
    t0 = time.perf_counter()
    for _ in range(n):
        client_step()
    per_client = (time.perf_counter() - t0) / n
    print(
        f"cpu baseline: {per_client*1e3:.3f} ms/client-step "
        f"→ {per_client*K:.2f} s/round @ {K} clients",
        file=sys.stderr,
    )
    return 1.0 / (per_client * K)


def bench_smpc() -> dict:
    """3-party fixed-prec Beaver matmul batches, two kernel tiers on the
    same chip: the vmapped batch path (`smpc.kernels.batched_beaver`) and
    the mesh-sharded party-axis path (`smpc.sharded`, 1-device mesh here;
    the party axis becomes cross-chip collectives on a slice). Chained
    launches + one final fetch (marginal timing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from pygrid_tpu.smpc import ring as R
    from pygrid_tpu.smpc.kernels import batched_beaver, share_kernel
    from pygrid_tpu.smpc.sharded import (
        deal_triples,
        make_sharded_beaver,
    )

    B, Pn, N = 512, 3, 64
    key = jax.random.PRNGKey(0)
    x = jax.random.bits(key, (B, N, N), dtype=jnp.uint32)
    x_r = R.Ring64(x, jnp.zeros_like(x))
    # vmap layout [B, P, N, N]
    vm_sh = jax.vmap(lambda v: share_kernel(key, v, Pn))(x_r)

    # chains ride lax.scan: compile cost stays flat in chain length, so
    # the spread can be wide enough (24 rounds) that per-call dispatch
    # noise is far below the signal
    def chain_vmap(n):
        @jax.jit
        def run(k, s):
            def body(carry, i):
                return batched_beaver(jax.random.fold_in(k, i), carry, carry), ()

            out, _ = jax.lax.scan(body, s, jnp.arange(n))
            return out
        return run

    def chain_sharded(n):
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("parties",))
        combine = make_sharded_beaver(mesh, op="matmul")

        @jax.jit
        def run(k, s):
            def body(carry, i):
                a_sh, b_sh, c_sh = deal_triples(
                    jax.random.fold_in(k, i), (N, N), (N, N), Pn,
                    op="matmul", batch=B,
                )
                return combine(carry, carry, a_sh, b_sh, c_sh), ()

            out, _ = jax.lax.scan(body, s, jnp.arange(n))
            return out
        return run

    # sharded layout [P, B, N, N]
    sh_sh = R.Ring64(
        jnp.moveaxis(vm_sh.lo, 1, 0), jnp.moveaxis(vm_sh.hi, 1, 0)
    )

    results = {}
    for name, make, arg in (
        ("vmap", chain_vmap, vm_sh),
        ("sharded", chain_sharded, sh_sh),
    ):
        small, large = 2, 26
        fns = {n: make(n) for n in (small, large)}

        def run_once(n):
            t0 = time.perf_counter()
            out = fns[n](key, arg)
            # slice on device, fetch ONE element — a full-array fetch
            # would copy ~25MB to the host inside the timed region
            _ = int(out.lo[0, 0, 0, 0])
            return time.perf_counter() - t0

        for n in fns:
            run_once(n)  # compile
        t_small = min(run_once(small) for _ in range(5))
        t_large = min(run_once(large) for _ in range(5))
        per = (t_large - t_small) / (large - small)
        results[name] = B / per
        print(
            f"smpc[{name}]: {per*1e3:.2f} ms per {B}-batch {Pn}-party "
            f"Beaver {N}x{N} matmul round ({B*Pn/per:,.0f} parties/sec)",
            file=sys.stderr,
        )

    # the kernel's design-point shape: 3-party Beaver at 512×512 (the
    # reference exercises Beaver matmul through 4-node grids at small
    # sizes — test_basic_syft_operations.py:455-491 — but an encrypted
    # model layer is this scale)
    B2, N2 = 8, 512
    x2 = jax.random.bits(jax.random.fold_in(key, 9), (B2, N2, N2), jnp.uint32)
    sh2 = jax.vmap(lambda v: share_kernel(key, R.Ring64(v, jnp.zeros_like(v)), Pn))(x2)

    fns2 = {n: chain_vmap(n) for n in (2, 26)}

    def run2(n):
        t0 = time.perf_counter()
        out = fns2[n](key, sh2)
        _ = int(out.lo[0, 0, 0, 0])
        return time.perf_counter() - t0

    for n in fns2:
        run2(n)
    per2 = (min(run2(26) for _ in range(5)) - min(run2(2) for _ in range(5))) / 24
    print(
        f"smpc[512x512]: {per2*1e3:.2f} ms per {B2}-batch {Pn}-party "
        f"Beaver {N2}x{N2} matmul round ({B2/per2:,.1f} matmuls/sec)",
        file=sys.stderr,
    )
    return {
        "smpc_beaver_matmuls_per_sec_vmap": round(results["vmap"], 0),
        "smpc_beaver_matmuls_per_sec_sharded": round(results["sharded"], 0),
        "smpc_beaver_512_matmuls_per_sec": round(B2 / per2, 1),
    }


def bench_attention() -> dict:
    """Causal attention L=4096 H=8 D=128 bf16: the Pallas flash kernel
    (`parallel.pallas_attention`) vs the XLA dense path
    (`parallel.ring_attention.attention`) — same computation, chained
    marginal timing."""
    import functools

    import jax
    import jax.numpy as jnp

    from pygrid_tpu.parallel.pallas_attention import flash_attention
    from pygrid_tpu.parallel.ring_attention import attention

    B, L, H, D = 1, 4096, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, L, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, L, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, L, H, D), jnp.bfloat16)

    def marginal(fn, lo=2, hi=42, trials=5):
        def chain(n):
            @jax.jit
            def f(x):
                for _ in range(n):
                    x = fn(x * (1.0 + 1e-6), k, v)
                return x
            return f

        fns = {n: chain(n) for n in (lo, hi)}
        for f in fns.values():
            out = f(q)
            _ = float(out.astype(jnp.float32).ravel()[0])

        def run(n):
            t0 = time.perf_counter()
            out = fns[n](q)
            _ = float(out.astype(jnp.float32).ravel()[0])
            return time.perf_counter() - t0

        t_lo = min(run(lo) for _ in range(trials))
        t_hi = min(run(hi) for _ in range(trials))
        return (t_hi - t_lo) / (hi - lo)

    # physicality floors: a marginal below FLOPs/peak means the
    # chip-state drift hit the two chain lengths differently (fast-state
    # hi chain vs slow-state lo chain under-measures the slope) —
    # re-measure rather than record an impossible >peak number. The
    # flash kernel prunes the causal upper triangle (2·B·H·L²·D); the
    # dense path executes the full masked L×L matmuls (4·B·H·L²·D).
    flash_floor_s = 2.0 * B * H * L * L * D / (peak_tflops() * 1e12)
    dense_floor_s = 2.0 * flash_floor_s

    def physical_marginal(fn, floor_s, attempts=3):
        ts = []
        for _ in range(attempts):
            t = marginal(fn)
            ts.append(t)
            if t >= floor_s:
                return t
        return max(ts)  # closest to physical of the failed attempts

    t_flash = physical_marginal(
        functools.partial(flash_attention, causal=True), flash_floor_s
    )
    t_xla = physical_marginal(
        functools.partial(attention, causal=True), dense_floor_s
    )
    print(
        f"attention[causal L={L} H={H} D={D} bf16]: "
        f"flash {t_flash*1e3:.3f} ms vs xla {t_xla*1e3:.3f} ms "
        f"({t_xla/t_flash:.2f}x)",
        file=sys.stderr,
    )
    return {
        "attention_flash_ms": round(t_flash * 1e3, 3),
        "attention_xla_ms": round(t_xla * 1e3, 3),
        "attention_flash_speedup": round(t_xla / t_flash, 2),
    }


def bench_attention_train() -> dict:
    """Causal attention TRAINING step (fwd + backward gradients) at
    L=4096 B=4 H=8 D=128 bf16: the Pallas flash VJP (two backward
    kernels, causal block pruning) vs differentiating the XLA dense
    path. Training is ~3× the forward FLOPs, so this — not the fwd-only
    line above — is the number long-context training rides on."""
    import jax
    import jax.numpy as jnp

    from pygrid_tpu.parallel.pallas_attention import flash_attention
    from pygrid_tpu.parallel.ring_attention import attention

    B, L, H, D = 4, 4096, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, L, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, L, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, L, H, D), jnp.bfloat16)

    def marginal(attn, lo=2, hi=10, trials=5):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v, causal=True).astype(jnp.float32))

        g = jax.grad(loss, argnums=(0, 1, 2))

        def chain(n):
            @jax.jit
            def f(q, k, v):
                def body(carry, _):
                    qq, kk, vv = carry
                    dq, dk, dv = g(qq, kk, vv)
                    return (
                        qq + dq * 1e-6, kk + dk * 1e-6, vv + dv * 1e-6
                    ), dq[0, 0, 0, 0]

                _, outs = jax.lax.scan(body, (q, k, v), None, length=n)
                return outs[-1]

            return f

        fns = {n: chain(n) for n in (lo, hi)}
        for f in fns.values():
            _ = float(f(q, k, v))

        def run(n):
            t0 = time.perf_counter()
            _ = float(fns[n](q, k, v))
            return time.perf_counter() - t0

        t_lo = min(run(lo) for _ in range(trials))
        t_hi = min(run(hi) for _ in range(trials))
        return (t_hi - t_lo) / (hi - lo)

    t_flash = marginal(flash_attention)
    t_xla = marginal(attention)
    print(
        f"attention-train[causal L={L} B={B} H={H} D={D} bf16]: "
        f"flash fwd+bwd {t_flash*1e3:.2f} ms vs xla VJP {t_xla*1e3:.2f} ms "
        f"({t_xla/t_flash:.2f}x)",
        file=sys.stderr,
    )
    return {
        "attention_flash_train_ms": round(t_flash * 1e3, 2),
        "attention_xla_train_ms": round(t_xla * 1e3, 2),
        "attention_flash_train_speedup": round(t_xla / t_flash, 2),
    }


# --- protocol plane ----------------------------------------------------------


class _NodeServer:
    """One in-process node app on its own event-loop thread (the bench twin
    of tests/integration/conftest.py's ServerThread)."""

    def __init__(self, database_url: str = ":memory:") -> None:
        import asyncio
        import socket

        from pygrid_tpu.node import create_app

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self.app = create_app("bench-node", database_url=database_url)
        self._loop = asyncio.new_event_loop()
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        import asyncio

        from aiohttp import web

        asyncio.set_event_loop(self._loop)

        async def _start():
            runner = web.AppRunner(self.app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", self.port)
            await site.start()
            self._runner = runner
            self._started.set()

        self._loop.run_until_complete(_start())
        self._loop.run_forever()

    def start(self) -> "_NodeServer":
        self._thread.start()
        if not self._started.wait(timeout=30):
            raise RuntimeError("bench node failed to start")
        return self

    def stop(self) -> None:
        import asyncio

        async def _cleanup():
            await self._runner.cleanup()

        fut = asyncio.run_coroutine_threadsafe(_cleanup(), self._loop)
        try:
            fut.result(timeout=10)
        finally:
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)


def bench_protocol(wire: str = "json", rounds: int = 2) -> dict:
    """Best of ``rounds`` runs of the full protocol workload — the first
    run pays import/allocator warmup that says nothing about the plane's
    capacity, and the capture host is shared (~2× swings from
    co-located load were observed)."""
    best: dict = {}
    key = "protocol_worker_updates_per_sec" + (
        "" if wire == "json" else f"_{wire}"
    )
    for _ in range(max(1, rounds)):
        result = _bench_protocol_once(wire)
        if not best or result[key] > best[key]:
            best = result
    return best


def _bench_protocol_once(wire: str) -> dict:
    """W concurrent FLClients through the full cycle protocol against a
    live node (SURVEY §3.3 steps 3-7: the path the reference serves with
    Flask/gevent + SQLAlchemy + torch serde).

    ``wire="json"`` is the reference-compatible base64-in-JSON contract;
    ``wire="binary"`` is this framework's msgpack frames with bf16
    payloads (the ``--wire bf16`` worker CLI path) — both modes hit the
    same node, same events, same aggregation."""
    import numpy as np

    import jax

    from pygrid_tpu.client import FLClient, ModelCentricFLClient
    from pygrid_tpu.models import mlp
    from pygrid_tpu.plans.plan import Plan
    from pygrid_tpu.plans.state import serialize_model_params

    W, R = PROTO_WORKERS, PROTO_CYCLES
    bf16 = wire == "binary"
    name, version = "bench-mnist", "1.0"
    server = _NodeServer().start()
    try:
        params = [
            np.asarray(p) for p in mlp.init(jax.random.PRNGKey(0), SIZES)
        ]
        plan = Plan(name="training_plan", fn=mlp.training_step)
        plan.build(
            np.zeros((BATCH, SIZES[0]), np.float32),
            np.zeros((BATCH, SIZES[-1]), np.float32),
            np.float32(LR),
            *params,
        )
        mc = ModelCentricFLClient(server.url)
        resp = mc.host_federated_training(
            model=params,
            client_plans={"training_plan": plan},
            client_config={
                "name": name, "version": version,
                "batch_size": BATCH, "lr": LR, "max_updates": 1,
            },
            server_config={
                "min_workers": W, "max_workers": W,
                "min_diffs": W, "max_diffs": W,
                "num_cycles": R,
                "do_not_reuse_workers_until_cycle": 0,
                "pool_selection": "random",
            },
        )
        assert resp.get("status") == "success", resp
        mc.close()

        deadline = time.perf_counter() + PROTO_DEADLINE
        bytes_reported = [0] * W
        cycles_done = [0] * W
        errors: list[str] = []

        def worker(idx: int) -> None:
            try:
                client = FLClient(server.url, timeout=PROTO_DEADLINE, wire=wire)
                auth = client.authenticate(name, version)
                wid = auth["worker_id"]
                while (
                    cycles_done[idx] < R and time.perf_counter() < deadline
                ):
                    cyc = client.cycle_request(
                        wid, name, version,
                        ping=1.0, download=1000.0, upload=1000.0,
                    )
                    if cyc.get("status") != "accepted":
                        time.sleep(0.05)  # cycle full/aggregating — retry
                        continue
                    model_params = client.get_model(
                        wid, cyc["request_key"], cyc["model_id"],
                        precision="bf16" if bf16 else None,
                    )
                    _plan = client.get_plan(
                        wid, cyc["request_key"],
                        cyc["plans"]["training_plan"],
                    )
                    # the diff is protocol-realistic in size/dtype; client
                    # compute stays off the clock so the number isolates
                    # the node-side protocol plane
                    diff = [
                        0.01 * np.asarray(p) for p in model_params
                    ]
                    blob = serialize_model_params(diff, bf16=bf16)
                    client.report(wid, cyc["request_key"], blob)
                    bytes_reported[idx] += (
                        len(blob) if bf16 else 4 * ((len(blob) + 2) // 3)
                    )
                    cycles_done[idx] += 1
                client.close()
            except Exception as err:  # noqa: BLE001 — surfaced below
                errors.append(f"worker {idx}: {err!r}")

        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(W)
        ]
        # the bench process is clients AND server on one host: CPython gc
        # walks megabytes of short-lived wire buffers per update and jax's
        # registered gc callback rides every collection — park both for
        # the timed window (bounded garbage: W×R reports)
        import gc

        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=PROTO_DEADLINE)
            wall = time.perf_counter() - t0
        finally:
            # an exception here (thread-start failure, Ctrl-C in join)
            # must not leave gc off for every later bench section
            gc.enable()
        completed = sum(1 for c in cycles_done if c >= R)
        total_updates = sum(cycles_done)
        if errors:
            print(f"protocol errors: {errors[:3]}", file=sys.stderr)
        print(
            f"protocol[{wire}]: {W} workers × {R} cycles in {wall:.2f}s — "
            f"{R/wall:.2f} full-cycles/sec, "
            f"{total_updates/wall:.1f} worker-updates/sec, "
            f"{sum(bytes_reported)/wall/1e6:.1f} MB/s diff ingest "
            f"({completed}/{W} workers completed)",
            file=sys.stderr,
        )
        suffix = "" if wire == "json" else f"_{wire}"
        return {
            f"protocol_full_cycles_per_sec{suffix}": round(R / wall, 3),
            f"protocol_worker_updates_per_sec{suffix}": round(
                total_updates / wall, 1
            ),
            f"protocol_diff_ingest_mb_per_sec{suffix}": round(
                sum(bytes_reported) / wall / 1e6, 1
            ),
            "protocol_workers": W,
        }
    finally:
        server.stop()


def _rss_kb() -> int | None:
    """Current VmRSS in kB (linux); None where /proc is absent."""
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except (OSError, ValueError, IndexError):
        return None
    return None


class _RSSPeak(threading.Thread):
    """Samples process RSS on a short cadence; ``stop()`` returns the
    peak seen — the node-memory-flatness evidence for the hierarchical
    ingest phases (CPython rarely returns freed pages, so per-phase
    DELTAS against the phase's starting RSS are what's comparable)."""

    def __init__(self, interval: float = 0.02) -> None:
        super().__init__(daemon=True)
        self.interval = interval
        self.base = _rss_kb() or 0
        self.peak = self.base
        self._stop_evt = threading.Event()

    def run(self) -> None:
        while not self._stop_evt.wait(self.interval):
            kb = _rss_kb()
            if kb and kb > self.peak:
                self.peak = kb

    def stop(self) -> tuple[float, float]:
        """(base_mb, peak_mb)."""
        self._stop_evt.set()
        self.join(timeout=2)
        kb = _rss_kb()
        if kb and kb > self.peak:
            self.peak = kb
        return self.base / 1024.0, self.peak / 1024.0


def _hier_host(server, name: str, n_workers: int):
    """Host one FL process sized for ``n_workers`` reports per cycle."""
    import numpy as np

    import jax

    from pygrid_tpu.client import ModelCentricFLClient
    from pygrid_tpu.models import mlp
    from pygrid_tpu.plans.plan import Plan

    params = [np.asarray(p) for p in mlp.init(jax.random.PRNGKey(0), SIZES)]
    plan = Plan(name="training_plan", fn=mlp.training_step)
    plan.build(
        np.zeros((BATCH, SIZES[0]), np.float32),
        np.zeros((BATCH, SIZES[-1]), np.float32),
        np.float32(LR),
        *params,
    )
    mc = ModelCentricFLClient(server.url)
    resp = mc.host_federated_training(
        model=params,
        client_plans={"training_plan": plan},
        client_config={
            "name": name, "version": "1.0",
            "batch_size": BATCH, "lr": LR, "max_updates": 1,
        },
        server_config={
            "min_workers": 1, "max_workers": n_workers,
            "min_diffs": n_workers, "max_diffs": n_workers,
            "num_cycles": 1,
            "do_not_reuse_workers_until_cycle": 0,
            "pool_selection": "random",
        },
    )
    assert resp.get("status") == "success", resp
    mc.close()
    return params


def _hier_assign(
    server, name: str, n_workers: int
) -> tuple[list[tuple[str, str]], int]:
    """Register + assign ``n_workers`` simulated workers IN-PROCESS (off
    the clock): the hierarchical mode measures the REPORT plane — at 10k
    workers the per-worker auth/cycle-request round trips would drown
    the number this bench exists to isolate."""
    ctx = server.app["node"]
    process = ctx.fl.process_manager.first(name=name, version="1.0")
    cycle = ctx.fl.cycle_manager.last(process.id)
    entries = []
    for i in range(n_workers):
        wid = f"{name}-w{i}"
        ctx.fl.worker_manager.create(wid)
        key = ctx.fl._generate_hash_key()
        ctx.fl.cycle_manager.assign(cycle, wid, key)
        entries.append((wid, key))
    return entries, cycle.id


def _hier_wait_cycle(server, cycle_id: int, deadline_s: float) -> bool:
    ctx = server.app["node"]
    deadline = time.perf_counter() + deadline_s
    while time.perf_counter() < deadline:
        cycle = ctx.fl.cycle_manager._cycles.first(id=cycle_id)
        if cycle is not None and cycle.is_completed:
            return True
        time.sleep(0.01)
    return False


def bench_protocol_hier(
    workers: tuple = None,
    fanouts: tuple = None,
    flat_workers: int | None = None,
    conns: int = 8,
    check_checkpoint: bool = True,
) -> dict:
    """Hierarchical report path: W simulated workers fold through
    sub-aggregator partials (fanout sweep) into one live node over real
    wire-v2 sockets, vs the flat binary leaf-report path — worker
    validation, zero-copy ingest, accumulator merge and cycle
    aggregation all on the clock; assignment in-process off the clock.
    Peak RSS is tracked per phase: the streaming partial path must hold
    node memory flat as W grows (one envelope per subtree, no
    per-worker tensors)."""
    import numpy as np

    from pygrid_tpu.client.base import GridWSClient
    from pygrid_tpu.federated.partials import PartialFold
    from pygrid_tpu.plans.state import (
        serialize_model_params,
        unserialize_model_params,
    )
    from pygrid_tpu.serde import tensor_copy_count
    from pygrid_tpu.utils.codes import CYCLE, MODEL_CENTRIC_FL_EVENTS, MSG_FIELD

    workers = workers or tuple(
        int(w)
        for w in os.environ.get(
            "PYGRID_BENCH_HIER_WORKERS", "64,1000,10000"
        ).split(",")
    )
    fanouts = fanouts or tuple(
        int(f)
        for f in os.environ.get(
            "PYGRID_BENCH_HIER_FANOUTS", "64,256"
        ).split(",")
    )
    flat_workers = flat_workers or _env_num(
        "PYGRID_BENCH_HIER_FLAT", 1000, int
    )
    # a FILE-backed warehouse, like a deployed node: report durability
    # (diff blobs / partial envelopes) lands on disk, so peak RSS
    # measures the STREAMING ingest residency — the flatness claim —
    # not the database growing inside the process
    db_dir = tempfile.mkdtemp(prefix="pygrid-bench-hier-")
    server = _NodeServer(
        database_url=os.path.join(db_dir, "node.db")
    ).start()
    out: dict = {"hier": {}, "flat_binary": {}}
    copies0 = tensor_copy_count()
    try:
        def _ingest(name, entries, cycle_id, fanout, send_partial,
                    n_conns=None):
            """The timed phase: fold+send over ``n_conns`` sockets, then
            wait for the cycle's aggregation. Returns (wall, rss)."""
            chunks = [
                entries[i : i + fanout]
                for i in range(0, len(entries), fanout)
            ]
            clients = [
                GridWSClient(server.url, offer_wire_v2=True)
                for _ in range(min(n_conns or conns, len(chunks)))
            ]
            errors: list[str] = []

            def sender(ci: int) -> None:
                try:
                    for chunk in chunks[ci :: len(clients)]:
                        send_partial(clients[ci], chunk, errors)
                except Exception as err:  # noqa: BLE001 — surfaced below
                    errors.append(repr(err))

            sampler = _RSSPeak()
            sampler.start()
            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=sender, args=(ci,), daemon=True)
                for ci in range(len(clients))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=PROTO_DEADLINE)
            done = _hier_wait_cycle(server, cycle_id, PROTO_DEADLINE)
            wall = time.perf_counter() - t0
            base_mb, peak_mb = sampler.stop()
            for c in clients:
                c.close()
            if errors:
                print(f"hier errors: {errors[:3]}", file=sys.stderr)
            return wall, base_mb, peak_mb, done, errors

        leaf_cache: dict[str, bytes] = {}

        def _leaf(params) -> bytes:
            key = "leaf"
            if key not in leaf_cache:
                leaf_cache[key] = serialize_model_params(
                    [0.01 * np.asarray(p) for p in params], bf16=True
                )
            return leaf_cache[key]

        # ── hierarchical phases ─────────────────────────────────────
        for W in workers:
            for fanout in fanouts:
                name = f"hier-{W}-{fanout}"
                params = _hier_host(server, name, W)
                entries, cycle_id = _hier_assign(server, name, W)
                leaf = _leaf(params)

                # edge folds run OFF the node's clock: in deployment the
                # W/fanout sub-aggregators fold in parallel on their own
                # hosts — the node-side number this bench isolates is
                # partial ingest → aggregation. Every leaf diff is the
                # same blob, so one fold per DISTINCT chunk size stands
                # in for all of them: its wall is the honest per-sub-
                # aggregator capacity, and staging reuses the folded
                # blob instead of parking W/fanout identical MB-scale
                # copies in the harness (which would drown the node-RSS
                # flatness signal this bench exists to show).
                fold_cache: dict[int, tuple[bytes, int, float]] = {}
                fold_wall = 0.0
                payloads = []
                for i in range(0, len(entries), fanout):
                    chunk = entries[i : i + fanout]
                    cached = fold_cache.get(len(chunk))
                    if cached is None:
                        fold_t0 = time.perf_counter()
                        fold = PartialFold()
                        for wid, key in chunk:
                            fold.add_report(wid, key, leaf)
                        blob, count, ws = fold.to_report()
                        dt_fold = time.perf_counter() - fold_t0
                        if len(chunk) == fanout:
                            fold_wall = dt_fold
                        cached = fold_cache[len(chunk)] = (blob, count, ws)
                    blob, count, ws = cached
                    payloads.append(
                        {
                            "workers": [[w, k] for w, k in chunk],
                            "count": count,
                            "weight_sum": ws,
                            CYCLE.DIFF: blob,
                        }
                    )
                if not fold_wall:  # W < fanout: only the short chunk
                    fold_wall = dt_fold
                payload_iter = iter(payloads)
                payload_lock = threading.Lock()

                def send_partial(client, _chunk, errors):
                    with payload_lock:
                        data_out = next(payload_iter, None)
                    if data_out is None:
                        return
                    resp = client.send_msg_binary(
                        MODEL_CENTRIC_FL_EVENTS.REPORT_PARTIAL,
                        data=data_out,
                    )
                    data = resp.get(MSG_FIELD.DATA, resp)
                    if data.get("error"):
                        errors.append(data["error"])

                wall, base_mb, peak_mb, done, errors = _ingest(
                    name, entries, cycle_id, fanout, send_partial
                )
                ckpt_ok = None
                if check_checkpoint and done and not errors:
                    from pygrid_tpu.client import ModelCentricFLClient

                    mc = ModelCentricFLClient(server.url)
                    got = mc.retrieve_model(name, "1.0")
                    mc.close()
                    diff = unserialize_model_params(leaf)
                    ckpt_ok = all(
                        np.allclose(
                            np.asarray(g), np.asarray(p) - np.asarray(d),
                            rtol=1e-5, atol=1e-6,
                        )
                        for g, p, d in zip(got, params, diff)
                    )
                entry = {
                    "workers": W,
                    "fanout": fanout,
                    "partials": -(-W // fanout),
                    "updates_per_sec": round(W / wall, 1),
                    "wall_s": round(wall, 3),
                    # ONE edge host folding its own subtree — in
                    # deployment the W/fanout sub-aggregators fold in
                    # parallel, so per-subtree fold latency adds once to
                    # the pipeline and node ingest above is the
                    # bottleneck stage
                    "subagg_fold_wall_s": round(fold_wall, 4),
                    "subagg_fold_updates_per_sec": round(
                        min(fanout, W) / fold_wall, 1
                    ),
                    "end_to_end_updates_per_sec": round(
                        W / (wall + fold_wall), 1
                    ),
                    "cycle_completed": done,
                    "checkpoint_ok": ckpt_ok,
                    "rss_base_mb": round(base_mb, 1),
                    "rss_peak_mb": round(peak_mb, 1),
                    "rss_delta_mb": round(peak_mb - base_mb, 1),
                }
                out["hier"][f"w{W}_f{fanout}"] = entry
                print(
                    f"hier[{W}w/{fanout}f]: {entry['updates_per_sec']} "
                    f"node-updates/sec ({entry['end_to_end_updates_per_sec']}"
                    f" e2e), {entry['partials']} partials, "
                    f"RSS +{entry['rss_delta_mb']}MB "
                    f"(ckpt_ok={ckpt_ok})",
                    file=sys.stderr,
                )

        # ── flat binary baseline (leaf frames, same harness) ────────
        Wf = flat_workers
        name = "hier-flatbase"
        params = _hier_host(server, name, Wf)
        entries, cycle_id = _hier_assign(server, name, Wf)
        leaf = _leaf(params)

        def send_leaf(client, chunk, errors):
            for wid, key in chunk:
                resp = client.send_msg_binary(
                    MODEL_CENTRIC_FL_EVENTS.REPORT,
                    data={
                        MSG_FIELD.WORKER_ID: wid,
                        CYCLE.KEY: key,
                        CYCLE.DIFF: leaf,
                    },
                )
                data = resp.get(MSG_FIELD.DATA, resp)
                if data.get("error"):
                    errors.append(data["error"])

        wall, base_mb, peak_mb, done, errors = _ingest(
            name, entries, cycle_id, 1, send_leaf
        )
        out["flat_binary"] = {
            "workers": Wf,
            "updates_per_sec": round(Wf / wall, 1),
            "wall_s": round(wall, 3),
            "cycle_completed": done,
            "rss_base_mb": round(base_mb, 1),
            "rss_peak_mb": round(peak_mb, 1),
            "rss_delta_mb": round(peak_mb - base_mb, 1),
        }
        print(
            f"flat-binary[{Wf}w]: {out['flat_binary']['updates_per_sec']} "
            f"updates/sec, RSS +{out['flat_binary']['rss_delta_mb']}MB",
            file=sys.stderr,
        )

        # ── node memory flatness (64 → 1k workers) ──────────────────
        # The sweep above maximizes throughput over `conns` sockets, so
        # its peak RSS tracks O(conns × partial_size) in-flight frames
        # (plus CPython arena ratcheting between phases) — not the
        # claim under test. Here: ONE connection, ONE partial in flight
        # at a time, tracemalloc watermark per phase. Each phase sends
        # the SAME number of same-sized partial frames (a partial blob
        # is model-sized whatever its count), so the transient frame
        # machinery is identical and the only variable is how many
        # workers stand behind each partial — the streaming ingest must
        # hold the same peak whether that is 64 or 1000.
        import gc
        import tracemalloc

        MEM_PARTIALS = 16
        mem: dict = {}
        for W in (64, min(1000, max(workers))):
            name = f"hier-mem-{W}"
            params = _hier_host(server, name, W)
            entries, cycle_id = _hier_assign(server, name, W)
            leaf = _leaf(params)
            fanout_mem = max(1, -(-W // MEM_PARTIALS))
            fold_cache2: dict[int, tuple[bytes, int, float]] = {}
            payloads = []
            for i in range(0, len(entries), fanout_mem):
                chunk = entries[i : i + fanout_mem]
                cached = fold_cache2.get(len(chunk))
                if cached is None:
                    fold = PartialFold()
                    for wid, key in chunk:
                        fold.add_report(wid, key, leaf)
                    cached = fold_cache2[len(chunk)] = fold.to_report()
                blob, count, ws = cached
                payloads.append(
                    {
                        "workers": [[w, k] for w, k in chunk],
                        "count": count,
                        "weight_sum": ws,
                        CYCLE.DIFF: blob,
                    }
                )
            payload_iter = iter(payloads)
            payload_lock = threading.Lock()

            def send_one(client, _chunk, errors):
                with payload_lock:
                    data_out = next(payload_iter, None)
                if data_out is None:
                    return
                resp = client.send_msg_binary(
                    MODEL_CENTRIC_FL_EVENTS.REPORT_PARTIAL, data=data_out
                )
                data = resp.get(MSG_FIELD.DATA, resp)
                if data.get("error"):
                    errors.append(data["error"])

            gc.collect()
            tracemalloc.start()
            wall, base_mb, peak_mb, done, errors = _ingest(
                name, entries, cycle_id, fanout_mem, send_one, n_conns=1
            )
            _, tm_peak = tracemalloc.get_traced_memory()
            tracemalloc.stop()
            mem[f"w{W}"] = {
                "workers": W,
                "alloc_peak_mb": round(tm_peak / 1e6, 1),
                "rss_delta_mb": round(peak_mb - base_mb, 1),
                "cycle_completed": done,
            }
            print(
                f"hier-mem[{W}w]: alloc peak "
                f"{mem[f'w{W}']['alloc_peak_mb']}MB, RSS "
                f"+{mem[f'w{W}']['rss_delta_mb']}MB",
                file=sys.stderr,
            )
        out["memory"] = mem
        peaks = [e["alloc_peak_mb"] for e in mem.values()]
        out["node_mem_peak_ratio_64_to_1k"] = (
            round(peaks[-1] / peaks[0], 2) if peaks[0] else None
        )

        flat_ups = out["flat_binary"]["updates_per_sec"]
        big = max(
            (e for e in out["hier"].values() if e["workers"] >= Wf),
            key=lambda e: e["updates_per_sec"],
            default=max(
                out["hier"].values(), key=lambda e: e["updates_per_sec"]
            ),
        )
        out["protocol_hier_updates_per_sec"] = big["updates_per_sec"]
        out["protocol_hier_speedup_vs_flat"] = (
            round(big["updates_per_sec"] / flat_ups, 1) if flat_ups else None
        )
        out["tensor_copies"] = tensor_copy_count() - copies0
        return out
    finally:
        server.stop()
        shutil.rmtree(db_dir, ignore_errors=True)


def _transformer_round_time(
    cfg, Kc: int, Bc: int, remat, small: int, large: int,
    trials: int = 5,
) -> tuple[float, float, int]:
    """(sec/round, FLOPs/round, tokens/round) for a FedAvg round over
    transformer clients with the Pallas flash kernels — the ONE
    FLOPs model and marginal-timing harness both transformer benches
    share (a correction here moves every fed_transformer_* metric
    together, keeping cross-round comparability).

    Round 5: rounds are built with the fused-aggregation builder
    (``make_fused_rounds`` — same FedAvg semantics, equivalence tested)
    and the CE head runs the bf16 backward (``ce_grad_dtype``) — the two
    changes that took the flagship from 47% to ~58% MFU; recorded in the
    emitted ``fed_transformer_path`` key so cross-round comparisons see
    the program change.

    FLOPs: 6ND for the matmul path (attn + mlp + tied output proj) plus
    the attention score/value quadratic term (~12·L·d per token PER
    LAYER, fwd+bwd, counted dense).

    NOTE: no global matmul_precision override here — a DotAlgorithmPreset
    context leaks into the Pallas kernel's own dots and Mosaic's lowering
    rejects it; the flash kernel manages its precision internally."""
    import functools

    import jax
    import jax.numpy as jnp

    from pygrid_tpu.models import transformer
    from pygrid_tpu.parallel import make_fused_rounds
    from pygrid_tpu.parallel.pallas_attention import flash_attention

    L = cfg.max_len
    tokens_per_round = Kc * Bc * L
    n_matmul = cfg.n_layers * (
        4 * cfg.d_model**2 + 2 * cfg.d_model * cfg.d_ff
    ) + cfg.vocab * cfg.d_model
    flops_round = (
        6.0 * n_matmul * tokens_per_round
        + 12.0 * cfg.n_layers * L * cfg.d_model * tokens_per_round
    )
    loss_fn = functools.partial(
        transformer.loss_and_acc, cfg=cfg, attn_fn=flash_attention,
        compute_dtype="bfloat16", remat=remat, ce_grad_dtype="bfloat16",
    )
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    X = jax.random.randint(jax.random.PRNGKey(1), (Kc, Bc, L), 0, cfg.vocab)
    y = jnp.roll(X, -1, axis=-1)
    lr = jnp.float32(0.1)
    fns = {
        n: make_fused_rounds(loss_fn, n_rounds=n) for n in (small, large)
    }
    for fn in fns.values():
        out = fn(params, X, y, lr)
        _ = float(out[1][-1])

    def run(n: int) -> float:
        t0 = time.perf_counter()
        out = fns[n](params, X, y, lr)
        _ = float(out[1][-1])
        return time.perf_counter() - t0

    t_small = min(run(small) for _ in range(trials))
    t_large = min(run(large) for _ in range(trials))
    per = (t_large - t_small) / (large - small)
    return per, flops_round, tokens_per_round


def _best_of(n: int, capture):
    """Min-keyed-on-time over ``n`` independent captures of
    ``capture() -> (per_round_s, ...)``: earlier captures drifted
    between a fast and a ~1.3x-slow state on a minutes timescale, a
    one-sided slowdown — the same rationale as the min-over-trials
    inside each capture. (Whether the drift survives on a directly
    attached chip: not measured.)"""
    return min((capture() for _ in range(n)), key=lambda t: t[0])


def bench_fed_transformer() -> dict:
    """Flagship composition bench: FedAvg over vmapped TRANSFORMER clients
    with the Pallas flash-attention kernel inside every client step —
    kernel plane, flash kernel and federated aggregation in one compiled
    program (the three existed separately through round 3; this measures
    them composed). Reports tokens/sec and MFU."""
    from pygrid_tpu.models import transformer

    # n_heads=4 → head_dim 128 = the MXU lane width: the TPU-native
    # head layout (dh=64 forces the kernel to pad every head to 128
    # lanes — measured 6 ms/round of pure padding waste at this scale).
    # Same d_model/layers/FLOPs; MFU is head-count independent.
    cfg = transformer.TransformerConfig(
        vocab=8192, d_model=512, n_heads=4, n_layers=4, d_ff=2048,
        max_len=512,
    )
    Kc, Bc = 8, 4
    per, flops_round, tokens = _best_of(
        2, lambda: _transformer_round_time(
            cfg, Kc, Bc, remat=False, small=2, large=10
        )
    )
    tok_s = tokens / per
    mfu = flops_round / per / (peak_tflops() * 1e12)
    print(
        f"fed-transformer[{cfg.n_layers}L d{cfg.d_model} L={cfg.max_len} "
        f"flash]: {per*1e3:.1f} ms/round, {tok_s:,.0f} tokens/sec, "
        f"MFU {mfu*100:.1f}% ({Kc} clients × {Bc}×{cfg.max_len} tokens)",
        file=sys.stderr,
    )
    return {
        "fed_transformer_tokens_per_sec": round(tok_s, 0),
        "fed_transformer_mfu_pct": round(mfu * 100, 1),
        "fed_transformer_ms_per_round": round(per * 1e3, 2),
        # recorded so cross-round comparisons never mistake a dtype or
        # layout change for an optimization
        "fed_transformer_compute_dtype": "bfloat16",
        "fed_transformer_head_dim": cfg.d_model // cfg.n_heads,
        "fed_transformer_path": "fused_rounds+bf16_ce_bwd",
    }


def bench_fed_transformer_long() -> dict:
    """Long-context federated-transformer TRAINING — the framework's
    stated differentiator (SURVEY §5.7) measured end-to-end instead of
    as kernel microbenchmarks: full training rounds at L=4096 and
    L=8192 with the Pallas flash kernels in BOTH directions (the XLA
    dense path cannot even materialize the L=8192 scores).

    The headline ``fed_transformer_long_{4096,8192}_*`` keys run WITHOUT
    block remat: flash attention's O(L·block) footprint means those
    shapes fit HBM with activations stored — remat would re-pay ~⅓ of
    the forward FLOPs for memory that is not scarce. Their ``*_remat_*``
    twins keep the rematerialized path measured. The ``_32768_`` key IS
    a remat run (at that length remat is the deployment config — see the
    loop comment), so the three headline L values are not config-uniform
    by design."""
    from pygrid_tpu.models import transformer

    out: dict = {}
    # 32K runs remat-only: at that length remat IS the deployment config
    # (activation storage would crowd the HBM a real batch needs) and
    # the attention quadratic dominates FLOPs, so the recompute tax is
    # small — measured 57% MFU, the framework's 32K-training-on-one-chip
    # claim made end-to-end
    for L, Kc, variants in (
        (4096, 8, ((False, ""), (True, "_remat"))),
        (8192, 4, ((False, ""), (True, "_remat"))),
        (32768, 1, ((True, ""),)),
    ):
        cfg = transformer.TransformerConfig(
            vocab=8192, d_model=512, n_heads=4, n_layers=4, d_ff=2048,
            max_len=L,
        )
        for remat, tag in variants:
            # headline (untagged) configs get the best-of-2 capture;
            # the _remat twins keep one (bench-time budget)
            per, flops_round, tokens = _best_of(
                2 if tag == "" else 1,
                lambda: _transformer_round_time(
                    cfg, Kc, 1, remat=remat, small=1, large=4, trials=4
                ),
            )
            tok_s = tokens / per
            mfu = flops_round / per / (peak_tflops() * 1e12)
            print(
                f"fed-transformer-long[L={L} {Kc}×1 "
                f"{'remat ' if remat else ''}flash]: "
                f"{per*1e3:.1f} ms/round, {tok_s:,.0f} tokens/sec, "
                f"MFU {mfu*100:.1f}%",
                file=sys.stderr,
            )
            out[f"fed_transformer_long_{L}{tag}_tokens_per_sec"] = round(
                tok_s, 0
            )
            out[f"fed_transformer_long_{L}{tag}_mfu_pct"] = round(
                mfu * 100, 1
            )
    # the long benches ride the same round-5 program change as the
    # flagship (fused rounds + bf16 CE backward) — recorded so the
    # round-4 -> round-5 jump is attributable
    out["fed_transformer_long_path"] = "fused_rounds+bf16_ce_bwd"
    return out


def bench_data_centric() -> dict:
    """Data-centric plane measured (SURVEY §6 row 3) in a CPU-pinned
    SUBPROCESS. Two reasons: this process holds the chip, and a chip
    belongs to one process at a time; and the node-side pointer/plan/
    Beaver ops are tiny 64×64 host-scale ops — the metric is the
    protocol plane (the reference analog is torch-CPU ops behind
    Flask), not device dispatch. The child is pinned with the plain
    ``JAX_PLATFORMS`` variable, the same way the scale-out replicas
    are."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import json, bench;"
            "print(json.dumps(bench._bench_data_centric_impl()))",
        ],
        env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True,
        text=True,
        timeout=600,
    )
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise RuntimeError(
            f"data-centric bench subprocess exited {proc.returncode}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _bench_data_centric_impl() -> dict:
    """The measurement itself (run CPU-pinned; see bench_data_centric):
    pointer-op round-trips/sec and remote plan execs/sec against a live
    node over real WS frames (reference workload
    ``examples/data-centric/mnist/02-FL-mnist-train-model.ipynb`` cells
    20-22), plus one §3.5 encrypted-inference latency — share → network
    discover → cross-node Beaver rounds → reconstruct — over an
    in-process 4-node grid."""
    import numpy as np

    from pygrid_tpu.client import DataCentricFLClient
    from pygrid_tpu.plans.plan import Plan
    from pygrid_tpu.runtime import PointerTensor, messages as M

    out: dict = {}
    server = _NodeServer().start()
    try:
        client = DataCentricFLClient(server.url)
        x = np.random.RandomState(0).randn(64, 64).astype(np.float32)
        ptr = client.send(x)
        _ = (ptr + ptr).get()  # warm incl. the node-side add dispatch
        N = 40
        t0 = time.perf_counter()
        for _ in range(N):
            a = client.send(x)
            b = a + a
            _ = b.get()
        dt = time.perf_counter() - t0
        # send + remote add + get = 3 WS request/response round trips
        out["datacentric_pointer_roundtrips_per_sec"] = round(3 * N / dt, 1)

        plan = Plan(name="bench-affine", fn=lambda v: v * 2.0 + 1.0)
        plan.build(np.zeros((64, 64), np.float32))
        resp = client.recv_obj_msg(M.ObjectMessage(obj=plan, id=424242))
        plan_ptr = PointerTensor(client, resp.id_at_location)
        r = client.run_plan(plan_ptr, x)  # warm (compile server-side)
        np.testing.assert_allclose(r.get(), x * 2.0 + 1.0, rtol=1e-5)
        t0 = time.perf_counter()
        for _ in range(N):
            client.run_plan(plan_ptr, x)
        dt = time.perf_counter() - t0
        out["datacentric_plan_execs_per_sec"] = round(N / dt, 1)
        client.close()
        print(
            f"data-centric: {out['datacentric_pointer_roundtrips_per_sec']}"
            " pointer round-trips/sec, "
            f"{out['datacentric_plan_execs_per_sec']} remote plan execs/sec"
            f" (64x64 f32, live node)",
            file=sys.stderr,
        )
    finally:
        server.stop()

    # §3.5 encrypted inference over a 4-node grid (examples/_grid spawns
    # the same in-process topology the integration suite uses)
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "examples"))
    from _grid import spawn_grid

    from pygrid_tpu.smpc import EncryptedModel, publish_encrypted_model

    network_url, nodes = spawn_grid(4)
    rng = np.random.default_rng(0)
    weights = [
        rng.uniform(-0.5, 0.5, (4, 3)).astype(np.float32),
        rng.uniform(-0.2, 0.2, (3,)).astype(np.float32),
        rng.uniform(-0.5, 0.5, (3, 2)).astype(np.float32),
        rng.uniform(-0.2, 0.2, (2,)).astype(np.float32),
    ]

    def forward(x, w1, b1, w2, b2):
        # CryptoNets-style polynomial circuit (affine → square → affine):
        # data-dependent nonlinearities need comparison protocols the
        # ring doesn't give for free (examples/encrypted_inference.py)
        h = x @ w1 + b1
        h = h * h
        return h @ w2 + b2

    plan = Plan(name="encrypted_forward", fn=forward)
    plan.build(np.zeros((2, 4), np.float32), *weights)
    clients = {n: DataCentricFLClient(url) for n, url in nodes.items()}
    publish_encrypted_model(
        plan,
        "bench-encrypted-mlp",
        host_client=clients["alice"],
        holder_clients=[clients["alice"], clients["bob"], clients["charlie"]],
        provider_client=clients["dan"],
        weights=weights,
    )
    model = EncryptedModel.discover(network_url, "bench-encrypted-mlp")
    xq = rng.uniform(-1, 1, (2, 4)).astype(np.float32)
    _ = model.predict(xq)  # warm (crypto-store refill + compiles)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        _ = model.predict(xq)
        times.append(time.perf_counter() - t0)
    out["encrypted_inference_ms"] = round(min(times) * 1e3, 1)
    model.close()
    for c in clients.values():
        c.close()
    print(
        f"encrypted inference[4-node grid, 2-layer MLP]: "
        f"{out['encrypted_inference_ms']} ms per predict "
        "(share discovery + cross-node Beaver rounds + reconstruct)",
        file=sys.stderr,
    )
    return out


#: wire-bench shape sets — MNIST-MLP (the protocol bench's checkpoint) and
#: a transformer-family checkpoint (embedding + per-layer attn/mlp/ln)
_WIRE_MODELS = {
    "mlp": [(784, 392), (392,), (392, 10), (10,)],
    "transformer": (
        [(8192, 256), (256,)]
        + [
            s
            for _ in range(4)
            for s in (
                (256, 768), (768,), (256, 256), (256,),
                (256, 1024), (1024,), (1024, 256), (256,),
                (256,), (256,),
            )
        ]
        + [(256, 8192)]
    ),
}

#: tiny stand-ins for CI: same structure, ~1000× fewer elements, so the
#: smoke test exercises every encode path in milliseconds
_WIRE_MODELS_TINY = {
    "mlp": [(24, 12), (12,), (12, 4), (4,)],
    "transformer": [(64, 16), (16,), (16, 48), (48,), (16, 64), (64, 16)],
}


def bench_wire(tiny: bool = False) -> dict:
    """Wire-layer capture for the model/diff hot loop: bytes per
    model-download + diff-upload round trip and p50 encode/decode latency,
    legacy hex-in-JSON framing (the reference contract — fl_events.py
    hexlifies every payload) vs the negotiated binary v2 path, plus the
    composed bf16 and frame-codec variants. Pure serialization — no
    sockets — so the numbers isolate the wire encodings themselves; the
    protocol benches above carry the rest of the stack.

    Also asserts the structural wins: binary decode of the checkpoint
    must make ZERO tensor-buffer copies (the read-only-view contract),
    tracked via the serde copy-count hook."""
    import binascii

    import numpy as np

    from pygrid_tpu.plans.state import serialize_model_params
    from pygrid_tpu.serde import (
        available_codecs,
        decode_frame,
        deserialize,
        encode_frame,
        serialize,
        tensor_copy_count,
    )

    rng = np.random.default_rng(0)
    repeats = 5 if tiny else 15
    out: dict = {"wire_codecs_available": list(available_codecs())}

    def _p50_ms(fn) -> float:
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return round(sorted(times)[len(times) // 2] * 1e3, 3)

    models = _WIRE_MODELS_TINY if tiny else _WIRE_MODELS
    for name, shapes in models.items():
        params = [
            rng.standard_normal(s).astype(np.float32) for s in shapes
        ]
        diffs = [0.01 * p for p in params]
        model_blob = serialize_model_params(params)
        diff_blob = serialize_model_params(diffs)
        head = {"worker_id": "w" * 36, "request_key": "k" * 64}

        # ── legacy: hex payloads inside JSON text frames ────────────────
        def _legacy_frames() -> tuple[str, str]:
            down = json.dumps({
                "type": "model-centric/get-model",
                "data": {**head, "model": binascii.hexlify(model_blob).decode()},
            })
            up = json.dumps({
                "type": "model-centric/report",
                "data": {**head, "diff": binascii.hexlify(diff_blob).decode()},
            })
            return down, up

        down_legacy, up_legacy = _legacy_frames()
        bytes_legacy = len(down_legacy.encode()) + len(up_legacy.encode())

        # ── v2: raw msgpack binary frames (tag byte, no envelope) ───────
        def _v2_frames(mb: bytes, db: bytes, codec=None) -> tuple[bytes, bytes]:
            down = encode_frame(serialize({
                "type": "model-centric/get-model",
                "data": {**head, "model": mb},
            }), codec)
            up = encode_frame(serialize({
                "type": "model-centric/report",
                "data": {**head, "diff": db},
            }), codec)
            return down, up

        down_v2, up_v2 = _v2_frames(model_blob, diff_blob)
        bytes_v2 = len(down_v2) + len(up_v2)

        model_bf16 = serialize_model_params(params, bf16=True)
        diff_bf16 = serialize_model_params(diffs, bf16=True)
        d16, u16 = _v2_frames(model_bf16, diff_bf16)
        bytes_bf16 = len(d16) + len(u16)

        codec = available_codecs()[0]
        dz, uz = _v2_frames(model_bf16, diff_bf16, codec)
        bytes_bf16_z = len(dz) + len(uz)

        # ── latency: p50 encode / decode per framing ────────────────────
        enc_legacy = _p50_ms(_legacy_frames)
        enc_v2 = _p50_ms(lambda: _v2_frames(model_blob, diff_blob))

        def _decode_legacy() -> None:
            msg = json.loads(down_legacy)
            deserialize(binascii.unhexlify(msg["data"]["model"]))

        def _decode_v2() -> None:
            msg = deserialize(decode_frame(down_v2))
            deserialize(msg["data"]["model"])

        dec_legacy = _p50_ms(_decode_legacy)
        dec_v2 = _p50_ms(_decode_v2)

        # ── structural: checkpoint decode must be zero-copy ─────────────
        copies_before = tensor_copy_count()
        decoded = deserialize(model_blob)
        copies = tensor_copy_count() - copies_before
        assert np.array_equal(decoded.tensors()[0], params[0])
        # enforced at FULL checkpoint scale too, not only in the tiny CI
        # twin — a copy path that only alignment/size triggers must fail
        # the capture (the guarded section records it), not silently land
        # a nonzero count in the BENCH file
        assert copies == 0, f"{name}: {copies} tensor-buffer copies on decode"

        out.update({
            f"wire_{name}_param_bytes": sum(p.nbytes for p in params),
            f"wire_{name}_roundtrip_bytes_legacy_hex_json": bytes_legacy,
            f"wire_{name}_roundtrip_bytes_v2": bytes_v2,
            f"wire_{name}_roundtrip_bytes_v2_bf16": bytes_bf16,
            f"wire_{name}_roundtrip_bytes_v2_bf16_{codec}": bytes_bf16_z,
            f"wire_{name}_bytes_ratio": round(bytes_legacy / bytes_v2, 2),
            f"wire_{name}_bytes_ratio_bf16": round(
                bytes_legacy / bytes_bf16, 2
            ),
            f"wire_{name}_encode_ms_legacy": enc_legacy,
            f"wire_{name}_encode_ms_v2": enc_v2,
            f"wire_{name}_decode_ms_legacy": dec_legacy,
            f"wire_{name}_decode_ms_v2": dec_v2,
            f"wire_{name}_decode_tensor_copies": copies,
        })
        print(
            f"wire[{name}]: {bytes_legacy/1e6:.2f} MB/round hex-JSON → "
            f"{bytes_v2/1e6:.2f} MB v2 ({bytes_legacy/bytes_v2:.2f}x), "
            f"{bytes_bf16/1e6:.2f} MB bf16, "
            f"decode {dec_legacy:.2f} → {dec_v2:.2f} ms p50, "
            f"{copies} tensor copies",
            file=sys.stderr,
        )
    return out


def _restore_env(name: str, prev: str | None) -> None:
    import os

    if prev is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = prev


def bench_telemetry_overhead(tiny: bool = False) -> dict:
    """Cost of the always-on telemetry on the wire hot loop: one
    model-download + diff-upload round (the bench_wire framing) measured
    bare vs instrumented exactly the way the live path is — a client
    span per frame, the trace header on every wire-v2 frame, the frame
    decode timing, and the byte counters. The acceptance bar is ≤ 2% on
    both bytes and p50 latency at full checkpoint scale (PR-2 tentpole);
    the tiny CI twin reports the same numbers on toy shapes where the
    fixed per-call cost is proportionally larger."""
    import numpy as np

    from pygrid_tpu import telemetry
    from pygrid_tpu.plans.state import serialize_model_params
    from pygrid_tpu.serde import (
        decode_frame_traced,
        deserialize,
        encode_frame,
        serialize,
    )
    from pygrid_tpu.telemetry import trace

    rng = np.random.default_rng(0)
    repeats = 9 if tiny else 25
    shapes = (_WIRE_MODELS_TINY if tiny else _WIRE_MODELS)["transformer"]
    params = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    diffs = [0.01 * p for p in params]
    model_blob = serialize_model_params(params)
    diff_blob = serialize_model_params(diffs)
    head = {"worker_id": "w" * 36, "request_key": "k" * 64}

    def _frames(with_trace: bool) -> tuple[bytes, bytes]:
        # the live client carries the context twice: the envelope's
        # `trace` field (GridWSClient._request) AND the frame header —
        # the instrumented round must pay both or the certified byte
        # delta is not the live wire's
        tb = trace.to_bytes() if with_trace else None
        envelope_trace = (
            {"trace": trace.header()} if with_trace else {}
        )
        down = encode_frame(serialize({
            "type": "model-centric/get-model",
            **envelope_trace,
            "data": {**head, "model": model_blob},
        }), trace=tb)
        up = encode_frame(serialize({
            "type": "model-centric/report",
            **envelope_trace,
            "data": {**head, "diff": diff_blob},
        }), trace=tb)
        return down, up

    import os

    from pygrid_tpu.telemetry import profiler, recorder

    # the profiler+recorder layer as the live path pays it: every frame
    # makes one profiler-wrapped call (timing + jit-cache check + bus
    # histogram) and one flight-recorder ring append. Two wrapped
    # probes: one built with the layer ON, one with PYGRID_PROFILER=off
    # (wrap() is then the identity — the disabled cost under test).
    def _probe(frame):
        return frame

    # the one hook wrap() needs from a jitted function — its trace-cache
    # size — so the wrapper's whole per-call cost is what gets measured
    _probe._cache_size = lambda: 1
    flight_on = profiler.wrap(_probe, kind="bench", bucket=0)
    prev_prof = os.environ.get("PYGRID_PROFILER")
    os.environ["PYGRID_PROFILER"] = "off"
    try:
        flight_off = profiler.wrap(_probe, kind="bench", bucket=1)
    finally:
        _restore_env("PYGRID_PROFILER", prev_prof)

    def _round(instrumented: bool, flight_fn=None) -> None:
        if instrumented:
            with trace.span("client.request", event_type="bench"):
                down, up = _frames(True)
            for frame in (down, up):
                telemetry.incr(
                    "wire_bytes_total", len(frame), direction="in",
                    codec="bench",
                )
                t0 = time.perf_counter()
                payload, tb = decode_frame_traced(frame)
                telemetry.observe(
                    "ws_frame_decode_seconds", time.perf_counter() - t0
                )
                if flight_fn is not None:
                    flight_fn(frame)
                    recorder.note("bench.frame", n_bytes=len(frame))
                with trace.serve(trace.from_bytes(tb)):
                    deserialize(payload)
        else:
            down, up = _frames(False)
            for frame in (down, up):
                deserialize(decode_frame_traced(frame)[0])

    # genuinely interleaved A/B/C/D (plain, traced, traced+flight,
    # traced+flight-disabled, repeat) so drift on a busy capture host
    # hits every variant the same way, with one untimed warmup pass
    # absorbing allocator/import one-offs. The D variant runs the SAME
    # layer call sites with both off-switches thrown — "≈0% when
    # disabled" measured, not vowed.
    _round(False)
    _round(True)
    _round(True, flight_on)
    plain_times: list[float] = []
    traced_times: list[float] = []
    flight_times: list[float] = []
    disabled_times: list[float] = []
    prev_flight = os.environ.get("PYGRID_FLIGHT")
    for _ in range(repeats):
        t0 = time.perf_counter()
        _round(False)
        plain_times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _round(True)
        traced_times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _round(True, flight_on)
        flight_times.append(time.perf_counter() - t0)
        os.environ["PYGRID_FLIGHT"] = "off"
        try:
            t0 = time.perf_counter()
            _round(True, flight_off)
            disabled_times.append(time.perf_counter() - t0)
        finally:
            _restore_env("PYGRID_FLIGHT", prev_flight)

    def _p50_ms(times: list[float]) -> float:
        return sorted(times)[len(times) // 2] * 1e3

    plain_ms = _p50_ms(plain_times)
    traced_ms = _p50_ms(traced_times)
    flight_ms = _p50_ms(flight_times)
    disabled_ms = _p50_ms(disabled_times)

    with trace.span("client.request", event_type="bench"):
        d_t, u_t = _frames(True)
    d_p, u_p = _frames(False)
    bytes_plain = len(d_p) + len(u_p)
    bytes_traced = len(d_t) + len(u_t)
    byte_pct = 100.0 * (bytes_traced - bytes_plain) / bytes_plain
    latency_pct = 100.0 * (traced_ms - plain_ms) / plain_ms
    flight_pct = 100.0 * (flight_ms - traced_ms) / traced_ms
    disabled_pct = 100.0 * (disabled_ms - traced_ms) / traced_ms
    out = {
        "telemetry_roundtrip_bytes_plain": bytes_plain,
        "telemetry_roundtrip_bytes_traced": bytes_traced,
        "telemetry_byte_overhead_pct": round(byte_pct, 4),
        "telemetry_roundtrip_ms_plain": round(plain_ms, 3),
        "telemetry_roundtrip_ms_traced": round(traced_ms, 3),
        "telemetry_latency_overhead_pct": round(latency_pct, 2),
        "telemetry_roundtrip_ms_flight": round(flight_ms, 3),
        "telemetry_flight_overhead_pct": round(flight_pct, 2),
        "telemetry_roundtrip_ms_flight_disabled": round(disabled_ms, 3),
        "telemetry_flight_disabled_overhead_pct": round(disabled_pct, 2),
        # on the tiny CI shapes the flight percentages are p50-minus-p50
        # noise over ~50µs rounds (the unit twin gates on ABSOLUTE
        # bounds for the same reason) — hold the layer to the absolute
        # budget there and to the ≤2% criterion at checkpoint scale
        "telemetry_within_2pct": bool(
            byte_pct <= 2.0
            and latency_pct <= 2.0
            and (
                (flight_ms - traced_ms < 0.5
                 and disabled_ms - traced_ms < 0.25)
                if tiny
                else (flight_pct <= 2.0 and disabled_pct <= 2.0)
            )
        ),
    }
    print(
        f"telemetry overhead: bytes +{byte_pct:.4f}%, "
        f"p50 {plain_ms:.3f} → {traced_ms:.3f} ms ({latency_pct:+.2f}%); "
        f"profiler+recorder {flight_ms:.3f} ms ({flight_pct:+.2f}%), "
        f"disabled {disabled_ms:.3f} ms ({disabled_pct:+.2f}%)",
        file=sys.stderr,
    )
    return out


def bench_report_handler() -> dict:
    """Isolated node-side report-handler latency (no sockets, no client
    threads): p50 ``route_requests`` time for a protocol-realistic report
    on each wire. Load-independent — the full-protocol numbers above share
    one host with their own bench clients, so this is the figure that
    tracks node-side progress even when the capture host is busy."""
    import numpy as np

    import jax

    from pygrid_tpu.federated import tasks
    from pygrid_tpu.models import mlp
    from pygrid_tpu.node import NodeContext
    from pygrid_tpu.node.events import Connection, route_requests
    from pygrid_tpu.plans.plan import Plan
    from pygrid_tpu.plans.state import serialize_model_params
    from pygrid_tpu.serde import deserialize, serialize, to_hex

    W = 32
    prev_sync = tasks._sync
    tasks.set_sync(True)  # completion runs inline → excluded via max_diffs
    try:
        ctx = NodeContext("handler-bench")
        params = [
            np.asarray(p) for p in mlp.init(jax.random.PRNGKey(0), SIZES)
        ]
        plan = Plan(name="training_plan", fn=mlp.training_step)
        plan.build(
            np.zeros((BATCH, SIZES[0]), np.float32),
            np.zeros((BATCH, SIZES[-1]), np.float32),
            np.float32(LR),
            *params,
        )
        out = {}
        for wire in ("json", "binary"):
            bf16 = wire == "binary"
            name = f"handler-{wire}"
            ctx.fl.create_process(
                model_blob=serialize_model_params(params),
                client_plans={"training_plan": bytes.fromhex(to_hex(plan))},
                name=name, version="1.0",
                client_config={"name": name, "version": "1.0"},
                server_config={
                    "min_workers": W, "max_workers": W,
                    # min above W: readiness never fires, so the timing is
                    # the per-report handler alone, not aggregation spikes
                    "min_diffs": W + 1, "max_diffs": W + 1, "num_cycles": 1,
                    "do_not_reuse_workers_until_cycle": 0,
                    "pool_selection": "random",
                },
                server_averaging_plan=None,
                client_protocols={},
            )
            blob = serialize_model_params(
                [0.01 * p for p in params], bf16=bf16
            )
            payload = blob if bf16 else base64.b64encode(blob).decode()
            encode = serialize if bf16 else json.dumps
            times = []
            for _ in range(W):
                conn = Connection(ctx, socket=object())
                auth = encode({
                    "type": "model-centric/authenticate",
                    "data": {"model_name": name, "model_version": "1.0"},
                })
                decode = deserialize if bf16 else json.loads
                wid = decode(route_requests(ctx, auth, conn))["data"]["worker_id"]
                cyc = decode(route_requests(ctx, encode({
                    "type": "model-centric/cycle-request",
                    "data": {"worker_id": wid, "model": name,
                             "version": "1.0", "ping": 1.0,
                             "download": 1000.0, "upload": 1000.0},
                }), conn))["data"]
                msg = encode({
                    "type": "model-centric/report",
                    "data": {"worker_id": wid,
                             "request_key": cyc["request_key"],
                             "diff": payload},
                })
                t0 = time.perf_counter()
                route_requests(ctx, msg, conn)
                times.append(time.perf_counter() - t0)
            p50 = float(sorted(times)[len(times) // 2]) * 1e3
            suffix = "" if wire == "json" else "_binary"
            out[f"protocol_report_handler_ms{suffix}"] = round(p50, 2)
            print(
                f"report handler[{wire}]: p50 {p50:.2f} ms "
                f"({len(times)} isolated reports)",
                file=sys.stderr,
            )
        return out
    finally:
        tasks.set_sync(prev_sync)


#: watchdog: a device call that never returns must not leave the driver
#: with nothing — emit an error line and exit non-zero. Generous default:
#: first TPU compiles are ~20-40s, the full bench minutes.
BENCH_TIMEOUT = _env_num("PYGRID_BENCH_TIMEOUT", 1500.0, float)


def _arm_watchdog() -> threading.Timer:
    def _fire() -> None:
        print(
            json.dumps(
                {
                    "metric": "fedavg_rounds_per_sec_1k_clients",
                    "value": None,
                    "unit": "rounds/sec (1024 simulated MNIST-MLP clients, batch 64)",
                    "error": f"bench exceeded {BENCH_TIMEOUT:.0f}s — "
                    "a device call hung",
                }
            ),
            flush=True,
        )
        os._exit(3)

    timer = threading.Timer(BENCH_TIMEOUT, _fire)
    timer.daemon = True
    timer.start()
    return timer


def main() -> None:
    from pygrid_tpu.utils import jaxenv

    jaxenv.configure_compile_cache()
    device = jaxenv.device_info()
    if device["platform"] == "cpu":
        # no fallback: a host-CPU run under these metric names would be
        # read as a device record
        sys.exit(
            f"bench.py measures an accelerator; JAX found platform "
            f"{device['platform']!r} ({device['device_kind']!r} × "
            f"{device['count']}) — run it on the chip"
        )
    peak_tflops()  # an unknown device_kind stops the run before any work
    watchdog = _arm_watchdog()
    # sections run unguarded: one that raises ends the run non-zero with
    # its traceback, instead of costing only its own keys
    proto: dict = {}
    kernel = bench_tpu()
    proto.update(bench_wire())
    proto.update(bench_telemetry_overhead())
    proto.update(bench_protocol("json"))
    proto.update(bench_protocol("binary"))
    proto.update(bench_protocol_hier())
    proto.update(bench_report_handler())
    proto.update(bench_data_centric())
    proto.update(bench_smpc())
    proto.update(bench_attention())
    proto.update(bench_attention_train())
    proto.update(bench_fed_transformer())
    proto.update(bench_fed_transformer_long())
    cpu_rps = bench_cpu_torch_baseline()
    # headline = the fastest of the identical-output kernel shapes
    # (identities asserted in test_fedavg_sim.py / test_fedavg_fused.py)
    best_rps = max(kernel["per_client_rps"], kernel["folded_rps"])
    best_mfu = max(kernel["per_client_mfu"], kernel["folded_mfu"])
    result = {
        "metric": "fedavg_rounds_per_sec_1k_clients",
        "value": round(best_rps, 3),
        "unit": "rounds/sec (1024 simulated MNIST-MLP clients, batch 64)",
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "device_count": device["count"],
        "vs_baseline": round(best_rps / cpu_rps, 1),
        "mfu_pct": round(best_mfu * 100, 1),
        "fedavg_rounds_per_sec_per_client_path": round(
            kernel["per_client_rps"], 3
        ),
        "mfu_pct_per_client_path": round(
            kernel["per_client_mfu"] * 100, 1
        ),
        "fedavg_rounds_per_sec_per_client_opaque": round(
            kernel["opaque_rps"], 3
        ),
        "mfu_pct_per_client_opaque": round(kernel["opaque_mfu"] * 100, 1),
        "fedavg_rounds_per_sec_folded_path": round(
            kernel["folded_rps"], 3
        ),
        "mfu_pct_folded_path": round(kernel["folded_mfu"] * 100, 1),
        "fedavg_rounds_per_sec_ls4": round(kernel["ls4_rps"], 3),
        "mfu_pct_ls4": round(kernel["ls4_mfu"] * 100, 1),
        "cpu_baseline_rounds_per_sec": round(cpu_rps, 4),
        **proto,
    }
    watchdog.cancel()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
