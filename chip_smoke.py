"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the grid's two device products once, through the doors a user
would use, at the full width of the flagship decoder, and checks what
comes out:

- **serve** — ``python -m pygrid_tpu.node`` as the one process holding
  the chip; a client hosts ``decode.bundle(cfg, params)`` and sends
  concurrent ``run-generation`` requests over WS and HTTP (mixed prompt
  lengths, ``n_new`` past one scheduling quantum, a shared multi-block
  prefix, more requests than slots) so ``paged_prefill``,
  ``paged_decode`` and ``paged_decode_fused`` all compile and run;
- after the node has exited, ONE second child runs on the chip, in turn:
  **reference** (every token the node answered against a float32
  full-forward reference, and the bit-identity count against
  ``decode.generate`` at the engine's dtypes), **train** (a handful of
  fused FedAvg rounds over the flash-attention transformer),
  **attention** (flash forward and backward against the XLA reference),
  **ring** (the Pallas uint64 ring matmul against numpy) and
  **sharded** (the client-sharded fused round, when there are four
  chips).

One process per chip: this parent pins its own JAX to the CPU (it only
builds the seeded weights and the traffic), gives its children
``JAX_PLATFORMS=tpu,cpu`` — an explicit list makes a failed TPU
initialisation fatal instead of a silent fall-back to the host — and
starts the second child only after the first has exited.

Every phase prints one JSON line naming the device it ran on. The run
exits non-zero unless every phase ran on ``platform == "tpu"`` and
passed; a failed phase is a non-zero exit, not a caught exception. The
last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The phases are plain functions of the config and ``interpret`` so that
``tests/unit/test_chip_smoke.py`` walks the same control flow at a tiny
size on the CPU; ``main()`` pins the real values.
"""

from __future__ import annotations

import base64
import functools
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent

#: the one model the repo has, at full width (bench.py's flagship)
FLAGSHIP = dict(
    vocab=8192, d_model=512, n_heads=4, n_layers=4, d_ff=2048, max_len=512
)
SEED = 0
#: the platform list the children get: TPU first and explicit, so a chip
#: that cannot be initialised stops the child; CPU beside it because the
#: node's averaging plans run on ``jax.devices("cpu")``
CHILD_PLATFORMS = "tpu,cpu"

#: a served token may trail the float32 reference's best logit by at
#: most this much. The engine computes logits from a bf16 KV pool with
#: f32 matmuls at the TPU's default precision, in programs of several
#: batch widths; with random weights 2.6% of positions have a
#: top-1/top-2 margin under 0.01 (median margin 0.36), so near-ties flip.
#: Measured on the v5e over 160 requests / 4000 tokens (PR 21): worst
#: gap 0.018. A token that is not a rounding tie sits a median 0.36
#: below the best, so 1/16 separates rounding from defects.
TIE_TOL = 0.0625
#: flash vs XLA attention, forward and each gradient: 2^-6 of the
#: reference's largest magnitude, i.e. two bf16 ulps at the top binade
#: (measured 0.0045, PR 21 chip run)
ATTN_REL_TOL = 2.0**-6


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ── the phases ──────────────────────────────────────────────────────────


def make_traffic(cfg, seed: int, block: int = 64) -> list[dict]:
    """Seeded request mix for one pass: 14 single-row greedy requests —
    more than the engine's 8 slots, so some wait in the queue while
    others decode (the per-step ``paged_decode`` path) and the tail
    drains with the queue empty (the fused scan). Three (prompt tail,
    ``n_new``) shapes, one below and two past the scheduling quantum
    (8); every fourth prompt opens with the same ``2·block + 22`` tokens
    (two full KV blocks to share). Token contents all differ; shapes
    repeat on purpose — ``decode.generate`` compiles one program per
    shape in the reference phase. Two requests go through the HTTP
    door, the rest over WS."""
    rng = np.random.RandomState(seed)
    room = cfg.max_len
    shared = rng.randint(0, cfg.vocab, size=min(2 * block + 22, room // 2))
    shapes = (
        (max(3, room // 64), 5),
        (room // 16, min(12, room // 5)),
        (room // 9, min(38, room // 4 - 1)),
    )
    out = []
    for i in range(14):
        tail_len, n_new = shapes[i % 3]
        tail = rng.randint(0, cfg.vocab, size=tail_len)
        prompt = np.concatenate([shared, tail]) if i % 4 == 0 else tail
        out.append(
            {
                "prompt": prompt[None, :].astype(np.int32),
                "n_new": n_new,
                "door": "http" if i in (5, 10) else "ws",
            }
        )
    return out


def _generate_over(door: str, node_url: str, token: str, model_id: str,
                   prompt: np.ndarray, n_new: int, timeout: float):
    """One greedy generation through the named door."""
    from pygrid_tpu.client import DataCentricFLClient
    from pygrid_tpu.serde import serialize

    if door == "ws":
        client = DataCentricFLClient(node_url, timeout=timeout)
        try:
            return client.run_remote_generation(model_id, prompt, n_new=n_new)
        finally:
            client.close()
    import requests

    resp = requests.post(
        node_url + "/data-centric/run-generation",
        headers={"token": token},
        json={
            "model_id": model_id,
            "data": base64.b64encode(serialize(prompt)).decode(),
            "n_new": n_new,
            "temperature": 0.0,
        },
        timeout=timeout,
    )
    _check(resp.status_code == 200, f"HTTP door answered {resp.status_code}: "
           f"{resp.text[:300]}")
    return np.asarray(resp.json()["tokens"])


def _drive_pass(node_url, token, model_id, traffic, timeout) -> list:
    """All of ``traffic`` at once, one thread per request; returns the
    answers in request order. Any request's exception fails the pass."""
    answers: list = [None] * len(traffic)
    errors: list = []

    def one(i, req):
        try:
            answers[i] = _generate_over(
                req["door"], node_url, token, model_id, req["prompt"],
                req["n_new"], timeout,
            )
        except Exception as err:  # noqa: BLE001 — re-raised below
            errors.append((i, err))

    threads = [
        threading.Thread(target=one, args=(i, r), daemon=True)
        for i, r in enumerate(traffic)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    _check(not any(t.is_alive() for t in threads),
           f"generation requests still running after {timeout:.0f}s")
    if errors:
        i, err = errors[0]
        raise SmokeFailure(
            f"{len(errors)} of {len(traffic)} requests failed; "
            f"request {i}: {type(err).__name__}: {err}"
        )
    return answers


def serve_phase(cfg, params, node_url: str, platform: str,
                block: int = 64, timeout: float = 600.0) -> dict:
    """Host the bundle on a RUNNING node and drive generation through
    both doors; returns the phase's result row plus the requests and
    answers (``served``) for the reference phase to judge. ``block`` is
    the node's KV page size (the traffic shares two whole pages)."""
    import requests

    from pygrid_tpu.client import DataCentricFLClient
    from pygrid_tpu.models import decode

    model_id = "flagship"
    client = DataCentricFLClient(node_url, timeout=timeout)
    try:
        hosted = client.serve_model(
            decode.bundle(cfg, params), model_id,
            allow_remote_inference=True,
        )
        _check(hosted.get("success") is True, f"serve_model: {hosted}")
        token = client._auth_token
        traffic = make_traffic(cfg, SEED, block)
        t0 = time.perf_counter()
        cold = _drive_pass(node_url, token, model_id, traffic, timeout)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = _drive_pass(node_url, token, model_id, traffic, timeout)
        warm_s = time.perf_counter() - t0
        # the same request alone, twice: same programs, same schedule —
        # greedy must repeat exactly
        solo = traffic[0]
        first = client.run_remote_generation(
            model_id, solo["prompt"], n_new=solo["n_new"]
        )
        again = client.run_remote_generation(
            model_id, solo["prompt"], n_new=solo["n_new"]
        )
    finally:
        client.close()
    for req, ans in zip(traffic * 2, cold + warm):
        _check(ans.shape == (1, req["n_new"]),
               f"answer shape {ans.shape}, asked (1, {req['n_new']})")
        _check(bool((ans >= 0).all() and (ans < cfg.vocab).all()),
               "answer token outside the vocabulary")
    _check(np.array_equal(first, again),
           "the same greedy request, alone, answered differently twice")

    engines = requests.get(
        node_url + "/telemetry/serving", timeout=30
    ).json()["engines"]
    _check(len(engines) == 1, f"expected one engine, got {len(engines)}")
    row = engines[0]
    _check(row["platform"] == platform,
           f"the node's engine runs on platform {row['platform']!r} "
           f"({row['device_kind']!r}), need {platform!r}")
    _check(row["fused"] is True, f"engine fused={row['fused']}")
    _check(row["fused_scans"] > 0, "no fused decode scan ran")
    _check(row["prefix_hits"] > 0, "no prefix-cache hit")
    _check(row["queue_depth"] == 0 and row["live_slots"] == 0,
           "engine not drained")
    _check(
        row["kv_demand_pages"] == 0
        and row["kv_blocks_free"] + row["kv_blocks_cached"]
        == row["kv_blocks_total"],
        f"KV ledger does not balance after drain: free "
        f"{row['kv_blocks_free']} + cached {row['kv_blocks_cached']} != "
        f"total {row['kv_blocks_total']} (demand {row['kv_demand_pages']})",
    )
    programs = requests.get(
        node_url + "/telemetry/programs", params={"cost": "0"}, timeout=30
    ).json()["programs"]
    kinds = {p["kind"] for p in programs}
    need = {"paged_prefill", "paged_decode", "paged_decode_fused"}
    _check(need <= kinds, f"programs never compiled: {sorted(need - kinds)}")
    return {
        "phase": "serve",
        "platform": row["platform"],
        "device_kind": row["device_kind"],
        "device_count": row["device_count"],
        "dtypes": {
            "kv": row["kv_dtype"], "compute": row["compute_dtype"],
        },
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 3),
        "requests": 2 * len(traffic) + 2,
        "doors": sorted({r["door"] for r in traffic}),
        "fused_scans": row["fused_scans"],
        "prefix_hits": row["prefix_hits"],
        "compiles": row["compiles_total"],
        "compile_s": round(
            sum(p["compile_ms"] for p in programs) / 1e3, 3
        ),
        "programs": sorted(p["program"] for p in programs),
        "warm_pass_identical": sum(
            bool(np.array_equal(a, b)) for a, b in zip(cold, warm)
        ),
        "served": [
            {
                "prompt": req["prompt"].tolist(),
                "n_new": req["n_new"],
                "tokens": ans.tolist(),
            }
            for req, ans in zip(traffic * 2, cold + warm)
        ],
    }


def reference_phase(cfg, params, served: list[dict], kv_dtype: str,
                    tie_tol: float) -> dict:
    """Judge what the node answered, on the device the node ran on.

    Every served token is checked against a plain float32 full forward
    (``transformer.apply`` at ``highest`` matmul precision) over the
    prompt plus the tokens served before it: the token must be that
    position's best logit or within ``tie_tol`` of it. That is the
    statement that survives rounding — with random weights the largest
    logit changes hands on the last bits.

    Beside it, the engine's old contract is MEASURED, not asserted:
    how many answers equal ``decode.generate`` bit for bit at the same
    dtypes. On the CPU at f32 that was all of them; on the chip the
    pool is bf16, matmuls run at default precision, and a request's
    steps run in programs of different batch widths."""
    import jax
    import jax.numpy as jnp

    from pygrid_tpu.models import decode, transformer

    t0 = time.perf_counter()
    params = [jnp.asarray(p) for p in params]

    @jax.jit
    def next_token_logits(tokens):
        with jax.default_matmul_precision("highest"):
            return transformer.apply(params, tokens, cfg)[0]

    @functools.lru_cache(maxsize=None)
    def generate_fn(n_new: int):
        # one program per (n_new, prompt length): jit keys the shapes
        return jax.jit(
            lambda x: decode.generate(
                params, x, n_new, cfg, cache_dtype=jnp.dtype(kv_dtype)
            )
        )

    generated: dict = {}  # the two passes send the same prompts
    worst_gap = 0.0
    identical = 0
    diverged_at = []
    for req in served:
        prompt = np.asarray(req["prompt"], np.int32)
        tokens = np.asarray(req["tokens"], np.int32)
        p_len, n_new = prompt.shape[1], tokens.shape[1]
        # causal: padding after the sequence cannot reach back into it,
        # so one [1, max_len] program serves every request
        seq = np.zeros((1, cfg.max_len), np.int32)
        seq[0, :p_len] = prompt[0]
        seq[0, p_len : p_len + n_new - 1] = tokens[0, :-1]
        logits = np.asarray(next_token_logits(jnp.asarray(seq)))
        rows = logits[p_len - 1 : p_len - 1 + n_new]
        _check(bool(np.isfinite(rows).all()), "reference logits not finite")
        gaps = rows.max(axis=-1) - rows[np.arange(n_new), tokens[0]]
        worst_gap = max(worst_gap, float(gaps.max()))
        _check(
            float(gaps.max()) <= tie_tol,
            f"served token {int(gaps.argmax())} of a request (prompt "
            f"{p_len}, n_new {n_new}) trails the float32 reference's "
            f"best logit by {float(gaps.max()):.4f} > {tie_tol}",
        )
        key = (prompt.tobytes(), n_new)
        if key not in generated:
            generated[key] = np.asarray(
                generate_fn(n_new)(jnp.asarray(prompt))
            )
        ref = generated[key]
        if np.array_equal(ref, tokens):
            identical += 1
        else:
            diverged_at.append(int(np.argmax(ref[0] != tokens[0])))
    return {
        "phase": "reference",
        "dtypes": {"kv": kv_dtype, "reference": "float32/highest"},
        "cold_s": round(time.perf_counter() - t0, 3),
        "warm_s": None,
        "answers": len(served),
        "worst_gap_to_reference_best": round(worst_gap, 5),
        "tie_tol": tie_tol,
        "bit_identical_to_generate": identical,
        "first_divergence_index": diverged_at,
    }


def _flagship_loss(cfg, attn_fn):
    from pygrid_tpu.models import transformer

    return functools.partial(
        transformer.loss_and_acc, cfg=cfg, attn_fn=attn_fn,
        compute_dtype="bfloat16", ce_grad_dtype="bfloat16",
    )


def _client_batches(cfg, clients: int, batch: int):
    import jax
    import jax.numpy as jnp

    X = jax.random.randint(
        jax.random.PRNGKey(SEED + 1), (clients, batch, cfg.max_len), 0,
        cfg.vocab,
    )
    return X, jnp.roll(X, -1, axis=-1)


def train_phase(cfg, interpret: bool, clients: int = 8, batch: int = 4,
                rounds: int = 6) -> dict:
    """A trainer that takes a few steps: ``rounds`` fused FedAvg rounds
    of ``clients × batch × max_len`` tokens through the flash-attention
    transformer (bf16 matmuls, bf16 CE backward) — bench.py's program.
    The loss must be finite and fall."""
    import jax
    import jax.numpy as jnp

    from pygrid_tpu.models import transformer
    from pygrid_tpu.parallel import make_fused_rounds
    from pygrid_tpu.parallel.pallas_attention import flash_attention

    loss_fn = _flagship_loss(
        cfg, functools.partial(flash_attention, interpret=interpret)
    )
    params = transformer.init(jax.random.PRNGKey(SEED), cfg)
    X, y = _client_batches(cfg, clients, batch)
    rounds_fn = make_fused_rounds(loss_fn, n_rounds=rounds)
    t0 = time.perf_counter()
    _final, losses, _accs = rounds_fn(params, X, y, jnp.float32(0.1))
    losses = np.asarray(losses)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(rounds_fn(params, X, y, jnp.float32(0.1)))
    warm_s = time.perf_counter() - t0
    _check(bool(np.isfinite(losses).all()), f"loss not finite: {losses}")
    _check(float(losses[-1]) < float(losses[0]),
           f"loss did not fall: {losses}")
    return {
        "phase": "train",
        "dtypes": {"params": "float32", "compute": "bfloat16",
                   "ce_grad": "bfloat16"},
        "pallas": "interpreted" if interpret else "compiled",
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 3),
        "rounds": rounds,
        "tokens_per_round": clients * batch * cfg.max_len,
        "losses": [round(float(v), 5) for v in losses],
    }


def attention_phase(cfg, interpret: bool, batch: int = 4,
                    dtype: str = "bfloat16",
                    rel_tol: float = ATTN_REL_TOL) -> dict:
    """Flash attention forward AND backward against the XLA reference
    (``ring_attention.attention``) at the model's attention shape, on
    this device. Each of out, dq, dk, dv must agree within ``rel_tol``
    of the reference's largest magnitude."""
    import jax
    import jax.numpy as jnp

    from pygrid_tpu.parallel.pallas_attention import flash_attention
    from pygrid_tpu.parallel.ring_attention import attention

    heads, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
    keys = jax.random.split(jax.random.PRNGKey(SEED + 2), 4)
    q, k, v, do = (
        jax.random.normal(kk, (batch, cfg.max_len, heads, dh)).astype(dtype)
        for kk in keys
    )

    def out_and_grads(attn):
        def scalar(q, k, v):
            out = attn(q, k, v, causal=True)
            return jnp.sum(
                out.astype(jnp.float32) * do.astype(jnp.float32)
            ), out

        (_, out), grads = jax.value_and_grad(
            scalar, argnums=(0, 1, 2), has_aux=True
        )(q, k, v)
        return (out, *grads)

    t0 = time.perf_counter()
    got = jax.block_until_ready(
        out_and_grads(functools.partial(flash_attention, interpret=interpret))
    )
    cold_s = time.perf_counter() - t0
    want = out_and_grads(attention)
    errors = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b.astype(jnp.float32))
        _check(bool(np.isfinite(a).all()), f"flash {name} not finite")
        err = float(np.abs(a - b).max() / np.abs(b).max())
        errors[name] = round(err, 6)
        _check(err <= rel_tol,
               f"flash {name} differs from the XLA reference by {err:.5f} "
               f"of its largest magnitude (> {rel_tol:.5f})")
    return {
        "phase": "attention",
        "dtypes": {"qkv": dtype},
        "pallas": "interpreted" if interpret else "compiled",
        "cold_s": round(cold_s, 3),
        "warm_s": None,
        "shape": [batch, cfg.max_len, heads, dh],
        "rel_err_vs_xla": errors,
        "rel_tol": rel_tol,
    }


def ring_phase(interpret: bool) -> dict:
    """``smpc.ring.ring_matmul`` on 2-D operands equals the numpy
    ``uint64`` product exactly. On the chip (``interpret=False``) the
    public entry must route to the Pallas kernel by itself; interpreted,
    the kernel is called directly (off-TPU the entry takes XLA)."""
    import jax

    from pygrid_tpu.smpc import ring
    from pygrid_tpu.smpc.pallas_kernels import pallas_ring_matmul

    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    for m, k, n in ((64, 64, 64), (200, 300, 130)):
        a = rng.integers(0, 2**64, size=(m, k), dtype=np.uint64)
        b = rng.integers(0, 2**64, size=(k, n), dtype=np.uint64)
        ra, rb = ring.to_ring(a), ring.to_ring(b)
        if interpret:
            out = pallas_ring_matmul(ra, rb, interpret=True)
        else:
            _check(
                "pallas_call" in str(jax.make_jaxpr(ring.ring_matmul)(ra, rb)),
                "ring_matmul did not take the Pallas route on platform "
                f"{jax.default_backend()!r}",
            )
            out = ring.ring_matmul(ra, rb)
        with np.errstate(over="ignore"):
            want = a @ b
        _check(np.array_equal(ring.from_ring(out), want),
               f"ring matmul {m}x{k}x{n} is not exact")
    return {
        "phase": "ring",
        "dtypes": {"ring": "uint64 as 2 x uint32"},
        "pallas": "interpreted" if interpret else "compiled",
        "cold_s": round(time.perf_counter() - t0, 3),
        "warm_s": None,
        "exact": True,
    }


def sharded_phase(cfg, interpret: bool, clients: int = 8,
                  batch: int = 4) -> dict:
    """The client-sharded fused round over a 4-way ``"clients"`` mesh —
    only when this process really has four devices; otherwise the row
    says ``not run`` (devices are never provisioned here). Checks that
    the client shards sit on four distinct devices and that the round
    agrees with the single-device fused round."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pygrid_tpu.models import transformer
    from pygrid_tpu.parallel import make_fused_round, make_sharded_fused_round
    from pygrid_tpu.parallel.pallas_attention import flash_attention

    n = jax.device_count()
    if n < 4:
        return {
            "phase": "sharded", "ran": False,
            "verdict": f"not run: {n} device",
            "dtypes": None, "cold_s": None, "warm_s": None,
        }
    devices = jax.devices()[:4]
    mesh = Mesh(np.asarray(devices), ("clients",))
    loss_fn = _flagship_loss(
        cfg, functools.partial(flash_attention, interpret=interpret)
    )
    params = transformer.init(jax.random.PRNGKey(SEED), cfg)
    X, y = _client_batches(cfg, clients, batch)
    Xs = jax.device_put(X, NamedSharding(mesh, P("clients")))
    ys = jax.device_put(y, NamedSharding(mesh, P("clients")))
    homes = {s.device for s in Xs.addressable_shards}
    _check(homes == set(devices),
           f"client shards sit on {sorted(d.id for d in homes)}, expected "
           f"{[d.id for d in devices]}")
    round_fn = make_sharded_fused_round(loss_fn, mesh)
    lr = jnp.float32(0.1)
    t0 = time.perf_counter()
    new_p, loss, _acc = jax.block_until_ready(round_fn(params, Xs, ys, lr))
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(round_fn(params, Xs, ys, lr))
    warm_s = time.perf_counter() - t0
    # replicated outputs: every device holds the whole updated model
    _check({s.device for s in new_p[0].addressable_shards} == set(devices),
           "updated params are not on all four devices")
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in devices
    ]
    _check(all(b is None for b in in_use) or all(b for b in in_use),
           f"per-device bytes_in_use {in_use}: some device holds nothing")
    _ref_p, ref_loss, _ = make_fused_round(loss_fn)(params, X, y, lr)
    _check(np.isfinite(float(loss)), f"sharded loss {float(loss)}")
    _check(abs(float(loss) - float(ref_loss)) <= 1e-2 * abs(float(ref_loss)),
           f"sharded loss {float(loss):.5f} vs single-device "
           f"{float(ref_loss):.5f}")
    return {
        "phase": "sharded", "ran": True,
        "verdict": "ran on 4 devices",
        "dtypes": {"params": "float32", "compute": "bfloat16"},
        "pallas": "interpreted" if interpret else "compiled",
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 3),
        "shard_devices": sorted(d.id for d in homes),
        "bytes_in_use": in_use,
        "loss": round(float(loss), 5),
        "single_device_loss": round(float(ref_loss), 5),
    }


# ── processes ───────────────────────────────────────────────────────────


def child_env(platforms: str) -> dict:
    """The environment the chip-holding children get. ``JAX_PLATFORMS``
    is set HERE and only here — the parent pins itself through
    ``jax.config`` precisely so its own CPU pin never leaks in."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platforms
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO), *filter(None, [env.get("PYTHONPATH")])]
    )
    return env


def start_node(env: dict, log_path: Path, timeout: float = 300.0):
    """``python -m pygrid_tpu.node`` as a child; returns ``(process,
    url)`` once it answers. The node initialises its backend before it
    opens the port, so a chip it cannot reach shows up here as an
    exited child, with the reason in its log."""
    import requests

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}"
    with log_path.open("w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "pygrid_tpu.node", "--id", "smoke",
             "--host", "127.0.0.1", "--port", str(port)],
            env=env, cwd=str(REPO), stdout=log, stderr=subprocess.STDOUT,
        )
    deadline = time.monotonic() + timeout
    while True:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"the node exited with code {proc.returncode} before "
                f"answering:\n{log_path.read_text()[-3000:]}"
            )
        try:
            requests.get(url + "/", timeout=2)
            return proc, url
        except requests.RequestException:
            if time.monotonic() > deadline:
                stop(proc)
                raise SmokeFailure(
                    f"the node did not answer within {timeout:.0f}s:\n"
                    f"{log_path.read_text()[-3000:]}"
                ) from None
            time.sleep(0.5)


def stop(proc: subprocess.Popen) -> None:
    """End a child and wait until it is gone (the chip is free again
    only then)."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(30)


def _emit(row: dict, device: dict, cache_dir: str, before: int) -> None:
    """One result line: the phase row, stamped with the device and the
    compile cache's state around the phase."""
    from pygrid_tpu.utils import jaxenv

    row.setdefault("platform", device["platform"])
    row.setdefault("device_kind", device["device_kind"])
    row.setdefault("device_count", device["count"])
    row["compile_cache"] = {
        "dir": cache_dir,
        "entries_before": before,
        "entries_after": jaxenv.cache_entries(cache_dir),
    }
    print(json.dumps({"ok": True, **row}), flush=True)


def device_child(workdir: Path) -> None:
    """The second child: every phase that needs the device after the
    node has given it back, in ONE process. Raises on the first failed
    check — the traceback and a non-zero exit are the report."""
    import jax

    from pygrid_tpu.models import transformer
    from pygrid_tpu.utils import jaxenv

    spec = json.loads((workdir / "served.json").read_text())
    platform, interpret = spec["platform"], spec["interpret"]
    cache_dir = jaxenv.configure_compile_cache()
    device = jaxenv.device_info()
    _check(device["platform"] == platform,
           f"the trainer child runs on platform {device['platform']!r} "
           f"({device['device_kind']!r}), need {platform!r}")
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    cfg = transformer.TransformerConfig(**spec["cfg"])
    with np.load(workdir / "params.npz") as npz:
        params = [npz[f"p{i}"] for i in range(len(npz.files))]
    phases = (
        lambda: reference_phase(
            cfg, params, spec["served"], spec["kv_dtype"], TIE_TOL
        ),
        lambda: train_phase(cfg, interpret),
        lambda: attention_phase(cfg, interpret),
        lambda: ring_phase(interpret),
        lambda: sharded_phase(cfg, interpret),
    )
    for phase in phases:
        before = jaxenv.cache_entries(cache_dir)
        seen = dict(cache_events)
        row = phase()
        row["compile_cache_hits"] = cache_events["hits"] - seen["hits"]
        row["compile_cache_misses"] = cache_events["misses"] - seen["misses"]
        _emit(row, device, cache_dir, before)


def run(cfg_fields: dict, platform: str, interpret: bool,
        child_platforms: str, block: int = 64) -> int:
    """The whole smoke for one model: serve child, then trainer child.
    ``main()`` pins the real values; everything here is explicit so the
    plumbing can be walked at a tiny size off the chip."""
    # this parent builds weights and traffic and must never hold the
    # chip its children need: pinned through jax.config, not the
    # environment the children inherit
    import jax

    jax.config.update("jax_platforms", "cpu")

    from pygrid_tpu import native
    from pygrid_tpu.models import transformer
    from pygrid_tpu.utils import jaxenv

    cache_dir = jaxenv.configure_compile_cache()
    cfg = transformer.TransformerConfig(**cfg_fields)
    params = [
        np.asarray(p)
        for p in transformer.init(jax.random.PRNGKey(SEED), cfg)
    ]
    print(
        f"chip_smoke: parent backend {jax.default_backend()!r}; children "
        f"get JAX_PLATFORMS={child_platforms}; native={native.BACKEND}; "
        f"compile cache {cache_dir} "
        f"({jaxenv.cache_entries(cache_dir)} entries)",
        file=sys.stderr,
    )
    _check(jax.default_backend() == "cpu", "the parent left the CPU")
    env = child_env(child_platforms)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        workdir = Path(tmp)
        before = jaxenv.cache_entries(cache_dir)
        node, url = start_node(env, workdir / "node.log")
        try:
            row = serve_phase(cfg, params, url, platform, block)
        except BaseException:
            sys.stderr.write((workdir / "node.log").read_text()[-3000:])
            raise
        finally:
            stop(node)
        served = row.pop("served")
        device = {
            "platform": row["platform"],
            "device_kind": row["device_kind"],
            "count": row["device_count"],
        }
        # which wire kernels carried the 68 MB bundle: the C++ library
        # built from this checkout's source, or the numpy paths
        row["native"] = native.BACKEND
        _emit(row, device, cache_dir, before)
        np.savez(
            workdir / "params.npz",
            **{f"p{i}": p for i, p in enumerate(params)},
        )
        (workdir / "served.json").write_text(
            json.dumps(
                {
                    "cfg": cfg_fields,
                    "platform": platform,
                    "interpret": interpret,
                    "kv_dtype": row["dtypes"]["kv"],
                    "served": served,
                }
            )
        )
        # only now — the node has exited and released the chip
        child = subprocess.run(
            [sys.executable, str(REPO / "chip_smoke.py"),
             "--device-phases", str(workdir)],
            env=env, cwd=str(REPO), stdout=subprocess.PIPE, text=True,
            timeout=900,
        )
    sys.stdout.write(child.stdout)
    sys.stdout.flush()
    _check(child.returncode == 0,
           f"the trainer child exited {child.returncode}")
    rows = [json.loads(line) for line in child.stdout.splitlines()]
    ran = [r["phase"] for r in rows]
    _check(ran == ["reference", "train", "attention", "ring", "sharded"],
           f"the trainer child reported phases {ran}")
    for r in rows:
        _check(
            (r["platform"], r["device_kind"], r["device_count"])
            == (platform, device["device_kind"], device["count"]),
            f"phase {r['phase']} ran on {r['platform']!r} "
            f"{r['device_kind']!r} × {r['device_count']}, the node on "
            f"{device}",
        )
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": device["platform"],
                    "kind": device["device_kind"],
                    "count": device["count"],
                },
            }
        ),
        flush=True,
    )
    return 0


def main() -> int:
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if pinned and "tpu" not in pinned.split(","):
        # the environment itself keeps JAX off the chip: say which
        # platform this run was given instead of starting anything
        sys.exit(
            f"chip_smoke: JAX_PLATFORMS pins this run to platform "
            f"{pinned!r}; every phase must run on 'tpu'"
        )
    return run(FLAGSHIP, "tpu", interpret=False,
               child_platforms=CHILD_PLATFORMS)


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--device-phases":
        device_child(Path(sys.argv[2]))
        sys.exit(0)
    sys.exit(main())
