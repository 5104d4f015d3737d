"""A serving cell: the node's app in this process (it holds the chip, so
the profiler can see it), the load generator a child that reaches it only
through the WS and HTTP doors.

Set-up: weights on the device from ``--seed`` in one jitted call, in the
type they are served in; the bundle registered with the node's model
store; the engine built and every shape of this cell's traffic warmed;
the generator connected. Then the window, the drain, the device's memory
peak — and only after the engine has given its pool back, the reference.

What the runner assumes of an engine is ``models/gpt2.py``'s last
paragraph and no more: what a request asks beside its length and what an
answer says beside its tokens pass through as named fields, and the
comparison that decides ``correct`` is the architecture's
(``model.served_gaps``). A traced run also keeps the node's counters at
the traced stretch's two ends (``ctx["snap_t0"]``, ``ctx["snap_t1"]``)
beside every device operation's seconds and count (``ctx["trace"]["ops"]``):
what a reader of one kernel's share of its roofline divides.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from lib import spec, stats, traffic as T
from lib.readers import memory_peak
from lib.trace import CompileCounter

MODEL_ID = "bench"


class CellFailure(Exception):
    """The cell could not be run as defined."""


# ── the node, in this process ────────────────────────────────────────────


class Node:
    """``pygrid_tpu.node.create_app`` served from a thread of this
    process."""

    def __init__(self) -> None:
        from pygrid_tpu.node import create_app

        self.app = create_app("bench")
        self.ctx = self.app["node"]
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self._loop = asyncio.new_event_loop()
        self._runner = None
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._serve, name="bench-node", daemon=True)

    def _serve(self) -> None:
        from aiohttp import web

        asyncio.set_event_loop(self._loop)

        async def up():
            self._runner = web.AppRunner(self.app, access_log=None)
            await self._runner.setup()
            await web.TCPSite(self._runner, "127.0.0.1", self.port).start()
            self._started.set()

        self._loop.run_until_complete(up())
        self._loop.run_forever()

    def start(self) -> None:
        self._thread.start()
        if not self._started.wait(60):
            raise CellFailure("the node's app did not start")

    def stop(self) -> None:
        """Clean the app up (closes the engines) and end the thread."""
        if self._runner is not None:
            asyncio.run_coroutine_threadsafe(
                self._runner.cleanup(), self._loop
            ).result(60)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(30)
        if self._thread.is_alive():
            raise CellFailure("the node's thread did not end")

    def get(self, path: str):
        import requests

        return requests.get(self.url + path, timeout=30)


def host(node: Node, model, generation_cache) -> object:
    """Register the bundle with the node's model store and build its
    engine. The parameters are already on the device, so the store's entry
    carries the parsed form (``generation_cache``) the first request would
    otherwise make from an uploaded blob: both as the architecture's
    adapter gives them (``hosted``)."""
    from pygrid_tpu.datacentric.model_storage import HostedModel

    hosted = HostedModel(
        model_id=MODEL_ID,
        model=model,
        allow_remote_inference=True,
        generation_cache=generation_cache,
    )
    node.ctx.models.storage(node.ctx.local_worker.id).cache.save(hosted)
    return node.ctx.serving.engine_for(MODEL_ID, hosted)


# ── reading the node's telemetry through its own routes ──────────────────


def scrape(node: Node) -> dict:
    """``/metrics`` as ``{sample name with labels: value}`` plus the
    engine's ``/telemetry/serving`` row under ``"engine"``."""
    out: dict = {}
    for line in node.get("/metrics").text.splitlines():
        if not line or line[0] == "#":
            continue
        name, _, value = line.rpartition(" ")
        try:
            out[name] = float(value)
        except ValueError:
            pass
    out["engine"] = node.get("/telemetry/serving").json()["engines"][0]
    return out


# ── the cell ─────────────────────────────────────────────────────────────


def start_generator(plan: dict, workdir: Path) -> subprocess.Popen:
    plan_path = workdir / "plan.json"
    plan_path.write_text(json.dumps(plan))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the child never holds the chip
    return subprocess.Popen(
        [sys.executable, str(Path(__file__).with_name("loadgen.py")), str(plan_path)],
        env=env, cwd=str(spec.ROOT), stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True,
    )


def latencies(results: list) -> dict:
    """Client-side numbers of a run, all on the generator's clock."""
    counted = [r for r in results if r.get("counted")]
    ok = [r for r in counted if r["ok"]]
    norm = [1e3 * (r["done"] - r["due"]) / r["n_new"] for r in ok]
    late = [1e3 * (r["sent"] - r["due"]) for r in counted if "sent" in r]
    return {"counted": counted, "ok": ok, "norm_ms_per_token": norm, "late_ms": late}


def window_tokens(results: list, w0: float, w1: float) -> float:
    """Output tokens completed in the window ``[w0, w1)``: an answered
    request's tokens, each credited in proportion to the part of the
    request's life (sent to answered) that lies inside the window. Answers
    are not streamed, so the client cannot see when a token was made; over
    a request's life is the nearest it can say, and whole requests at the
    window's edges would swing the count by some 3%."""
    total = 0.0
    for r in results:
        if not r.get("ok"):
            continue
        life = max(r["done"] - r["sent"], 1e-9)
        inside = max(0.0, min(r["done"], w1) - max(r["sent"], w0))
        total += r["n_new"] * inside / life
    return total


def check_served(cell: dict, model, seed: int, results: list, control: bool) -> dict:
    """``correct`` for a serving cell: a seeded sample of the requests the
    window finished, the longest among them, each handed to the
    architecture's own comparison (``model.served_gaps``) as it was sent
    and as it was answered; the number compared is the widest gap by which
    a served token's logit lies below the reference's best, and beside it
    the mean gap. How a token came to be (from which state, in what order)
    is the adapter's knowledge; the runner's is the sample, the shape and
    range of an answer's tokens, and the limits."""
    cfg, check = cell["config"], cell["traffic"]["check"]
    done = [r for r in results if r.get("ok") and r.get("counted") is not False]
    if not done:
        return {"correct": False, "compared": [], "why": "no request finished"}
    rng = np.random.default_rng([int(seed), 0xC0DE])
    longest = max(done, key=lambda r: r["prompt_len"] + r["n_new"])
    rest = [r for r in done if r is not longest]
    take = min(len(rest), check["sample"] - 1)
    sample = [longest] + [rest[i] for i in rng.permutation(len(rest))[:take]]
    pad_to = max(r["prompt_len"] + r["n_new"] for r in sample)
    pad_to = -(-pad_to // 128) * 128
    stored = cell["config"]["deployment"]["weights_dtype"]
    w = model.make_weights(seed, cfg, stored)
    ctrl = model.control_weights(w) if control else None
    gaps, cgaps, malformed = [], [], []
    for r in sample:
        request = {
            "prompt": T.prompt_tokens(cell["traffic"], seed, r, cfg["vocab_size"]),
            "n_new": r["n_new"], "fields": r.get("fields", {}),
        }
        answer = {"tokens": np.asarray(r["tokens"], np.int64), **r.get("answer", {})}
        served = answer["tokens"]
        if served.shape != (1, r["n_new"]) or served.min() < 0 or served.max() >= cfg["vocab_size"]:
            malformed.append(f"#{r['i']}: tokens {served.shape}")
            continue
        try:
            g, cg = model.served_gaps(w, cfg, request, answer, pad_to, ctrl)
        except (KeyError, ValueError) as err:
            # the adapter could not read the answer's fields: one more
            # malformed answer, not the end of the run
            malformed.append(f"#{r['i']}: {type(err).__name__}: {err}"[:200])
            continue
        gaps.append(g)
        if cg is not None:
            cgaps.append(cg)
    flat = np.concatenate(gaps) if gaps else np.zeros(1)
    compared = [
        {"name": "served_gap_max", "value": float(flat.max()), "limit": check["gap_max_limit"]},
        {"name": "served_gap_mean", "value": float(flat.mean()), "limit": check["gap_mean_limit"]},
        {"name": "malformed_answers", "value": float(len(malformed)), "limit": 0.0},
    ]
    out = {
        "correct": all(c["value"] <= c["limit"] for c in compared) and bool(np.isfinite(flat).all()),
        "compared": compared,
        "sampled_requests": len(sample),
        "sampled_tokens": int(flat.size),
    }
    if malformed:
        out["malformed"] = malformed[:3]
    if cgaps:
        cflat = np.concatenate(cgaps)
        out["control"] = {
            "served_gap_max": float(cflat.max()), "served_gap_mean": float(cflat.mean()),
        }
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
             control: bool = False, engine_hook=None, log=print) -> dict:
    """One run of a serving cell. ``t_start`` is the epoch second the
    process began. ``engine_hook`` lets a self-test break the timed path
    underneath. Returns the measurements; the caller shapes the result
    line."""
    import jax

    cfg, tr = cell["config"], cell["traffic"]
    deploy = cell["config"]["deployment"]
    model = spec.load_model(cfg["model_type"])
    os.environ.update({k: str(v) for k, v in deploy["env"].items()})
    phases = {"process_and_jax_s": time.time() - t_start}
    compiles = CompileCounter()

    t = time.time()
    params = jax.block_until_ready(
        model.make_program_params(seed, cfg, deploy["weights_dtype"])
    )
    phases["weights_s"] = time.time() - t

    t = time.time()
    node = Node()
    node.start()
    workdir = Path(tempfile.mkdtemp(prefix="perfbench_"))
    child = None
    try:
        # the generator starts now and connects while the engine warms up
        plan = {
            "url": node.url, "model_id": MODEL_ID, "traffic": tr, "seed": seed,
            "seconds": seconds, "vocab": cfg["vocab_size"],
            "timeout": float(tr["drain_s"]) + seconds,
            "out": str(workdir / "results.json"),
        }
        child = start_generator(plan, workdir)
        engine = host(node, *model.hosted(cfg, params))
        phases["node_and_engine_s"] = time.time() - t
        t = time.time()
        lo, hi = tr["prompt_len"]["lo"], tr["prompt_len"]["hi"]
        lens, n = [], lo
        while n < hi:
            lens.append(n)
            n *= 2
        engine.warmup(tuple(lens + [hi]))
        jax.block_until_ready(engine._k)
        if engine_hook is not None:
            engine_hook(engine)
        phases["warmup_s"] = time.time() - t

        t = time.time()
        for say, expect in ((None, "CONNECTED"), ("PROBE", "READY")):
            if say:
                child.stdin.write(say + "\n")
                child.stdin.flush()
            got = child.stdout.readline().strip()
            if got != expect:
                raise CellFailure(f"the load generator said {got!r}, not {expect}")
        phases["generator_s"] = time.time() - t

        built = T.build(tr, seed, seconds)
        lead_s = built["lead_in_s"]
        snap0 = scrape(node)
        compiled_before = compiles.n
        t_go = time.time() + 0.25
        child.stdin.write(f"GO {t_go!r}\n")
        child.stdin.flush()
        w0, w1 = t_go + lead_s, t_go + lead_s + seconds
        phases["lead_in_s"] = lead_s
        setup_s = w0 - t_start

        time.sleep(max(0.0, w0 - time.time()))
        snap1 = scrape(node)
        compiled_at_w0 = compiles.n
        tracer, snap_t0, snap_t1 = None, None, None
        if trace:
            from lib.trace import Tracer

            tracer = Tracer(workdir / "trace")
            tracer.start()
            # the counters over the traced stretch: read once the tracer
            # runs and again just before it stops (its clock's two ends)
            snap_t0 = scrape(node)
        trace_until = time.time() + float(tr["trace_s"])
        free_min = snap1["engine"]["kv_blocks_free"]
        while time.time() < w1 - 0.05:
            if tracer is not None and tracer.window_s == 0.0 and time.time() >= trace_until:
                snap_t1 = scrape(node)
                tracer.stop()
            time.sleep(min(1.0, max(0.0, w1 - time.time())))
            row = node.get("/telemetry/serving").json()["engines"][0]
            free_min = min(free_min, row["kv_blocks_free"])
        if tracer is not None and tracer.window_s == 0.0:
            snap_t1 = scrape(node)
            tracer.stop()
        time.sleep(max(0.0, w1 - time.time()))
        snap2 = scrape(node)
        compiled_in_window = compiles.n - compiled_at_w0

        rc = child.wait(float(tr["drain_s"]) + 60)
        if rc != 0:
            raise CellFailure(f"the load generator exited with code {rc}")
        snap3 = scrape(node)
        run = json.loads((workdir / "results.json").read_text())
        device = jax.devices()[0]
        mem = device.memory_stats() or {}
        engine_row = snap3["engine"]
        traced = tracer.result() if tracer is not None else None
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait(30)
        node.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    # the engine's pool and the program's weights go before the reference
    # makes its own: the memory peak above stays the program's
    del engine, params
    node.ctx.models.storage(node.ctx.local_worker.id).cache.remove(MODEL_ID)
    gc.collect()

    results = run["results"]
    if run["loop"] == "closed":
        n_list = len(built["requests"])
        log(f"perfbench: list used {100.0 * run['list_used']:.0f}% of {n_list} requests")
        if run["spent"]:
            # fewer callers than the cell defines for part of the run: a
            # rate read from it would be low for no fault of the engine
            raise CellFailure(
                f"the closed loop's list of {n_list} requests was spent before the "
                "window closed: the mix's `cycles` is too small for this engine"
            )
    t = time.time()
    verdict = check_served(cell, model, seed, results, control)
    phases["reference_s"] = time.time() - t

    rel0, rel1 = lead_s, lead_s + seconds
    if run["loop"] == "open":
        lat = latencies(results)
        attempted = len(lat["counted"])
        failed = attempted - len(lat["ok"])
    else:
        inside = [r for r in results if r["sent"] < rel1 and r["done"] > rel0]
        attempted = len(inside)
        failed = sum(not r["ok"] for r in inside)
        lat = {"norm_ms_per_token": [], "late_ms": []}
    by_door: dict = {}
    for r in results:
        by_door[r["door"]] = by_door.get(r["door"], 0) + 1
    log(f"perfbench: set-up {setup_s:.2f}s = " + ", ".join(f"{k} {v:.2f}" for k, v in phases.items()))
    log(f"perfbench: requests by door {by_door}; attempted {attempted}, failed {failed}; "
        f"compiles inside the window {compiled_in_window} "
        f"(after warm-up, before the window {compiled_at_w0 - compiled_before})")
    if lat["late_ms"]:
        log(f"perfbench: generator lateness ms p50 {stats.percentile(lat['late_ms'], 50):.3f} "
            f"p99 {stats.percentile(lat['late_ms'], 99):.3f} max {max(lat['late_ms']):.3f}")
        worst = sorted(
            (r for r in results if r.get("counted") and "sent" in r),
            key=lambda r: r["due"] - r["sent"],
        )[:3]
        log("perfbench: latest three " + "; ".join(
            f"#{r['i']} {r['door']} due {r['due']:.3f} queued {r['queued']:.3f} sent {r['sent']:.3f}"
            for r in worst
        ))
    errors = [r.get("error") for r in results if not r.get("ok")]
    if errors:
        log(f"perfbench: first request errors {errors[:3]}")
    e2e = {"setup_s": setup_s}
    if lat["norm_ms_per_token"]:
        e2e["norm_latency_p50"] = stats.percentile(lat["norm_ms_per_token"], 50)
        e2e["norm_latency_p90"] = stats.percentile(lat["norm_ms_per_token"], 90)
    if run["loop"] == "closed":
        e2e["gen_tokens_per_s"] = window_tokens(results, rel0, rel1) / seconds
    ctx = {
        "kind": "serve", "loop": run["loop"], "seconds": seconds, "results": results,
        "late_ms": lat["late_ms"], "window": (rel0, rel1),
        "snap_go": snap0, "snap_w0": snap1, "snap_w1": snap2, "snap_end": snap3,
        "snap_t0": snap_t0, "snap_t1": snap_t1,
        "kv_blocks_free_min": free_min, "engine": engine_row,
        "memory_peak_bytes": memory_peak(mem), "trace": traced,
    }
    return {
        "e2e": e2e, "ctx": ctx, "verdict": verdict, "attempted": attempted,
        "failed": failed, "compiles_in_window": compiled_in_window,
        "memory_peak_bytes": memory_peak(mem), "trace": traced,
        "phases": phases,
    }
