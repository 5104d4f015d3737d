"""Per-jit-callsite profiling and device-memory sampling.

Two questions the bench trajectory (BENCH_r0*.json) cannot answer from
aggregate counters alone:

1. **Where did a serving regression come from** — compile, execute, or
   host time? The profiler wraps every jitted program the serving
   :class:`~pygrid_tpu.serving.programs.ProgramSet` builds and splits
   wall-clock per call into *compile* (the call grew the program's jit
   cache — detected via the same ``_cache_size`` hook ``trace_count()``
   reads) and *execute* (steady-state) time, per program key. The
   wrapper never touches argument buffers after the call (the engine
   donates its cache buffers), only the clock. **Execute semantics**:
   the clock stops when the jitted call returns, WITHOUT forcing a
   device sync — on async-dispatch backends (TPU/GPU) ``execute`` is
   host dispatch time, a lower bound on device time; the end-to-end
   per-step figure including the result fetch is the engine's own
   ``serving_token_seconds`` histogram. Forcing a sync here would
   serialize the engine's host/device overlap just to measure it.
2. **Is device memory drifting** — a background sampler reads
   ``jax.local_devices()[*].memory_stats()`` on a cadence and serves
   the latest HBM gauges to ``/metrics`` (CPU backends report no
   memory_stats; the gauges are simply absent there).

Everything is off-switchable: ``PYGRID_PROFILER=off`` makes ``wrap()``
return the function unchanged and the sampler never start, so the
disabled cost is zero by construction (asserted by
``bench.bench_telemetry_overhead``). The compile-cache introspection
endpoint ``GET /telemetry/programs`` serves :func:`programs_snapshot`.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable

from pygrid_tpu.telemetry import bus

#: device-memory sampling cadence, seconds (env-overridable)
DEFAULT_SAMPLE_INTERVAL_S = 10.0


def enabled() -> bool:
    """The profiler off-switch (docs/OBSERVABILITY.md §6): the layer is
    on by default and disabled with ``PYGRID_PROFILER=off|0``."""
    return os.environ.get("PYGRID_PROFILER", "").lower() not in ("off", "0")


def cost_enabled() -> bool:
    """XLA cost attribution off-switch (``PYGRID_PROFILER_COST=off``):
    the analysis re-lowers each program once from captured avals — a
    trace, not an execution, but still work an operator may not want on
    a loaded node's telemetry endpoint."""
    return enabled() and os.environ.get(
        "PYGRID_PROFILER_COST", ""
    ).lower() not in ("off", "0")


class JitSiteProfiler:
    """Registry of jitted-program callsites and their timing splits.

    One entry per ``(model, kind, bucket)`` program — the same identity
    the serving ``ProgramSet`` compiles under. ``wrap()`` is the only
    producer; snapshots are read by ``GET /telemetry/programs``.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._programs: dict[tuple, dict] = {}
        #: program key -> (jitted fn, arg avals) captured at first call,
        #: for lazy XLA cost attribution (flops / bytes accessed); avals
        #: are ShapeDtypeStructs — metadata only, never buffer refs, so
        #: donated arguments are not pinned or touched
        self._cost_src: dict[tuple, tuple] = {}
        self._cost: dict[tuple, dict | None] = {}

    def wrap(
        self,
        fn: Callable,
        kind: str,
        bucket: int,
        model_id: str = "",
    ) -> Callable:
        """Time every call of a jitted ``fn``; classify as compile when
        the call grew the jit cache (``fn._cache_size`` — the
        ``trace_count()`` hook), execute otherwise. Returns ``fn``
        unchanged when the profiler is disabled."""
        if not enabled():
            return fn
        key = (model_id, kind, int(bucket))
        with self._lock:
            entry = self._programs.setdefault(
                key,
                {
                    "model": model_id,
                    "kind": kind,
                    "bucket": int(bucket),
                    "compiles": 0,
                    "compile_s": 0.0,
                    "hits": 0,
                    "execute_s": 0.0,
                    "traces": 0,
                },
            )
        cache_size = fn._cache_size
        # per-WRAPPER trace watermark (not the shared entry's): a
        # re-hosted model rebuilds its programs under the same key, and
        # the fresh jit cache must still classify its first calls as
        # compiles, not hits
        seen = {"traces": 0, "calls": 0}

        def wrapped(*args: Any, **kwargs: Any):
            if seen["calls"] == 0 and hasattr(fn, "lower"):
                # capture arg AVALS (shape/dtype only) BEFORE the first
                # call — afterwards donated buffers may be consumed and
                # even metadata reads would race the donation
                self._capture_avals(key, fn, args, kwargs)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            traces = cache_size()
            with self._lock:
                compiled = traces > seen["traces"]
                seen["traces"] = max(seen["traces"], traces)
                seen["calls"] += 1
                if compiled:
                    entry["compiles"] += 1
                    entry["compile_s"] += dt
                    entry["traces"] += 1
                else:
                    entry["hits"] += 1
                    entry["execute_s"] += dt
            if compiled:
                bus.observe("profiler_compile_seconds", dt, kind=kind)
            else:
                bus.observe("profiler_execute_seconds", dt, kind=kind)
            return out

        wrapped._cache_size = cache_size  # keep trace_count() honest
        wrapped.__wrapped__ = fn
        return wrapped

    def _capture_avals(self, key: tuple, fn, args, kwargs) -> None:
        """Shape/dtype skeleton of a program's first-call arguments —
        enough to re-``lower`` it later for cost analysis without
        holding (or ever having held) the real buffers."""
        if not cost_enabled():
            return
        try:
            import jax

            def _aval(a):
                if hasattr(a, "shape") and hasattr(a, "dtype"):
                    return jax.ShapeDtypeStruct(a.shape, a.dtype)
                return a  # static leaf (python scalar) — pass through

            avals = jax.tree_util.tree_map(_aval, (args, kwargs))
        except Exception:  # noqa: BLE001 — attribution is best-effort
            return
        with self._lock:
            self._cost_src.setdefault(key, (fn, avals))

    def _cost_for(self, key: tuple) -> dict | None:
        """Lazy per-program XLA cost analysis (flops / bytes accessed),
        computed ONCE per program from the captured avals and cached.
        Prefers ``Lowered.cost_analysis()`` (an HLO-level estimate — a
        trace, no backend compile); falls back to
        ``Compiled.cost_analysis()`` where the lowered hook is missing.
        None when unavailable (non-jitted wrappers, disabled knob)."""
        with self._lock:
            if key in self._cost:
                return self._cost[key]
            src = self._cost_src.get(key)
        if src is None or not cost_enabled():
            return None
        result: dict | None = None
        try:
            fn, (args, kwargs) = src
            lowered = fn.lower(*args, **kwargs)
            try:
                analysis = lowered.cost_analysis()
            except Exception:  # noqa: BLE001 — hook varies by jax version
                analysis = None
            if not analysis:
                analysis = lowered.compile().cost_analysis()
            if isinstance(analysis, (list, tuple)):
                analysis = analysis[0] if analysis else None
            if isinstance(analysis, dict):
                flops = analysis.get("flops")
                nbytes = analysis.get("bytes accessed")
                result = {
                    "flops": float(flops) if flops is not None else None,
                    "bytes_accessed": float(nbytes)
                    if nbytes is not None
                    else None,
                }
        except Exception:  # noqa: BLE001 — attribution is best-effort
            result = None
        with self._lock:
            self._cost[key] = result
        return result

    def snapshot(self, include_cost: bool = False) -> list[dict]:
        """Per-program rows for ``GET /telemetry/programs``: program
        key, bucket, compile ms, hit count, execute-time split — plus,
        with ``include_cost``, the program's XLA cost analysis (flops /
        bytes accessed per call and totals over its call count), and
        rows RANKED by total bytes accessed so the heaviest device
        pressure sorts first (wall-clock alone hides a cheap-to-dispatch
        but bandwidth-hungry program)."""
        with self._lock:
            rows = [
                (key, dict(e)) for key, e in self._programs.items()
            ]
        out = []
        for key, e in rows:
            hits = e["hits"]
            row = {
                "program": f"{e['kind']}/{e['bucket']}",
                "model": e["model"],
                "kind": e["kind"],
                "bucket": e["bucket"],
                "compiles": e["compiles"],
                "compile_ms": round(e["compile_s"] * 1e3, 3),
                "hits": hits,
                "execute_ms_total": round(e["execute_s"] * 1e3, 3),
                "execute_ms_mean": round(
                    e["execute_s"] * 1e3 / hits, 4
                )
                if hits
                else None,
            }
            if include_cost:
                cost = self._cost_for(key)
                calls = hits + e["compiles"]
                row["flops"] = cost["flops"] if cost else None
                row["bytes_accessed"] = (
                    cost["bytes_accessed"] if cost else None
                )
                row["bytes_accessed_total"] = (
                    cost["bytes_accessed"] * calls
                    if cost and cost["bytes_accessed"] is not None
                    else None
                )
                row["flops_total"] = (
                    cost["flops"] * calls
                    if cost and cost["flops"] is not None
                    else None
                )
            out.append(row)
        if include_cost:
            return sorted(
                out,
                key=lambda r: (
                    -(r.get("bytes_accessed_total") or 0.0),
                    r["model"], r["kind"], r["bucket"],
                ),
            )
        return sorted(
            out, key=lambda r: (r["model"], r["kind"], r["bucket"])
        )

    def reset(self) -> None:
        with self._lock:
            self._programs.clear()
            self._cost_src.clear()
            self._cost.clear()


class DeviceMemorySampler:
    """Background thread sampling device memory on a cadence.

    ``memory_stats()`` is a host-side XLA client call (no device sync),
    but ``/metrics`` should not pay even that per scrape under load —
    the sampler keeps the latest reading and the exporter serves it."""

    def __init__(self, interval_s: float | None = None) -> None:
        if interval_s is None:
            # fallback-on-typo parse: this constructor runs at module
            # load (for MEMORY), so a bad env var must not brick imports
            interval_s = bus.env_float(
                "PYGRID_PROFILER_INTERVAL_S", DEFAULT_SAMPLE_INTERVAL_S
            )
        self.interval_s = interval_s
        self._lock = threading.Lock()
        self._latest: list[dict] = []
        self._sampled_at: float | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        #: start()/stop() pairs outstanding — several apps in one
        #: process (the test grid) share this sampler; the thread stops
        #: only when the LAST app cleans up
        self._starts = 0

    @staticmethod
    def sample_once() -> list[dict]:
        """One synchronous read of every local device's memory stats.
        Devices that report none (CPU answers ``None``) contribute
        nothing. A backend that cannot initialise raises: the node's
        entry point initialises it before the sampler starts, so a
        failure here is a defect, not a state to sample around."""
        import jax

        out = []
        for d in jax.local_devices():
            stats = d.memory_stats()
            if not stats:
                continue
            out.append(
                {
                    "device": str(d.id),
                    "platform": d.platform,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                }
            )
        return out

    def latest(self) -> list[dict]:
        """The most recent background sample — NEVER samples inline:
        the reader may be the aiohttp event loop, and a cold
        ``import jax`` there would stall every socket. Empty until the
        sampler thread's first pass (it samples immediately on start)."""
        with self._lock:
            return [dict(s) for s in self._latest]

    def age_s(self) -> float | None:
        """Seconds since the last background sample (None before the
        first) — an age far beyond ``interval_s`` means the sampler
        stalled, which the gauges alone cannot show."""
        with self._lock:
            if self._sampled_at is None:
                return None
            return time.monotonic() - self._sampled_at

    def start(self) -> None:
        """Acquire the sampler. The refcount moves even when the
        profiler is disabled (only the thread spawn is gated), so every
        app's start()/stop() pair stays balanced — a disabled app's
        cleanup must never steal a live app's hold on the thread."""
        with self._lock:
            self._starts += 1
            if not enabled():
                return
            if (
                self._thread is not None
                and self._thread.is_alive()
                and not self._stop.is_set()
            ):
                return
            # no live sampling thread — or the live one is a stop()-
            # signalled straggler whose join timed out (it exits at its
            # next wait on ITS OWN captured event); spawn a fresh
            # sampler with a fresh event either way
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._loop,
                args=(self._stop,),
                name="pygrid-memory-sampler",
                daemon=True,
            )
            self._thread.start()

    def stop(self) -> None:
        """Release one start(); the thread stops when the last holder
        releases (apps in one process share the sampler)."""
        with self._lock:
            self._starts = max(0, self._starts - 1)
            if self._starts > 0:
                return
            thread = self._thread
            self._stop.set()
        if thread is not None:
            thread.join(timeout=2)

    def _loop(self, stop: threading.Event) -> None:
        while True:
            sample = self.sample_once()  # first pass BEFORE the wait
            with self._lock:
                self._latest = sample
                self._sampled_at = time.monotonic()
            if stop.wait(self.interval_s):
                return


#: process-wide instances — same posture as the telemetry bus
PROFILER = JitSiteProfiler()
MEMORY = DeviceMemorySampler()

wrap = PROFILER.wrap
programs_snapshot = PROFILER.snapshot


def export_device_memory(exp) -> None:
    """Write the latest device-memory gauges into an Exposition (called
    by the node ``/metrics`` handler). No-op when disabled or when the
    backend has no memory_stats (CPU)."""
    if not enabled():
        return
    for sample in MEMORY.latest():
        labels = {
            "device": sample["device"],
            "platform": sample["platform"],
        }
        for kind in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            value = sample.get(kind)
            if value is not None:
                exp.gauge(
                    "device_memory_bytes",
                    value,
                    "device (HBM) memory from jax memory_stats, by kind",
                    {**labels, "kind": kind},
                )


def reset() -> None:
    PROFILER.reset()
