"""Grid telemetry — the shared instrumentation spine.

The reference stack has no observability at all (stdlib logging only —
SURVEY §5.1, §5.5). This subsystem is what a production grid operates
through:

- :mod:`pygrid_tpu.telemetry.bus` — a process-wide, always-on,
  lock-cheap event bus: ring-buffered structured events, labeled
  counters, and log-linear-bucket histograms.
- :mod:`pygrid_tpu.telemetry.trace` — distributed-trace context
  (``trace_id``/``span_id``) with the wire encodings: a 24-byte header
  on wire-v2 binary frames, a ``trace`` JSON field on legacy framing,
  and ``X-PyGrid-Trace`` on HTTP.
- :mod:`pygrid_tpu.telemetry.timeline` — per-FL-cycle round timelines
  (phase durations, per-worker report latency, bytes per codec,
  straggler counts), served by ``GET /telemetry/cycles/<id>``.
- :mod:`pygrid_tpu.telemetry.promtext` — a strict Prometheus
  text-format parser used by the scrape-validity tests (and handy for
  ops tooling).
- :mod:`pygrid_tpu.telemetry.loopclock` — a loop thread's time
  partitioned into named phases: seconds per phase on the bus, and one
  ``jax.profiler.TraceAnnotation`` per phase on the profiler's clock.
- :mod:`pygrid_tpu.telemetry.gcwatch` — the cyclic collector's pauses
  by generation (``process_gc_seconds``), a full collection also as a
  ``gc.full`` annotation on the profiler's clock.
- :mod:`pygrid_tpu.telemetry.profiler` — per-jit-callsite
  compile/execute timing (``GET /telemetry/programs``) and background
  device-memory gauges; off-switch ``PYGRID_PROFILER=off``.
- :mod:`pygrid_tpu.telemetry.recorder` — the flight recorder: a
  bounded ring of notable moments and redacted JSON crash dumps on
  engine failure / unhandled handler exceptions / operator request.
- :mod:`pygrid_tpu.telemetry.slo` — declarative burn-rate SLOs over
  the bus histograms (``GET /telemetry/slo``, the deep ``/healthz``).

Everything here must stay cheap enough to be ON by default: the hot
loop's budget is < 2% over the bare wire path
(``bench.bench_telemetry_overhead``).
"""

from __future__ import annotations

from pygrid_tpu.telemetry import (  # noqa: F401
    gcwatch,
    loopclock,
    profiler,
    recorder,
    slo,
    timeline,
    trace,
)
from pygrid_tpu.telemetry.bus import (  # noqa: F401
    BUS,
    Histogram,
    counters,
    events,
    histograms,
    incr,
    incr_many,
    observe,
    record,
    reset,
)
from pygrid_tpu.telemetry.trace import (  # noqa: F401
    TRACE_HEADER,
    TraceContext,
    current,
    span,
)


def export(exp) -> None:
    """Write every bus counter and histogram family into an
    :class:`pygrid_tpu.utils.metrics.Exposition` — the one exporter both
    the node and network ``/metrics`` routes call, so the exposed
    families cannot drift between the two apps."""
    from pygrid_tpu.serde import tensor_copy_count
    from pygrid_tpu.telemetry.bus import family_help

    for (name, labels), value in sorted(counters().items()):
        exp.counter(name, value, family_help(name), dict(labels))
    for (name, labels), snap in sorted(histograms().items()):
        exp.histogram(name, snap, family_help(name), dict(labels))
    exp.counter(
        "serde_tensor_copies_total",
        tensor_copy_count(),
        "tensor-buffer byte copies made by wire deserialization",
    )
    for generation, snap in gcwatch.snapshots().items():
        exp.histogram(
            gcwatch.FAMILY, snap, family_help(gcwatch.FAMILY),
            {"generation": str(generation)},
        )


def http_middleware():
    """aiohttp middleware shared by the node and network apps: adopts the
    ``X-PyGrid-Trace`` header (or synthesizes a root trace for legacy
    clients), and feeds the per-route request-latency histogram and
    status-code counter. WebSocket upgrades are counted but not timed —
    a connection's lifetime is not a request latency."""
    import time

    from aiohttp import web

    @web.middleware
    async def middleware(request, handler):
        incoming = trace.parse_header(
            request.headers.get(TRACE_HEADER, "")
        )
        route = "unmatched"
        resource = request.match_info.route.resource
        if resource is not None:
            route = resource.canonical
        t0 = time.monotonic()
        status = 500
        websocket = False
        with trace.serve(incoming):
            try:
                try:
                    response = await handler(request)
                except web.HTTPException as err:
                    # aiohttp signals router 404/405 (and handler
                    # redirects) by raising — that's the status the
                    # client sees, not a 500
                    status = err.status
                    raise
                status = response.status
                websocket = isinstance(response, web.WebSocketResponse)
                return response
            finally:
                incr(
                    "http_requests_total",
                    1,
                    route=route,
                    code=str(status),
                )
                if not websocket:
                    observe(
                        "http_request_seconds",
                        time.monotonic() - t0,
                        route=route,
                    )

    return middleware
