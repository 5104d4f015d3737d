"""From a profiler trace to device busy time, the operations that took
most of it, and the idle gaps. Reads the ``.xplane.pb`` that
``jax.profiler`` writes, with ``jax.profiler.ProfileData`` alone."""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

#: planes of accelerator devices, e.g. "/device:TPU:0"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
#: the line of a device plane that carries one event per executed operation
OPS_LINE = "XLA Ops"
#: control-flow operations span the operations of their bodies: they count
#: as busy time (the device runs the loop) but not as an operation's own
CONTAINERS = re.compile(r"^%?(while|conditional|call)([.\d]*)?($|[ =(])")


def device_events(xplane_path: str | Path) -> dict[str, list[tuple[str, float, float]]]:
    """``{device plane: [(operation, start_s, duration_s), ...]}`` for each
    accelerator plane of the trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane_path))
    out: dict[str, list[tuple[str, float, float]]] = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                events.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
        out[plane.name] = events
    return out


def find_xplane(trace_dir: str | Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def merge(events) -> list[tuple[float, float, str]]:
    """Union of the events' intervals: sorted, disjoint ``(start, end,
    last operation)``; the last operation is the one that ends the busy
    stretch, which the gap after it is attributed to."""
    merged: list[list] = []
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
                merged[-1][2] = name
        else:
            merged.append([start, end, name])
    return [tuple(m) for m in merged]


_SHAPE = re.compile(r"^\(?([a-z]+[0-9]*)\[([0-9,]*)\]")


def short_name(name: str) -> str:
    """An operation's kind and, where the trace gives it, the type and
    shape of its (first) result, without its instance number:
    ``%fusion.12 = bf16[8,16]{1,0} fusion(...)`` becomes
    ``fusion_bf16_8_16`` and ``convert.325.remat`` becomes ``convert.remat``,
    so that instances of one kind and shape add up."""
    head, _, rest = name.partition(" = ")
    kind = re.sub(r"\.\d+", "", head.strip().lstrip("%")) or head
    shape = _SHAPE.match(rest.strip())
    if not shape:
        return kind
    dims = shape.group(2).replace(",", "_")
    return f"{kind}_{shape.group(1)}" + (f"_{dims}" if dims else "")


def reduce(per_device: dict, window_s: float, top: int = 10) -> dict:
    """Busy seconds (the union of operation intervals, averaged over the
    devices), and the breakdown: the ``top`` operations by summed time and
    the ``top`` idle-gap groups by the operation before the gap, both taken
    over all devices. ``ops`` is the table the operations were ranked
    from, whole: ``{short name: [seconds, count]}`` for every device
    operation of the traced stretch, for a reader of one kernel's time."""
    if not per_device:
        raise ValueError("the trace has no accelerator plane")
    busy, gap_time = [], defaultdict(float)
    ops: dict = defaultdict(lambda: [0.0, 0])
    for events in per_device.values():
        merged = merge(events)
        busy.append(sum(end - start for start, end, _ in merged))
        for name, _start, dur in events:
            if not CONTAINERS.match(name):
                entry = ops[short_name(name)]
                entry[0] += dur
                entry[1] += 1
        for (_, end, last), (start, _, _) in zip(merged, merged[1:]):
            gap_time["after:" + short_name(last)] += start - end
    busy_s = sum(busy) / len(busy)

    def ranked(table):
        return [
            [k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:top]
        ]

    return {
        "busy_s": busy_s,
        "window_s": float(window_s),
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "breakdown": {
            "device_ops": ranked({k: v[0] for k, v in ops.items()}),
            "idle_gaps": ranked(gap_time),
        },
        "ops": dict(ops),
    }


class CompileCounter:
    """Counts the programs this process builds from now on, compiled or
    loaded from the persistent cache (JAX reports both under one event):
    inside a measured window the count has to stay where it was."""

    def __init__(self) -> None:
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, _duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Tracer:
    """``jax.profiler`` around a stretch of the window, in the process that
    holds the chip. ``start`` and ``stop`` take the host clock, so the
    traced window's length is what the host saw."""

    def __init__(self, out_dir: str | Path) -> None:
        self.out_dir = Path(out_dir)
        self.window_s = 0.0
        self._t0 = None

    def start(self) -> None:
        import time

        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(self.out_dir), profiler_options=options)
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        import time

        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()

    def result(self) -> dict:
        return reduce(device_events(find_xplane(self.out_dir)), self.window_s)
