"""What the per-layer readers share: each ``metrics/<name>.py`` is a few
lines over these. A helper returns None where there is nothing to read."""

from __future__ import annotations


def hist_delta(a: dict, b: dict, name: str, label: str = "") -> tuple[float, float]:
    """(sum, count) that the histogram ``name`` gained between two scrapes
    of ``/metrics``; ``label`` selects series whose label set contains it."""
    def total(snap, suffix):
        return sum(
            v for k, v in snap.items()
            if isinstance(v, float)
            and k.split("{")[0].endswith(name + suffix) and label in k
        )
    return (
        total(b, "_sum") - total(a, "_sum"),
        total(b, "_count") - total(a, "_count"),
    )


def hist_mean(ctx: dict, name: str, scale: float = 1.0, span=("snap_w0", "snap_w1"),
              label: str = ""):
    """Mean of the node's histogram ``name`` over the window (or another
    pair of scrapes), times ``scale``."""
    if ctx.get("kind") != "serve":
        return None
    total, count = hist_delta(ctx[span[0]], ctx[span[1]], name, label)
    return scale * total / count if count else None


def counter_delta(ctx: dict, name: str, span=("snap_w0", "snap_w1")):
    """Growth of the counter(s) ``name`` (all label sets) over the window."""
    def total(snap):
        return sum(
            v for k, v in snap.items()
            if isinstance(v, float) and k.split("{")[0].endswith(name)
        )
    return total(ctx[span[1]]) - total(ctx[span[0]])


def idle_pct(ctx: dict):
    trace = ctx.get("trace")
    return None if not trace else trace["idle_pct"]


def hbm_peak_gb(ctx: dict):
    peak = ctx.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9


def memory_peak(stats: dict):
    """The device's peak as JAX reports it: the most its arrays held
    (``peak_bytes_in_use``) plus the most the runtime reserved for compiled
    programs' temporaries (``peak_bytes_reserved``) - a training step's
    10 GB of activations are in the second and not in the first."""
    if "peak_bytes_in_use" not in stats:
        return None
    return int(stats["peak_bytes_in_use"]) + int(stats.get("peak_bytes_reserved", 0))
