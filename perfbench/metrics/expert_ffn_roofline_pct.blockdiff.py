"""The grouped expert kernel's share of its HBM roofline over the traced
stretch: the bytes of expert weights the forwards had to read (growth of
``serving_expert_bytes_total{kind="read"}`` between ``snap_t0`` and
``snap_t1``: touched (layer, expert) pairs x one expert's three matrices,
prefills and block steps alike; ``models/sdar_moe.py`` ``expert_ffn_bytes``
is the same count from the configuration) over the device's published
bandwidth, as a share of the seconds of ``grouped_expert_ffn`` in the
trace (``ctx["trace"]["ops"]``). Memory bounds it: at ~16 rows an expert
the kernel's operations are a fortieth of the peak's for those bytes. None
untraced, and for a program without the counter or the kernel."""
from lib.peaks import peak
from lib.readers import counter_delta

BYTES = "serving_expert_bytes_total"
TRACED = ("snap_t0", "snap_t1")


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not all(ctx.get(s) for s in TRACED):
        return None
    if not any(k.split("{")[0].endswith(BYTES) for k in ctx["snap_t1"]):
        return None
    seconds = sum(
        v[0] for name, v in trace.get("ops", {}).items()
        if name.startswith("grouped_expert_ffn")
    )
    if not seconds:
        return None
    moved = counter_delta(ctx, BYTES, TRACED)  # its only kind is "read"
    kind = ctx.get("device_kind") or ctx["engine"]["device_kind"]
    return 100.0 * moved / peak(kind)["hbm_bytes_per_s"] / seconds
