"""The grouped expert kernel's share of its roofline over the traced
stretch, where a chip holds every expert of its layers and prefills and
decode steps stand side by side: the least time for what the forwards
between ``snap_t0`` and ``snap_t1`` asked of it, over the seconds of
``grouped_expert_ffn*`` in the trace (``ctx["trace"]["ops"]``). The least
time is the larger of two floors: the bytes of the experts touched (growth
of ``serving_expert_bytes_total{kind="read"}``, prefills and steps alike)
over the HBM bandwidth, and the operations of the assignments made (growth
of ``serving_expert_rows_total{kind="held"}``: every assignment falls on a
held expert here; the adapter's ``expert_ffn_flops``) over the bf16 peak. A
decode step's ~4 rows an expert are bound by the bytes, a long prompt's
~260 by the operations. None untraced, and for a program without the
counters or the kernel."""
from lib import spec
from lib.loopclock import delta
from lib.peaks import peak

CELL = "agent-saturate"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("snap_t0") or not ctx.get("snap_t1"):
        return None
    seconds = sum(
        v[0] for name, v in trace.get("ops", {}).items()
        if name.startswith("grouped_expert_ffn")
    )
    # the counters' growth over the TRACED stretch, not the window
    traced = dict(ctx, snap_w0=ctx["snap_t0"], snap_w1=ctx["snap_t1"])
    moved = delta(traced, "serving_expert_bytes_total", 'kind="read"')
    rows = delta(traced, "serving_expert_rows_total", 'kind="held"')
    if not seconds or moved is None or rows is None:
        return None
    cfg = spec.cell(spec.load_benchmark(), CELL)["config"]
    model = spec.load_model(cfg["model_type"])
    p = peak(ctx.get("device_kind") or ctx["engine"]["device_kind"])
    floor_s = max(
        moved / p["hbm_bytes_per_s"],
        model.expert_ffn_flops(cfg, rows) / p["bf16_flops_per_s"],
    )
    return 100.0 * floor_s / seconds
