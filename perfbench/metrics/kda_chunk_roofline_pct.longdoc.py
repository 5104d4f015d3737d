"""The chunked delta-rule kernel's share of its roofline over the traced
stretch: the least time the chip could take for the positions the prefills
computed in it, over the seconds of ``kda_chunk*`` in the trace
(``ctx["trace"]["ops"]``). Positions: growth of
``serving_prefill_tokens_total{kind="padded"}`` between ``snap_t0`` and
``snap_t1`` (the kernel runs over a bucket's padding too, chunk by chunk),
times the cell's delta-rule layers. The least time is the larger of the
operations over the bf16 peak and the bytes over the HBM bandwidth, both a
position and layer from the configuration (``models/solar_open2.py``
``kda_chunk_flops``, ``kda_chunk_bytes``): the bytes bound it. None
untraced, and for a program without the kernel."""
from lib import spec
from lib.loopclock import delta
from lib.peaks import peak

CELL = "longdoc-saturate"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("snap_t0") or not ctx.get("snap_t1"):
        return None
    seconds = sum(
        v[0] for name, v in trace.get("ops", {}).items()
        if name.startswith("kda_chunk")
    )
    # the counter's growth over the TRACED stretch, not the window
    traced = dict(ctx, snap_w0=ctx["snap_t0"], snap_w1=ctx["snap_t1"])
    padded = delta(traced, "serving_prefill_tokens_total", 'kind="padded"')
    if not seconds or not padded:
        return None
    cfg = spec.cell(spec.load_benchmark(), CELL)["config"]
    model = spec.load_model(cfg["model_type"])
    p = peak(ctx.get("device_kind") or ctx["engine"]["device_kind"])
    positions = padded * model.n_kda(cfg)
    floor_s = max(
        positions * model.kda_chunk_flops(cfg) / p["bf16_flops_per_s"],
        positions * model.kda_chunk_bytes(cfg) / p["hbm_bytes_per_s"],
    )
    return 100.0 * floor_s / seconds
