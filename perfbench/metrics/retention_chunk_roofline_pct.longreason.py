"""The chunked retention kernel's share of its roofline over the traced
stretch: the least time the chip could take for the chunks the prefills
ran in it, over the seconds of ``retention_chunk*`` in the trace
(``ctx["trace"]["ops"]``). Chunks: growth of
``serving_retention_chunks_total{kind="computed"}`` between ``snap_t0`` and
``snap_t1`` (what the program says its kernel ran: a chunk that holds
nothing but a bucket's padding is skipped), of ``CHUNK`` positions each,
times the cell's layers. The least time is the larger of the operations
over the bf16 peak and the bytes over the HBM bandwidth, both a position
and layer from the configuration (``models/brumby.py``
``retention_chunk_flops``, ``retention_chunk_bytes``, over the 8,256
monomials the mathematics needs): the operations bound it. None untraced,
and for a program without the kernel or the counter."""
from lib import spec
from lib.loopclock import delta
from lib.peaks import peak

CELL = "longreason-saturate"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("snap_t0") or not ctx.get("snap_t1"):
        return None
    seconds = sum(
        v[0] for name, v in trace.get("ops", {}).items()
        if name.startswith("retention_chunk")
    )
    # the counter's growth over the TRACED stretch, not the window
    traced = dict(ctx, snap_w0=ctx["snap_t0"], snap_w1=ctx["snap_t1"])
    chunks = delta(traced, "serving_retention_chunks_total", 'kind="computed"')
    if not seconds or not chunks:
        return None
    cfg = spec.cell(spec.load_benchmark(), CELL)["config"]
    model = spec.load_model(cfg["model_type"])
    p = peak(ctx.get("device_kind") or ctx["engine"]["device_kind"])
    positions = chunks * model.CHUNK * cfg["num_hidden_layers"]
    floor_s = max(
        positions * model.retention_chunk_flops(cfg) / p["bf16_flops_per_s"],
        positions * model.retention_chunk_bytes(cfg) / p["hbm_bytes_per_s"],
    )
    return 100.0 * floor_s / seconds
