"""The share of the device's bf16 peak that the whole of this cell's device
work reached, on the host's clock: the operations of every position the
window computed for a request (the prefills' TRUE prompt tokens,
``serving_prefill_tokens_total{kind="true"}``, and the decode steps' live
rows, ``serving_dispatch_rowsteps_total{kind="live"}``; the adapter's
``forward_flops_per_position`` of the cell's configuration: the same at
every context, the state does not grow) over the published peak, as a share
of the seconds the prefills and the dispatches took
(``serving_prefill_seconds`` + ``serving_dispatch_seconds``: no second is
counted twice, and with the device never idle they are its time). A
bucket's padding and a width's free rows are not counted. None for a
program without the counters."""
from lib import spec
from lib.loopclock import delta, dispatches
from lib.peaks import peak
from lib.readers import hist_delta

CELL = "longreason-saturate"


def read(ctx):
    engine = ctx.get("engine") or {}
    prompt = delta(ctx, "serving_prefill_tokens_total", 'kind="true"')
    rows = delta(ctx, "serving_dispatch_rowsteps_total", 'kind="live"')
    # a program that hosts this family counts its prefills' chunks
    if prompt is None or rows is None or delta(ctx, "serving_retention_chunks_total") is None:
        return None
    seconds = dispatches(ctx)[0] + hist_delta(
        ctx["snap_w0"], ctx["snap_w1"], "serving_prefill_seconds"
    )[0]
    if not seconds:
        return None
    cfg = spec.cell(spec.load_benchmark(), CELL)["config"]
    model = spec.load_model(cfg["model_type"])
    ops = (prompt + rows) * model.forward_flops_per_position(cfg)
    kind = ctx.get("device_kind") or engine["device_kind"]
    return 100.0 * ops / peak(kind)["bf16_flops_per_s"] / seconds
