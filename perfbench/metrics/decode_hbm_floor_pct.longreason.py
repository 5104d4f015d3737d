"""The decode steps' share of their HBM roofline on the host's clock: the
bytes the window's decode steps had to move, over the device's published
bandwidth, as a share of the seconds their dispatches took
(``serving_dispatch_seconds``). Bytes: every layer and the head, once a
step (the adapter's ``dense_step_bytes`` of the cell's configuration x
steps: per-step dispatches + ``serving_fused_steps_total``), and each live
row's state read once and written once
(``serving_dispatch_rowsteps_total{kind="live"}`` x the adapter's
``state_bytes_per_slot``, over the 8,256 monomials the mathematics needs,
twice). There are no K/V pages to read. The dispatch seconds contain the
device's time, so it cannot pass 100. None for a program without the
counters."""
from lib import spec
from lib.loopclock import delta, dispatches
from lib.peaks import peak

CELL = "longreason-saturate"


def read(ctx):
    engine = ctx.get("engine") or {}
    rows = delta(ctx, "serving_dispatch_rowsteps_total", 'kind="live"')
    seconds = dispatches(ctx)[0]
    # a program that hosts this family counts its prefills' chunks
    if rows is None or not seconds or delta(ctx, "serving_retention_chunks_total") is None:
        return None
    steps = dispatches(ctx, 'path="step"')[1] + (delta(ctx, "serving_fused_steps_total") or 0.0)
    cfg = spec.cell(spec.load_benchmark(), CELL)["config"]
    model = spec.load_model(cfg["model_type"])
    moved = steps * model.dense_step_bytes(cfg) + 2.0 * rows * model.state_bytes_per_slot(cfg)
    floor_s = moved / peak(ctx.get("device_kind") or engine["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / seconds
