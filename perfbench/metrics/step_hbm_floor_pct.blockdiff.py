"""The block steps' share of their HBM roofline on the host's clock: the
bytes the window's block steps had to move over the device's published
bandwidth, as a share of the seconds their dispatches took (program call to
tokens fetched, ``serving_dispatch_seconds``). Bytes: every layer outside
its experts and the output head once a step (the adapter's
``dense_forward_bytes`` of the cell's configuration), the expert weights
the steps touched (``serving_expert_bytes_total{path="step"}``), and the K/V
pages the attention read (``serving_kv_pages_total{kind="read"}`` x
``kv_block_bytes``). The dispatch seconds contain the device's time, so it
cannot pass 100. None for a program without the counters."""
from lib import spec
from lib.loopclock import delta, dispatches
from lib.peaks import peak

CELL = "blockdiff-saturate"


def read(ctx):
    engine = ctx.get("engine") or {}
    experts = delta(ctx, "serving_expert_bytes_total", 'path="step"')
    pages = delta(ctx, "serving_kv_pages_total", 'kind="read"')
    seconds, steps = dispatches(ctx, 'path="step"')
    if experts is None or pages is None or not seconds or "kv_block_bytes" not in engine:
        return None
    cfg = spec.cell(spec.load_benchmark(), CELL)["config"]
    dense = spec.load_model(cfg["model_type"]).dense_forward_bytes(cfg)
    moved = steps * dense + experts + pages * engine["kv_block_bytes"]
    kind = ctx.get("device_kind") or engine["device_kind"]
    return 100.0 * moved / peak(kind)["hbm_bytes_per_s"] / seconds
