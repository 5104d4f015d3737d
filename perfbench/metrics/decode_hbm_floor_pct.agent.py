"""The decode steps' share of their HBM roofline on the host's clock, where
a step is expert weights and little else: the bytes the window's decode
steps had to move, over the device's published bandwidth, as a share of
the seconds their dispatches took (``serving_dispatch_seconds``). Bytes:
every layer outside its routed experts and the head, once a step (the
adapter's ``dense_step_bytes`` of the cell's configuration x steps:
per-step dispatches + ``serving_fused_steps_total``); the experts the
steps touched (``serving_expert_bytes_total`` of the paths ``step`` and
``fused``); the live rows' convolution state read and written
(``serving_state_bytes_total{kind=read}`` twice); the K/V pages the
attention read (``serving_kv_pages_total{kind=read}`` x ``kv_block_bytes``).
The dispatch seconds contain the device's time, so it cannot pass 100.
None for a program without the counters."""
from lib import spec
from lib.loopclock import delta, dispatches
from lib.peaks import peak

CELL = "agent-saturate"


def read(ctx):
    engine = ctx.get("engine") or {}
    state_read = delta(ctx, "serving_state_bytes_total", 'kind="read"')
    pages = delta(ctx, "serving_kv_pages_total", 'kind="read"')
    experts = delta(ctx, "serving_expert_bytes_total")
    seconds = dispatches(ctx)[0]
    if state_read is None or pages is None or experts is None or not seconds:
        return None
    experts -= delta(ctx, "serving_expert_bytes_total", 'path="prefill"') or 0.0
    steps = dispatches(ctx, 'path="step"')[1] + (delta(ctx, "serving_fused_steps_total") or 0.0)
    cfg = spec.cell(spec.load_benchmark(), CELL)["config"]
    dense = spec.load_model(cfg["model_type"]).dense_step_bytes(cfg)
    moved = (
        steps * dense + experts + 2.0 * state_read
        + pages * engine["kv_block_bytes"]
    )
    floor_s = moved / peak(ctx.get("device_kind") or engine["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / seconds
