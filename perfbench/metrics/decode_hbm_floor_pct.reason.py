"""The decode steps' share of their HBM roofline on the host's clock: the
bytes the window's decode steps had to move, over the device's published
bandwidth, as a share of the seconds their dispatches took (program call to
tokens fetched, ``serving_dispatch_seconds``). Bytes: the weights once a
step (``weights_bytes`` of the engine's ``/telemetry/serving`` row x steps:
per-step dispatches + ``serving_fused_steps_total``), the live rows'
recurrent state read and written (``serving_state_bytes_total{kind=read}``
twice: a decode step writes what it read), and the K/V pages the attention
read (``serving_kv_pages_total{kind=read}`` x ``kv_block_bytes``). What the
adapter's ``decode_step_bytes`` counts from the configuration, summed from
what the program says it served. The dispatch seconds contain the device's
time, so it cannot pass 100. None for a program without the counters."""
from lib.loopclock import delta, dispatches
from lib.peaks import peak


def read(ctx):
    engine = ctx.get("engine") or {}
    state_read = delta(ctx, "serving_state_bytes_total", 'kind="read"')
    pages = delta(ctx, "serving_kv_pages_total", 'kind="read"')
    seconds = dispatches(ctx)[0]
    if "weights_bytes" not in engine or state_read is None or pages is None or not seconds:
        return None
    steps = dispatches(ctx, 'path="step"')[1] + (delta(ctx, "serving_fused_steps_total") or 0.0)
    moved = (
        steps * engine["weights_bytes"] + 2.0 * state_read
        + pages * engine["kv_block_bytes"]
    )
    floor_s = moved / peak(ctx.get("device_kind") or engine["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / seconds
