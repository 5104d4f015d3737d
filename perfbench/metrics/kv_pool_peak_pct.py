"""Most of the KV pool's blocks in use at once during the window
(``/telemetry/serving`` sampled each second), 0-100 of the usable blocks."""


def read(ctx):
    row = ctx.get("engine")
    if not row or not row.get("kv_blocks_total"):
        return None
    total = row["kv_blocks_total"]
    return 100.0 * (total - ctx["kv_blocks_free_min"]) / total
