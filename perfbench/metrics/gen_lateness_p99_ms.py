"""How late the generator sent the window's requests, 99th percentile, ms
(its own clock). A starved generator must not read as a fast server."""
from lib.stats import percentile


def read(ctx):
    late = ctx.get("late_ms")
    return percentile(late, 99) if late else None
