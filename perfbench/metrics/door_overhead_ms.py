"""What the WS door adds around the handler: mean client time (sent to
answered) of the run's WS requests minus the mean of the node's
``node_event_seconds{event="run-generation"}`` over the same requests
(go to the end of the drain), ms."""
from lib.readers import hist_mean


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    ws = [r for r in ctx["results"] if r.get("ok") and r["door"] == "ws"]
    inside = hist_mean(ctx, "node_event_seconds", 1e3, ("snap_go", "snap_end"),
                       label="run-generation")
    if not ws or inside is None:
        return None
    return 1e3 * sum(r["done"] - r["sent"] for r in ws) / len(ws) - inside
